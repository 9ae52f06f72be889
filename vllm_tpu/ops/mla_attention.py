"""Multi-head Latent Attention (MLA) over the paged cache.

Reference analog: ``csrc/attention/mla/`` decode kernels +
``vllm/model_executor/layers/attention/mla_attention.py:318`` and the
``MLAAttentionSpec`` cache contract (``vllm/v1/kv_cache_interface.py:323``).

MLA caches ONE latent row per token per layer instead of per-head K/V:
``latent = [c_kv (kv_lora_rank) || k_pe (qk_rope_head_dim)]`` — e.g.
512+64=576 numbers vs 2*KH*Dh for MHA, an ~10-50x KV-memory cut, which is
the whole point of the scheme (DeepSeek-V2, arXiv:2405.04434).

The TPU formulation runs fully *absorbed* for both prefill and decode:

- queries are mapped into latent space once per step
  (``q_lat = q_nope @ W_uk``), giving ``q_abs = [q_lat || q_pe]`` of width
  ``kv_lora_rank + rope_dim`` per head;
- attention scores are plain dot products against the cached latent rows
  (MQA shape: ONE shared "KV head");
- the context value is ``probs @ c_kv`` — i.e. the first ``kv_lora_rank``
  lanes of the cached row — mapped back per head by W_uv *outside* this op
  (absorbed into the output projection by the model).

This keeps the cache minimal and needs no K/V re-expansion for chunked
prefill: the absorbed math is exact at every query position. The CUDA
reference instead materializes full per-head K/V for prefill and uses
separate decode kernels (flashmla/cutlass_mla); on TPU one ragged gather
formulation covers both, and XLA fuses the surrounding einsums.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from vllm_tpu.ops.attention import AttentionMetadata


def mla_kv_cache_shape(
    num_layers: int, num_blocks: int, block_size: int, latent_dim: int
) -> tuple[int, int, int, int]:
    """[L, NB, BS, lanes]: one latent row a token, no K/V planes and no
    head axis. A page is ``[BS, lanes]`` with the tokens on the sublanes
    (an axis of one head there is padded to a whole sublane tile on the
    TPU, in HBM too, and the kernel's page copy cannot slice it) and the
    row padded with zeros to whole 128-lane tiles, which is what the
    device would store anyway: 576 numbers lie in 640."""
    return (num_layers, num_blocks, block_size, -(-latent_dim // 128) * 128)


def _to_lanes(x: jnp.ndarray, lanes: int) -> jnp.ndarray:
    pad = lanes - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def write_latent(
    kv_cache: jnp.ndarray,  # [L, NB, BS, DL]
    layer: jnp.ndarray,  # scalar i32
    latent: jnp.ndarray,  # [T, DL]  (c_kv || k_pe, rope already applied)
    slot_mapping: jnp.ndarray,  # [T]
) -> jnp.ndarray:
    """Scatter this step's latent rows into the paged slots (in place when
    the cache is a donated scan carry)."""
    nl, nb, bs, dl = kv_cache.shape
    flat = kv_cache.reshape(nl * nb * bs, dl)
    flat = flat.at[layer * (nb * bs) + slot_mapping].set(
        _to_lanes(latent, dl).astype(kv_cache.dtype)
    )
    return flat.reshape(nl, nb, bs, dl)


def mla_paged_attention(
    q_abs: jnp.ndarray,  # [T, H, DL] absorbed queries (q_lat || q_pe)
    kv_cache: jnp.ndarray,  # [L, NB, BS, DL]
    layer: jnp.ndarray,  # scalar i32
    md: AttentionMetadata,
    scale: float,
    value_dim: int,  # = kv_lora_rank: lanes of the latent that act as V
) -> jnp.ndarray:
    """Ragged causal attention in latent space -> [T, H, value_dim].

    MQA structure (one shared latent row per position); the per-head value
    up-projection W_uv is applied by the caller. On TPU (or under
    VLLM_TPU_PALLAS_INTERPRET off-TPU) this routes to the Pallas MLA
    kernel (``ops/mla_kernel.py``: rpa fork with kh=1, score width DL,
    value width ``value_dim`` — streams pages through VMEM); the XLA
    gather below is the reference path, which materializes ``[T, C, DL]``
    and only survives short contexts.
    """
    nl, nb, bs, dl = kv_cache.shape
    q_abs = _to_lanes(q_abs, dl)  # zeros against the rows' zero lanes
    t, h, _ = q_abs.shape

    from vllm_tpu import envs

    on_tpu = jax.default_backend() == "tpu"
    interpret = bool(envs.VLLM_TPU_PALLAS_INTERPRET) and not on_tpu
    if (on_tpu or interpret) and not envs.VLLM_TPU_DISABLE_PALLAS:
        from vllm_tpu.ops.mla_kernel import mla_ragged_paged_attention

        return mla_ragged_paged_attention(
            q_abs,
            kv_cache,
            jnp.asarray(layer, jnp.int32).reshape(1),
            md.seq_lens,
            md.block_tables,
            md.query_start_loc,
            md.num_seqs,
            sm_scale=scale,
            value_dim=value_dim,
            interpret=interpret,
        )

    pages = kv_cache[layer, md.block_tables]  # [R, B, BS, DL]
    r, b = md.block_tables.shape
    ctx = b * bs
    lat_req = pages.reshape(r, ctx, dl)
    lat_t = lat_req[md.token_req_idx].astype(jnp.float32)  # [T, C, DL]

    qf = q_abs.astype(jnp.float32)
    scores = jnp.einsum("thd,tcd->thc", qf, lat_t) * scale

    local = jnp.arange(ctx, dtype=jnp.int32)[None, :]
    causal = local <= md.positions[:, None]  # [T, C]
    scores = jnp.where(causal[:, None, :], scores, -jnp.inf)

    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)  # padding rows
    out = jnp.einsum("thc,tcd->thd", probs, lat_t[..., :value_dim])
    return out.astype(q_abs.dtype)
