"""Paged attention over an HBM block table.

This replaces the reference's CUDA paged attention + KV insert pipeline
(``csrc/attention/paged_attention_v1/v2.cu``, ``reshape_and_cache_flash`` in
``csrc/cache_kernels.cu``) with a TPU-native design:

- ONE ragged layout for prefill and decode alike: the step processes a flat
  ``[T]`` token batch spanning all scheduled requests (chunked prefills and
  single-token decodes mixed), exactly like the reference's unified v1
  scheduler feeds its workers.
- The KV cache is ONE donated buffer ``[L, NB, BS, 2*KH, D]`` carried
  through the model's layer scan — every op here takes the full cache plus
  a layer index, so XLA updates it in place (scanning per-layer slices
  instead would double-buffer the cache and copy a full layer per step).
- KV insert is a static-shape scatter into the paged cache via a per-token
  ``slot_mapping``; padded tokens target slot 0 (the null block, a
  write-only garbage page — never read).
- ``ref_ragged_paged_attention`` is pure XLA (gather + masked softmax),
  correct on any backend and used for CPU tests; ``ops/rpa_kernel.py`` is
  the in-repo Pallas flash kernel with identical semantics (the TPU fast
  path).

K/V heads are INTERLEAVED on axis 3 (``0::2`` = K, ``1::2`` = V) so one
block's per-head K,V pair is contiguous — the layout the flash kernel DMAs
per block-table entry.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclass
class AttentionMetadata:
    """Device-side per-step attention inputs (all padded to bucket sizes).

    Shapes: T = padded token count, R = padded request count,
    B = padded blocks-per-request.
    """

    positions: jnp.ndarray  # [T] i32, position of each token in its sequence
    slot_mapping: jnp.ndarray  # [T] i32, flat cache slot = block_id*bs + off
    block_tables: jnp.ndarray  # [R, B] i32
    seq_lens: jnp.ndarray  # [R] i32, context length incl. this step's tokens
    query_start_loc: jnp.ndarray  # [R+1] i32, ragged row offsets into [T]
    token_req_idx: jnp.ndarray  # [T] i32, owning request row per token
    # [R] i32: index into [T] of each request's last scheduled token (rows
    # beyond the live request count point at 0 and are masked downstream).
    logits_indices: jnp.ndarray
    num_seqs: jnp.ndarray  # [1] i32, live (unpadded) request count
    # Cascade attention (reference: ``gpu_model_runner.py:2367`` +
    # ``merge_attn_states.cu``): when every live request shares this many
    # leading block-table entries, attention over that common prefix is
    # computed once (no per-token KV duplication) and LSE-merged with the
    # per-request suffix. STATIC (part of the jit signature; the runner
    # buckets it to bound trace count).
    num_common_prefix_blocks: int = field(
        default=0, metadata=dict(static=True)
    )
    # True when EVERY live row of this step is a single-position decode
    # (one scheduled token per request, token i belongs to row i, so
    # T == R). Unlocks the decode-specialized sequence-pipelined kernel
    # (``ops/rpa_decode_kernel.py``). STATIC: dispatch happens inside
    # jit, and the runner forces ``t_pad == r_pad`` when setting it, so
    # the extra trace count is bounded by the request buckets.
    decode_only: bool = field(default=False, metadata=dict(static=True))
    # [R] i32, for a decode-only step: the order the decode kernel serves
    # the rows in (``ops/rpa_decode_kernel.py::decode_row_order``: live
    # rows longest first, so that a block's rows end together), computed
    # once a step where the metadata is built, not in the layers. None =
    # the rows as given.
    decode_order: jnp.ndarray | None = None
    # The runner's device mesh when it has one (else None). STATIC. A
    # Mosaic kernel cannot be partitioned by GSPMD, so under tp > 1 the
    # dispatch runs the Pallas kernels per tp shard inside a shard_map
    # (``_call_attention_kernel``): q is sharded over heads and the cache
    # over its KV-head rows, as every model's ``kv_cache_sharding`` lays
    # them out.
    tp_mesh: Any = field(default=None, metadata=dict(static=True))
    # Attention causal over BLOCKS of this many positions (generation by
    # diffusion over blocks): a query sees every position j with
    # ``j // block_len <= pos // block_len``, so whole blocks to its left
    # and its own block in both directions. 1 = plain causal. STATIC; a
    # row's query tokens always cover whole blocks.
    block_len: int = field(default=1, metadata=dict(static=True))
    # Hybrid attention+SSM models (Jamba/Bamba-class): per-request state
    # slot for the constant-size Mamba caches ([R] i32; None for pure
    # attention models). Reference: HybridKVCacheCoordinator per-type
    # groups (``kv_cache_coordinator.py:392``).
    state_slots: jnp.ndarray | None = None
    # Tree-attention spec verification (reference: tree_attn.py:255 tree
    # bias). When set, this step's tokens are per-request draft-tree
    # WINDOWS of static width W: ``tree_mask [T, W]`` bool says which of
    # its own row's window tokens each query attends (ancestors + self),
    # ``tree_window_start [T]`` is the stream index of the row's window
    # start, and ``tree_paged`` is a pseudo-sequence view (one query per
    # token, kv_len = committed context) for the paged-context part. See
    # ``tree_verify_attention``.
    tree_mask: jnp.ndarray | None = None
    tree_window_start: jnp.ndarray | None = None
    tree_paged: "AttentionMetadata | None" = None

    def live_tokens(self) -> jnp.ndarray:
        """[T] bool: the step's tokens that are not padding."""
        return (
            jnp.arange(self.positions.shape[0])
            < self.query_start_loc[self.num_seqs[0]]
        )


def packed_kv_layout(head_dim: int) -> bool:
    """True when K/V pair-pack on the lane axis instead of interleaving
    heads. head_dim below the 128-lane tile (64) cannot be DMA'd or
    memref-sliced by Mosaic, so such models store ``[.., KH, 2*D]`` rows
    (k||v contiguous, a full 128-lane tile for D=64)."""
    return head_dim % 128 != 0


def kv_cache_shape(
    num_layers: int, num_blocks: int, block_size: int, num_kv_heads: int,
    head_dim: int,
) -> tuple[int, int, int, int, int]:
    """Framework-wide KV cache geometry (one donated 5-D buffer)."""
    if packed_kv_layout(head_dim):
        return (num_layers, num_blocks, block_size, num_kv_heads, 2 * head_dim)
    return (num_layers, num_blocks, block_size, 2 * num_kv_heads, head_dim)


def split_kv_lanes(head_dim: int, v_head_dim: int) -> int:
    """Lanes of one row of the SPLIT layout (a key and a value of unlike
    widths): the key padded with zeros to whole 128-lane tiles, then the
    value. A key of 192 and a value of 128 give 256 + 128."""
    return -(-head_dim // 128) * 128 + v_head_dim


def split_kv_cache_shape(
    num_layers: int, num_blocks: int, block_size: int, num_kv_heads: int,
    head_dim: int, v_head_dim: int, dtype=jnp.bfloat16,
) -> tuple[int, int, int, int]:
    """Geometry of a cache whose key and value heads differ in width: one
    donated 4-D buffer ``[L, NB, KH * BS, Dk_pad + Dv]``, a page's rows
    HEAD-MAJOR (row ``h * BS + offset``), so that a KV head's tokens are
    whole sublane tiles, a tp shard's heads are contiguous rows, and kinds
    of layer with ``KH * BS`` in common (8 heads of 16 tokens, 4 of 32)
    share ONE array: ``write_kv`` and the attention ops view it as
    ``[L, NB, KH, BS, lanes]`` by the K/V head count of the layer they
    serve. ``block_size`` must fill whole sublane tiles of ``dtype`` on
    the TPU (16 tokens of bf16, 32 of fp8), or a page would pad there."""
    tile = 8 * 4 // jnp.dtype(dtype).itemsize
    if jax.default_backend() == "tpu" and block_size % tile:
        raise ValueError(
            f"a page of {block_size} tokens pads to {tile}-row sublane "
            f"tiles of {jnp.dtype(dtype).name} in the split KV layout: "
            f"use a block_size that is a multiple of {tile}")
    return (num_layers, num_blocks, num_kv_heads * block_size,
            split_kv_lanes(head_dim, v_head_dim))


def _split_view(kv_cache: jnp.ndarray, num_kv_heads: int) -> jnp.ndarray:
    """``[L, NB, KH * BS, lanes]`` seen as ``[L, NB, KH, BS, lanes]``."""
    nl, nb, rows, lanes = kv_cache.shape
    assert rows % num_kv_heads == 0, (kv_cache.shape, num_kv_heads)
    return kv_cache.reshape(
        nl, nb, num_kv_heads, rows // num_kv_heads, lanes)


def kv_dequant_scale(kv_cache) -> float | None:
    """Dequant scale for quantized (fp8) KV pages: values are cast, not
    scaled, on insert, so the scale is 1.0; None = no dequant needed."""
    if kv_cache.dtype in (jnp.float8_e4m3fn, jnp.float8_e5m2):
        return 1.0
    return None


def write_kv(
    kv_cache: jnp.ndarray,  # [L, NB, BS, 2*KH, D] or packed [L, NB, BS, KH, 2D]
    layer: jnp.ndarray,  # scalar i32
    k: jnp.ndarray,  # [T, KH, D]
    v: jnp.ndarray,  # [T, KH, D]
    slot_mapping: jnp.ndarray,  # [T]
) -> jnp.ndarray:
    """Scatter this step's K/V into layer `layer`'s paged slots (in place
    when the cache is a donated scan carry). A 4-D cache is the split
    layout (``split_kv_cache_shape``): ``k [T, KH, Dk]`` and ``v [T, KH,
    Dv]`` go to row ``h * BS + offset`` of their page, the key's padding
    lanes written as zeros, ``BS`` read off the cache and ``KH``."""
    if kv_cache.ndim == 4:
        nl, nb, rows, lanes = kv_cache.shape
        t, kh, d = k.shape
        bs = rows // kh
        pad = lanes - d - v.shape[-1]
        kv_new = jnp.concatenate(
            [k, jnp.zeros((t, kh, pad), k.dtype), v], axis=-1)
        row = ((layer * nb + slot_mapping // bs)[:, None] * kh
               + jnp.arange(kh, dtype=jnp.int32)[None, :]) * bs + (
            slot_mapping % bs)[:, None]  # [T, KH]
        flat = kv_cache.reshape(nl * nb * rows, lanes)
        flat = flat.at[row].set(kv_new.astype(kv_cache.dtype))
        return flat.reshape(kv_cache.shape)
    nl, nb, bs, rows, lanes = kv_cache.shape
    t, kh, d = k.shape
    if packed_kv_layout(d):
        # [T, KH, 2D]: k||v per head on the lane axis.
        kv_new = jnp.concatenate([k, v], axis=-1)
    else:
        # [T, KH, 2, D] -> [T, 2KH, D] gives k0,v0,k1,v1,... along axis 1.
        kv_new = jnp.stack([k, v], axis=2).reshape(t, rows, lanes)
    flat = kv_cache.reshape(nl * nb * bs, rows, lanes)
    flat = flat.at[layer * (nb * bs) + slot_mapping].set(
        kv_new.astype(kv_cache.dtype)
    )
    return flat.reshape(nl, nb, bs, rows, lanes)


def paged_attention(
    q: jnp.ndarray,
    kv_cache: jnp.ndarray,  # [L, NB, BS, 2*KH, D]
    layer: jnp.ndarray,  # scalar i32
    md: AttentionMetadata,
    scale: float,
    *,
    sliding_window=None,
    soft_cap: float | None = None,
    k_scale: float | None = None,
    v_scale: float | None = None,
    kernel_suffix: str = "",
    sinks: jnp.ndarray | None = None,
    num_kv_heads: int | None = None,
) -> jnp.ndarray:
    """Backend dispatcher: in-repo Pallas flash kernel on TPU, XLA reference
    elsewhere (and under VLLM_TPU_DISABLE_PALLAS). ``kernel_suffix`` is
    appended to the kernel's name in a trace (``rpa_kernel_window``): a
    model whose layers are of two kinds tells their calls apart by it.

    ``sinks [H]`` float32: a learned logit a q head that joins every row's
    softmax denominator and has no value (``p_ij = exp(s_ij) / (exp(sink)
    + sum_k exp(s_ik))``), inside both kernels as their starting state. A
    4-D ``kv_cache`` is the split layout (``split_kv_cache_shape``) and
    needs ``num_kv_heads``; the output then has the value's width."""
    import vllm_tpu.envs as envs

    if sinks is not None or kv_cache.ndim == 4:
        assert md.tree_mask is None and md.num_common_prefix_blocks == 0, (
            "tree verification and cascade attention have no form with a "
            "sink or with unlike key and value widths")
        return dispatch_ragged_attention(
            q, kv_cache, layer, md, scale, sliding_window=sliding_window,
            soft_cap=soft_cap, k_scale=k_scale, v_scale=v_scale,
            kernel_suffix=kernel_suffix, sinks=sinks,
            num_kv_heads=num_kv_heads,
        )
    if md.tree_mask is not None:
        # Tree-verification step: ancestor-masked window + paged context.
        # Single choke point for the no-sliding-window contract — the
        # window floor is undefined for tree positions, so silently
        # dropping the argument would compute full attention on windowed
        # layers (runner init also rejects known windowed models early).
        assert sliding_window is None, (
            "tree spec verification does not support sliding-window "
            "attention"
        )
        return tree_verify_attention(
            q, kv_cache, layer, md, scale,
            soft_cap=soft_cap, k_scale=k_scale, v_scale=v_scale,
        )
    if md.num_common_prefix_blocks > 0:
        # Shared-prefix decode: XLA cascade formulation (a cascade-aware
        # Pallas kernel is the optimization seam).
        return cascade_ref_attention(
            q, kv_cache, layer, md, scale, sliding_window=sliding_window,
            soft_cap=soft_cap, k_scale=k_scale, v_scale=v_scale,
        )
    return dispatch_ragged_attention(
        q, kv_cache, layer, md, scale, sliding_window=sliding_window,
        soft_cap=soft_cap, k_scale=k_scale, v_scale=v_scale,
        kernel_suffix=kernel_suffix,
    )


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _call_attention_kernel(kernel, mesh, q, kv_cache, replicated, traced_kw,
                           return_lse: bool = False, sinks=None,
                           head_major: bool = False):
    """Run a Pallas attention kernel; per tp shard when ``mesh`` has
    tp > 1. Each shard sees its own q heads ``[T, H/tp, D]`` and its own
    rows of the cache ``[L, NB, BS, rows/tp, lanes]`` (K/V of one head are
    adjacent rows, or one packed row, so a head never straddles shards),
    or its own heads of the split layout's view ``[L, NB, KH/tp, BS,
    lanes]`` (``head_major``), and its own heads' ``sinks``; the row
    metadata and the traced scalars are replicated."""
    from jax.sharding import PartitionSpec as P

    from vllm_tpu.parallel.mesh import AXIS_TP, shard_map

    tp = 1 if mesh is None else mesh.shape[AXIS_TP]
    if sinks is not None:
        plain = kernel
        kernel = lambda q, kv, sinks, *rep, **kw: plain(
            q, kv, *rep, sinks=sinks, **kw)
        replicated = (sinks, *replicated)
    if tp == 1:
        return kernel(q, kv_cache, *replicated, **traced_kw)
    if head_major:
        num_kv_heads, cache_spec = kv_cache.shape[2], P(
            None, None, AXIS_TP, None, None)
    else:
        num_kv_heads = kv_cache.shape[3] // (
            1 if packed_kv_layout(q.shape[-1]) else 2)
        cache_spec = P(None, None, None, AXIS_TP, None)
    if q.shape[1] % tp or num_kv_heads % tp:
        raise ValueError(
            f"tensor parallel size {tp} must divide the q heads "
            f"({q.shape[1]}) and the KV heads ({num_kv_heads}) for the "
            "Pallas attention kernels"
        )
    # Python ints (a static window, the unstriped ctx view) become arrays:
    # shard_map takes array arguments only.
    traced_kw = {
        k: None if v is None else jnp.asarray(v, jnp.int32)
        for k, v in traced_kw.items()
    }
    heads = P(None, AXIS_TP, None)
    rep_specs = P()
    if sinks is not None:
        rep_specs = (P(AXIS_TP), *[P()] * (len(replicated) - 1))
    return shard_map(
        lambda q, kv, rep, kw: kernel(q, kv, *rep, **kw),
        mesh=mesh,
        in_specs=(heads, cache_spec, rep_specs, P()),
        out_specs=(heads, P(None, AXIS_TP)) if return_lse else heads,
        # Mosaic wants every mesh axis manual, not only tp: take all that
        # an enclosing shard_map (context parallelism) has not taken.
        axis_names=set(mesh.axis_names)
        - set(jax.sharding.get_abstract_mesh().manual_axes),
    )(q, kv_cache, replicated, traced_kw)


def _pallas_ok(head_dim: int, split: bool = False) -> bool:
    """Kernels on, and a layout the attention kernels have: K and V of one
    ``head_dim`` of 64 (packed on the lane axis), 128 or 256 (interleaved
    rows); or the split layout, whose key the dispatch has padded to whole
    lane tiles already, whatever the value's width."""
    import vllm_tpu.envs as envs

    return not envs.VLLM_TPU_DISABLE_PALLAS and (
        head_dim % 128 == 0 if split else head_dim in (64, 128, 256))


def ragged_kernel_tiling(
    num_q_heads: int, num_kv_heads: int, head_dim: int, page_size: int,
    q_dtype, kv_dtype, num_q_tokens: int, pages_per_seq: int, *,
    block_len: int = 1, tp_size: int = 1, split: bool = False,
):
    """The ``RaggedTiling`` ``dispatch_ragged_attention`` runs a step of
    these shapes with where the step is not decode-only, or None where it
    takes the XLA reference (no TPU, kernels off, a head_dim the kernel
    has no layout for): for the runner's count of the kernel's work."""
    from vllm_tpu.ops.rpa_kernel import ragged_tiling

    if not (_pallas_ok(head_dim, split) and _on_tpu()):
        return None
    return ragged_tiling(
        num_q_heads // tp_size, 2 * num_kv_heads // tp_size, head_dim,
        page_size, q_dtype, kv_dtype, num_q_tokens, pages_per_seq,
        block_len=block_len,
    )


def _decode_block_overrides() -> dict:
    """The decode kernel's block sizes where the environment names them."""
    import vllm_tpu.envs as envs

    kw = {}
    if envs.VLLM_TPU_DECODE_SEQS_PER_BLOCK > 0:
        kw["num_seqs_per_block"] = envs.VLLM_TPU_DECODE_SEQS_PER_BLOCK
    if envs.VLLM_TPU_DECODE_KV_PAGES_PER_BLOCK > 0:
        kw["num_kv_pages_per_block"] = envs.VLLM_TPU_DECODE_KV_PAGES_PER_BLOCK
    return kw


def decode_kernel_tiling(
    head_dim: int, page_size: int, num_rows: int, pages_per_seq: int, *,
    split: bool = False,
):
    """(rows a block, pages a tile) ``dispatch_ragged_attention`` runs a
    decode-only step's plain call (no window, no LSE) of these shapes with,
    or None where that call is not the decode kernel's (no TPU, kernels
    off, the escape hatch): for the runner's count of the kernel's work."""
    import vllm_tpu.envs as envs
    from vllm_tpu.ops.rpa_decode_kernel import decode_tiling

    if envs.VLLM_TPU_DISABLE_DECODE_KERNEL or not (
            _pallas_ok(head_dim, split) and _on_tpu()):
        return None
    return decode_tiling(
        num_rows, page_size, pages_per_seq, **_decode_block_overrides())


def dispatch_ragged_attention(
    q: jnp.ndarray,
    kv_cache: jnp.ndarray,
    layer: jnp.ndarray,
    md: AttentionMetadata,
    scale: float,
    *,
    sliding_window=None,
    soft_cap: float | None = None,
    k_scale: float | None = None,
    v_scale: float | None = None,
    return_lse: bool = False,
    ctx_stride=1,
    ctx_phase=0,
    allow_interpret: bool = False,
    kernel_suffix: str = "",
    sinks: jnp.ndarray | None = None,
    num_kv_heads: int | None = None,
):
    """THE kernel-vs-reference dispatch point (plain and striped-context
    callers alike — eligibility rules live only here): the Pallas flash
    kernel when it can run (TPU; or interpret mode on other backends when
    ``allow_interpret`` and VLLM_TPU_PALLAS_INTERPRET — the CP shard_map
    tests), else the XLA gather reference. ``sinks`` and the split layout
    (a 4-D cache and its ``num_kv_heads``) as ``paged_attention`` says."""
    import vllm_tpu.envs as envs

    split = kv_cache.ndim == 4
    if split:
        # The kernels and the reference see the page head-major in five
        # dims and the query padded with zeros to the key's stored lanes.
        kv_cache = _split_view(kv_cache, num_kv_heads)
        pad = -q.shape[-1] % 128
        if pad:
            q = jnp.pad(q, ((0, 0), (0, 0), (0, pad)))
        assert not return_lse, "no LSE output from the split layout"
    if sinks is not None:
        assert not return_lse and md.block_len == 1, (
            "a sink joins neither an LSE merge nor attention over blocks")
        sinks = sinks.astype(jnp.float32)
    interpret = allow_interpret and bool(envs.VLLM_TPU_PALLAS_INTERPRET)
    on_tpu = _on_tpu()
    # Decode-only fast path: every live row is a single-position decode
    # (T == R, token i == row i), so the sequence-pipelined kernel can
    # batch KV DMAs across sequences instead of walking them serially.
    # Striped-context (CP) and LSE callers stay on the general kernel, and
    # so does a call that states a window (an int or a traced scalar): a
    # row then reads a block of keys, which the general kernel's narrow
    # rung fetches from the window's floor on, a live row a visit.
    decode_ok = (
        md.decode_only
        and sliding_window is None
        and not return_lse
        and isinstance(ctx_stride, int)
        and ctx_stride == 1
        and isinstance(ctx_phase, int)
        and ctx_phase == 0
        and q.shape[0] == md.seq_lens.shape[0]
        and not envs.VLLM_TPU_DISABLE_DECODE_KERNEL
    )
    use_kernel = _pallas_ok(q.shape[-1], split) and (on_tpu or interpret)
    run_interpret = interpret and not on_tpu
    layer1 = jnp.asarray(layer, jnp.int32).reshape(1)
    static_kw = dict(
        sm_scale=scale, soft_cap=soft_cap, k_scale=k_scale, v_scale=v_scale,
        interpret=run_interpret,
    )
    extra = dict(sinks=sinks, head_major=split)
    if split:
        static_kw["head_major"] = True
    if md.block_len > 1:
        assert not md.decode_only and sliding_window is None, (
            "attention over blocks has no decode-only or windowed form")
    if use_kernel and decode_ok:
        from vllm_tpu.ops.rpa_decode_kernel import decode_paged_attention

        blk_kw = (
            dict(num_seqs_per_block=2, num_kv_pages_per_block=2)
            if run_interpret
            else _decode_block_overrides()
        )
        return _call_attention_kernel(
            functools.partial(
                decode_paged_attention, **static_kw, **blk_kw,
                name="rpa_decode_kernel" + kernel_suffix),
            md.tp_mesh,
            q,
            kv_cache,
            (layer1, md.seq_lens, md.block_tables, md.num_seqs,
             md.decode_order),
            {},
            **extra,
        )
    if use_kernel:
        from vllm_tpu.ops.rpa_kernel import ragged_paged_attention

        # The kernel picks its blocks for the chip; off-TPU interpret
        # runs name small ones instead.
        blk_kw = (
            dict(num_kv_pages_per_block=2, num_queries_per_block=8)
            if run_interpret
            else {}
        )
        return _call_attention_kernel(
            functools.partial(
                ragged_paged_attention, return_lse=return_lse,
                block_len=md.block_len, **static_kw, **blk_kw,
                name="rpa_kernel" + kernel_suffix,
            ),
            md.tp_mesh,
            q,
            kv_cache,
            (layer1, md.seq_lens, md.block_tables, md.query_start_loc,
             md.num_seqs),
            dict(sliding_window=sliding_window, ctx_stride=ctx_stride,
                 ctx_phase=ctx_phase),
            return_lse=return_lse,
            **extra,
        )
    return ref_ragged_paged_attention(
        q, kv_cache, layer, md, scale, sliding_window=sliding_window,
        soft_cap=soft_cap, k_scale=k_scale, v_scale=v_scale,
        return_lse=return_lse, ctx_stride=ctx_stride, ctx_phase=ctx_phase,
        block_len=md.block_len, sinks=sinks, head_major=split,
    )


def ref_ragged_paged_attention(
    q: jnp.ndarray,  # [T, H, D]
    kv_cache: jnp.ndarray,  # [L, NB, BS, 2*KH, D] (already holds this step's KV)
    layer: jnp.ndarray,  # scalar i32
    md: AttentionMetadata,
    scale: float,
    *,
    sliding_window=None,
    soft_cap: float | None = None,
    k_scale: float | None = None,
    v_scale: float | None = None,
    return_lse: bool = False,
    ctx_stride: int = 1,
    ctx_phase: int = 0,
    ctx_min_pos=0,
    block_len: int = 1,
    sinks: jnp.ndarray | None = None,
    head_major: bool = False,
) -> jnp.ndarray:
    """Gather-based masked attention. Each token attends to its request's
    cached context up to and including its own position (causal), or with
    ``block_len`` > 1 up to the end of its own block of that many
    positions (causal over blocks). ``sinks [H]`` joins every row's
    softmax denominator; ``head_major`` says the cache is the split
    layout's view ``[L, NB, KH, BS, Dk_pad + Dv]`` and ``q`` padded to
    ``Dk_pad``: the output is ``[T, H, Dv]``.

    ``ctx_stride``/``ctx_phase`` describe striped context-parallel shards:
    local page j holds global page ``j * stride + phase`` (stride 1 = the
    whole context). ``ctx_min_pos`` additionally masks context positions
    below it (cascade's suffix pass under striping: a rank's suffix table
    slice can still contain one shared-prefix page). ``return_lse=True``
    additionally returns the per-(token, head) logsumexp — the
    ``merge_attn_states`` contract."""
    t, h, d = q.shape
    # Gather only the referenced pages of this layer: [R, B, BS, rows, lanes].
    pages = kv_cache[layer, md.block_tables]
    if head_major:
        pages = jnp.swapaxes(pages, 2, 3)  # [R, B, KH, BS, .] -> [R, B, BS, KH, .]
    nl, nb = kv_cache.shape[:2]
    bs, rows, lanes = pages.shape[2:]
    packed = head_major or packed_kv_layout(d)
    kh = rows if packed else rows // 2
    groups = h // kh

    r, b = md.block_tables.shape
    ctx = b * bs
    kv_req = pages.reshape(r, ctx, rows, lanes)
    if packed:
        k_all = kv_req[:, :, :, :d]
        v_all = kv_req[:, :, :, d:]
    else:
        k_all = kv_req[:, :, 0::2]
        v_all = kv_req[:, :, 1::2]

    # Per-token gather of the owning request's context.
    k_t = k_all[md.token_req_idx].astype(jnp.float32)  # [T, C, KH, D]
    v_t = v_all[md.token_req_idx].astype(jnp.float32)
    if k_scale is not None:
        k_t = k_t * k_scale
    if v_scale is not None:
        v_t = v_t * v_scale

    qg = q.reshape(t, kh, groups, d).astype(jnp.float32)
    scores = jnp.einsum("tkgd,tckd->tkgc", qg, k_t) * scale
    if soft_cap is not None:
        scores = soft_cap * jnp.tanh(scores / soft_cap)

    local = jnp.arange(ctx, dtype=jnp.int32)
    ctx_pos = (
        ((local // bs) * ctx_stride + ctx_phase) * bs + local % bs
    )[None, :]  # [1, C] global positions of the local context slots
    last_seen = md.positions
    if block_len > 1:
        last_seen = (md.positions // block_len + 1) * block_len - 1
    causal = ctx_pos <= last_seen[:, None]  # [T, C]
    causal &= ctx_pos >= jnp.asarray(ctx_min_pos, jnp.int32)
    if sliding_window is not None:
        # Accepts a python int OR a traced scalar (0 = full attention),
        # so a layer scan can alternate windowed/full layers.
        win = jnp.asarray(sliding_window, jnp.int32)
        causal &= (ctx_pos > (md.positions[:, None] - win)) | (win <= 0)
    scores = jnp.where(causal[:, None, None, :], scores, -jnp.inf)

    if sinks is not None:
        # One more column a row, with no value: it only takes its share.
        sink = jnp.broadcast_to(
            sinks.reshape(1, kh, groups, 1), (t, kh, groups, 1))
        probs = jax.nn.softmax(
            jnp.concatenate([scores, sink], axis=-1), axis=-1)[..., :-1]
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    # Fully-masked rows (padding tokens) produce NaN-free zeros:
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    out = jnp.einsum("tkgc,tckd->tkgd", probs, v_t)
    out = out.reshape(t, h, v_t.shape[-1]).astype(q.dtype)
    if not return_lse:
        return out
    lse = jax.scipy.special.logsumexp(scores, axis=-1)  # [T, KH, G]
    return out, lse.reshape(t, h)


def tree_verify_attention(
    q: jnp.ndarray,  # [T, H, D] — T = (padded) sum of per-request windows
    kv_cache: jnp.ndarray,
    layer: jnp.ndarray,
    md: AttentionMetadata,  # tree_mask/tree_window_start/tree_paged set
    scale: float,
    *,
    soft_cap: float | None = None,
    k_scale: float | None = None,
    v_scale: float | None = None,
) -> jnp.ndarray:
    """Attention for a tree-verification step, in two LSE-merged parts.

    Reference analog: ``vllm/v1/attention/backends/tree_attn.py`` builds a
    [T, T] tree bias and runs one masked attention; TPU-first we split:

    1. COMMITTED context: every window token sees exactly the request's
       context BEFORE this step, regardless of its depth — so the step is
       reshaped into one-query pseudo-sequences (``md.tree_paged``:
       kv_len = committed length, duplicated block-table rows) and runs
       the ordinary ragged kernel. No kernel changes; the tradeoff is the
       context pages are DMA'd once per window token instead of once per
       request (verify steps are a small fraction of decode time).
    2. TREE window: each token attends its own window's ancestors + self
       (``md.tree_mask``), a dense [T, W] attention over this step's K/V
       read back from the just-written cache slots.

    Both parts return logsumexps and merge exactly
    (``merge_attn_states``)."""
    from vllm_tpu.ops.cp_attention import merge_attn_states

    t, h, d = q.shape
    nl, nb, bs, rows, lanes = kv_cache.shape
    packed = packed_kv_layout(d)
    kh = rows if packed else rows // 2
    groups = h // kh
    w = md.tree_mask.shape[1]

    out_c, lse_c = dispatch_ragged_attention(
        q, kv_cache, layer, md.tree_paged, scale,
        soft_cap=soft_cap, k_scale=k_scale, v_scale=v_scale,
        return_lse=True, allow_interpret=True,
    )

    # Window K/V: this step's rows, read from the slots just written.
    win_idx = jnp.clip(
        md.tree_window_start[:, None] + jnp.arange(w, dtype=jnp.int32)[None],
        0, md.slot_mapping.shape[0] - 1,
    )  # [T, W] stream indices of the row's window tokens
    w_slots = md.slot_mapping[win_idx]  # [T, W] flat cache slots
    flat = kv_cache.reshape(nl * nb * bs, rows, lanes)
    kv_win = flat[layer * (nb * bs) + w_slots]  # [T, W, rows, lanes]
    if packed:
        k_w = kv_win[..., :d]
        v_w = kv_win[..., d:]
    else:
        k_w = kv_win[:, :, 0::2]
        v_w = kv_win[:, :, 1::2]
    k_w = k_w.astype(jnp.float32)
    v_w = v_w.astype(jnp.float32)
    if k_scale is not None:
        k_w = k_w * k_scale
    if v_scale is not None:
        v_w = v_w * v_scale

    qg = q.reshape(t, kh, groups, d).astype(jnp.float32)
    scores = jnp.einsum("tkgd,twkd->tkgw", qg, k_w) * scale
    if soft_cap is not None:
        scores = soft_cap * jnp.tanh(scores / soft_cap)
    scores = jnp.where(
        md.tree_mask[:, None, None, :], scores, -jnp.inf
    )
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    out_w = jnp.einsum("tkgw,twkd->tkgd", probs, v_w).reshape(t, h, d)
    lse_w = jax.scipy.special.logsumexp(scores, axis=-1).reshape(t, h)

    return merge_attn_states(
        jnp.stack([
            out_c.astype(jnp.float32), out_w.astype(jnp.float32)
        ]),
        jnp.stack([lse_c.astype(jnp.float32), lse_w]),
    ).astype(q.dtype)


def cascade_ref_attention(
    q: jnp.ndarray,  # [T, H, D]
    kv_cache: jnp.ndarray,
    layer: jnp.ndarray,
    md: AttentionMetadata,  # num_common_prefix_blocks > 0
    scale: float,
    *,
    sliding_window=None,
    soft_cap: float | None = None,
    k_scale: float | None = None,
    v_scale: float | None = None,
    return_lse: bool = False,
    ctx_stride: int = 1,
    ctx_phase=0,
) -> jnp.ndarray:
    """Shared-prefix (cascade) attention: every live request's first
    ``num_common_prefix_blocks`` block-table entries are identical, so the
    prefix KV is gathered ONCE (no [T, C] per-token duplication), attended
    by the whole batch, and LSE-merged with the per-request suffix
    attention (reference: ``gpu_model_runner.py:2367`` cascade path +
    ``csrc/attention/merge_attn_states.cu``).

    Striping-aware (``ctx_stride``/``ctx_phase``): under context
    parallelism the shared prefix is striped across ranks like everything
    else. ``num_common_prefix_blocks`` counts GLOBAL prefix pages; this
    rank's slice of them is the table's first ``ceil(ncb/stride)`` columns
    (a static bound — the per-rank count varies with the traced phase, so
    the boundary pages are resolved by global-position masks: the prefix
    pass masks positions >= ncb*bs, the suffix pass masks < ncb*bs)."""
    from vllm_tpu.ops.cp_attention import merge_attn_states

    ncb = md.num_common_prefix_blocks
    t, h, d = q.shape
    nl, nb, bs, rows, lanes = kv_cache.shape
    packed = packed_kv_layout(d)
    kh = rows if packed else rows // 2
    groups = h // kh
    # Static per-rank bounds on the striped prefix: pr_max columns cover
    # every rank's prefix pages; the suffix slice starts at pr_min (the
    # one possibly-shared boundary column is disambiguated by masks).
    pr_max = -(-ncb // ctx_stride)
    pr_min = ncb // ctx_stride
    prefix_end = ncb * bs  # first global position PAST the shared prefix

    # ---- common prefix: one shared gather ----
    pages_c = kv_cache[layer, md.block_tables[0, :pr_max]]
    cp = pr_max * bs
    kv_c = pages_c.reshape(cp, rows, lanes)
    if packed:
        k_c, v_c = kv_c[:, :, :d], kv_c[:, :, d:]
    else:
        k_c, v_c = kv_c[:, 0::2], kv_c[:, 1::2]
    k_c = k_c.astype(jnp.float32)
    v_c = v_c.astype(jnp.float32)
    if k_scale is not None:
        k_c = k_c * k_scale
    if v_scale is not None:
        v_c = v_c * v_scale

    qg = q.reshape(t, kh, groups, d).astype(jnp.float32)
    scores = jnp.einsum("tkgd,ckd->tkgc", qg, k_c) * scale
    if soft_cap is not None:
        scores = soft_cap * jnp.tanh(scores / soft_cap)
    local = jnp.arange(cp, dtype=jnp.int32)
    ctx_pos = (
        ((local // bs) * ctx_stride + jnp.asarray(ctx_phase, jnp.int32))
        * bs + local % bs
    )[None, :]
    causal = ctx_pos <= md.positions[:, None]
    causal &= ctx_pos < prefix_end
    if sliding_window is not None:
        win = jnp.asarray(sliding_window, jnp.int32)
        causal &= (ctx_pos > (md.positions[:, None] - win)) | (win <= 0)
    scores = jnp.where(causal[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    out_c = jnp.einsum("tkgc,ckd->tkgd", probs, v_c).reshape(t, h, d)
    lse_c = jax.scipy.special.logsumexp(scores, axis=-1).reshape(t, h)

    # ---- per-request suffix: the plain ragged path over the remaining
    # blocks, with context positions offset past the prefix ----
    import dataclasses as _dc

    md_suffix = _dc.replace(
        md,
        block_tables=md.block_tables[:, pr_min:],
        num_common_prefix_blocks=0,
    )
    out_s, lse_s = ref_ragged_paged_attention(
        q, kv_cache, layer, md_suffix, scale,
        sliding_window=sliding_window, soft_cap=soft_cap,
        k_scale=k_scale, v_scale=v_scale, return_lse=True,
        ctx_stride=ctx_stride,
        # Local column j of the sliced table is absolute column j+pr_min:
        # global page (j+pr_min)*stride + phase.
        ctx_phase=pr_min * ctx_stride + jnp.asarray(ctx_phase, jnp.int32),
        ctx_min_pos=prefix_end,
    )
    out = merge_attn_states(
        jnp.stack([out_c.astype(jnp.float32), out_s.astype(jnp.float32)]),
        jnp.stack([lse_c, lse_s]),
    ).astype(q.dtype)
    if not return_lse:
        return out
    lse = jnp.logaddexp(lse_c, lse_s)
    return out, lse
