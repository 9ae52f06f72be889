"""Device-time probe: attention kernel block-size sweep at the bench's
decode shape, inside a 32-layer chain (layer index varies per iteration —
XLA cannot CSE the calls).

Two sweeps on TPU:
- the general ragged kernel's (num_queries_per_block, num_kv_pages_per_block)
  grid (the round-5 sweep that tuned the mixed-batch path), and
- the decode-specialized sequence-pipelined kernel's
  (num_seqs_per_block, num_kv_pages_per_block) grid, compared against the
  general kernel at the same shape — the A/B that decides dispatch.

On CPU (or ``--smoke``) the decode kernel runs in Pallas interpret mode
at a tiny shape against the XLA reference — numerics-only smoke coverage
of every sweep point (the general kernel is left to its own tests
there).

The grouped-decode comparison that used to live here concluded in round
5: grouped measured slower and was deleted.
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("VLLM_TPU_LOG_LEVEL", "WARNING")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _bench(name, f, q, kv, n_layers):
    out = f(q, kv)
    out.block_until_ready()
    best = float("inf")
    for _ in range(5):
        t0 = time.monotonic()
        f(q, kv).block_until_ready()
        best = min(best, time.monotonic() - t0)
    per_layer_us = best / n_layers * 1e6
    print(f"{name:28s} {best * 1e3:8.2f} ms/{n_layers}-layer  "
          f"{per_layer_us:7.1f} us/layer")
    return out, best


def tpu_sweep():
    import functools

    # Bench decode shape: 64 seqs, 1 query each, ctx ~96-160, fp8 KV,
    # 32 q heads / 8 kv heads / 128 head dim, page 16, 704 blocks.
    S, H, KH, D, BS, NB, L = 64, 32, 8, 128, 16, 704, 32
    PAGES = 16  # block-table width (b_pad bucket)

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((S, H, D)), jnp.bfloat16)
    kv = jnp.asarray(
        rng.standard_normal((L, NB, BS, 2 * KH, D)) * 0.1,
        jnp.float8_e4m3fn,
    )
    kv_lens = jnp.asarray(rng.integers(96, 160, size=S), jnp.int32)
    # Distinct pages per seq (1 + s*PAGES + p), clipped to NB.
    pt = (1 + np.arange(S)[:, None] * PAGES + np.arange(PAGES)[None, :]) % NB
    page_tables = jnp.asarray(pt, jnp.int32)
    cu = jnp.asarray(np.arange(S + 1), jnp.int32)
    num_seqs = jnp.asarray([S], jnp.int32)
    scale = D ** -0.5

    def chain(attn_fn):
        @jax.jit
        def f(q, kv):
            def body(li, acc):
                return acc + attn_fn(q, kv, li).astype(jnp.float32)

            return jax.lax.fori_loop(
                0, L, body, jnp.zeros((S, H, D), jnp.float32)
            )
        return f

    def rpa_fn(q, kv, li, **kw):
        from vllm_tpu.ops.rpa_kernel import ragged_paged_attention

        return ragged_paged_attention(
            q, kv, jnp.asarray(li, jnp.int32).reshape(1), kv_lens,
            page_tables, cu, num_seqs, sm_scale=scale,
            k_scale=0.05, v_scale=0.05, **kw,
        )

    def decode_fn(q, kv, li, **kw):
        from vllm_tpu.ops.rpa_decode_kernel import decode_paged_attention

        return decode_paged_attention(
            q, kv, jnp.asarray(li, jnp.int32).reshape(1), kv_lens,
            page_tables, num_seqs, sm_scale=scale,
            k_scale=0.05, v_scale=0.05, **kw,
        )

    print("device:", jax.devices()[0])
    ref, t_rpa = _bench("rpa (tuned)", chain(rpa_fn), q, kv, L)
    for nq in (4, 8, 16, 32, 64):
        for pg in (4, 8, 16):
            try:
                fn = functools.partial(
                    rpa_fn, num_queries_per_block=nq,
                    num_kv_pages_per_block=pg,
                )
                got, t = _bench(
                    f"rpa nq={nq} pg={pg}", chain(fn), q, kv, L
                )
                err = float(
                    jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref))
                )
                print(f"    vs tuned: {t_rpa / t:5.2f}x   rel err {err:.4f}")
            except Exception as e:  # noqa: BLE001
                print(f"    nq={nq} pg={pg} failed: {type(e).__name__}: "
                      f"{str(e)[:120]}")
    # Decode-specialized kernel: seqs-per-block x kv-pages-per-block.
    for sb in (4, 8, 16, 32):
        for pg in (4, 8, 16):
            try:
                fn = functools.partial(
                    decode_fn, num_seqs_per_block=sb,
                    num_kv_pages_per_block=pg,
                )
                got, t = _bench(
                    f"decode sb={sb} pg={pg}", chain(fn), q, kv, L
                )
                err = float(
                    jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref))
                )
                print(f"    vs rpa tuned: {t_rpa / t:5.2f}x   "
                      f"rel err {err:.4f}")
            except Exception as e:  # noqa: BLE001
                print(f"    sb={sb} pg={pg} failed: {type(e).__name__}: "
                      f"{str(e)[:120]}")


def smoke_sweep():
    """CPU: decode kernel in interpret mode vs the XLA reference at a
    tiny shape, across the block-size sweep points (numerics only)."""
    from vllm_tpu.ops.attention import (
        AttentionMetadata,
        kv_cache_shape,
        ref_ragged_paged_attention,
    )
    from vllm_tpu.ops.rpa_decode_kernel import decode_paged_attention

    S, H, KH, D, BS, NB, L = 5, 4, 2, 128, 4, 32, 2
    rng = np.random.default_rng(0)
    kv_lens = rng.integers(1, 14, size=S).tolist()
    q = jnp.asarray(rng.standard_normal((S, H, D)), jnp.float32)
    kv = jnp.asarray(
        rng.standard_normal(kv_cache_shape(L, NB, BS, KH, D)), jnp.float32
    )
    max_pages = max(-(-kv_len // BS) for kv_len in kv_lens)
    pt = np.zeros((S, max_pages), np.int32)
    nxt = 1
    for i, kv_len in enumerate(kv_lens):
        nb = -(-kv_len // BS)
        pt[i, :nb] = np.arange(nxt, nxt + nb)
        nxt += nb
    assert nxt <= NB
    page_tables = jnp.asarray(pt)
    seq_lens = jnp.asarray(kv_lens, jnp.int32)
    num_seqs = jnp.asarray([S], jnp.int32)
    scale = D ** -0.5
    md = AttentionMetadata(
        positions=jnp.asarray([kv_len - 1 for kv_len in kv_lens], jnp.int32),
        slot_mapping=jnp.zeros(S, jnp.int32),
        block_tables=page_tables,
        seq_lens=seq_lens,
        query_start_loc=jnp.arange(S + 1, dtype=jnp.int32),
        token_req_idx=jnp.arange(S, dtype=jnp.int32),
        logits_indices=jnp.arange(S, dtype=jnp.int32),
        num_seqs=num_seqs,
        decode_only=True,
    )
    print("device:", jax.devices()[0], "(interpret-mode smoke)")
    want = np.asarray(
        ref_ragged_paged_attention(q, kv, jnp.int32(1), md, scale)
    )
    worst = 0.0
    for sb in (1, 2, 4):
        for pg in (1, 2, 4):
            got = np.asarray(decode_paged_attention(
                q, kv, jnp.asarray([1], jnp.int32), seq_lens,
                page_tables, num_seqs, sm_scale=scale,
                num_seqs_per_block=sb, num_kv_pages_per_block=pg,
                interpret=True,
            ))
            err = float(np.max(np.abs(got - want)))
            worst = max(worst, err)
            status = "ok" if err < 2e-4 else "MISMATCH"
            print(f"decode sb={sb} pg={pg}  max abs err {err:.2e}  {status}")
    if worst >= 2e-4:
        raise SystemExit(f"decode kernel smoke mismatch: {worst}")
    print("smoke sweep ok")


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if "--smoke" in argv or jax.default_backend() != "tpu":
        smoke_sweep()
    else:
        tpu_sweep()
    return 0


if __name__ == "__main__":
    sys.exit(main())
