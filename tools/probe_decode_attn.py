"""Device-time probe of the decode attention kernel.

On a TPU, by default, THE CELLS' SHAPES: a bucket of rows of which ``n`` are
live (8, 17, 40 and 128 of 128 at ``mistral7b.chat``'s 32 q heads on 8 KV
heads, lognormal contexts of median 560, sigma 1, 16 ... 5,000; 4 of 32 at
``lagunaxs2.code-mixed``'s 48 q heads, contexts of 2,000 ... 8,000), inside
a 32-call chain (the layer index varies a call, so XLA cannot CSE them).
Side by side, in us a call: the decode kernel under the step's order of the
rows (``decode_row_order``), the same with the rows as given, the ragged
kernel on the same rows (what ``VLLM_TPU_DISABLE_DECODE_KERNEL=1`` runs),
and with ``--parent DIR`` (an unpacked parent commit) that tree's decode
kernel; with ``run`` and ``least`` of ``decode_tile_counts`` and the tiles
the walk runs with the rows as given. Every live row is held to the ragged
kernel's answer, and the orders to one another bit for bit; a disagreement
exits non-zero.

``--sweep`` is the old block-size sweep at the old bench's decode shape
(64 rows of 96-160 keys, fp8 KV): the general ragged kernel's
(num_queries_per_block, num_kv_pages_per_block) grid and the decode
kernel's (num_seqs_per_block, num_kv_pages_per_block) grid.

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


def _lognormal_contexts(rng, n, median=560, sigma=1.0, lo=16, hi=5000):
    return np.clip(
        np.exp(rng.normal(np.log(median), sigma, size=n)), lo, hi
    ).astype(np.int32)


# name -> (rows of the bucket, q heads, KV heads, live rows' contexts(rng))
CELL_SHAPES = {
    **{f"mistral.{n}_of_128": (
        128, 32, 8, lambda rng, n=n: _lognormal_contexts(rng, n))
       for n in (8, 17, 40, 128)},
    "laguna.4_of_32": (
        32, 48, 8,
        lambda rng: rng.integers(2000, 8000, size=4).astype(np.int32)),
}


def cell_shapes(parent_dir=None, only=None):
    """The decode-only call at the cells' shapes: us a call by kernel."""
    from vllm_tpu.ops.rpa_decode_kernel import (
        decode_paged_attention,
        decode_row_order,
        decode_tile_counts,
        decode_tiling,
    )
    from vllm_tpu.ops.rpa_kernel import ragged_paged_attention

    parent = None
    if parent_dir:
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "parent_rpa_decode_kernel",
            os.path.join(parent_dir, "vllm_tpu/ops/rpa_decode_kernel.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)

    D, BS, L, CALLS, PAGES = 128, 16, 4, 32, 512
    print("device:", jax.devices()[0])
    failed = []
    for name, (R, H, KH, contexts) in CELL_SHAPES.items():
        if only and name not in only:
            continue
        rng = np.random.default_rng(0)
        kv_live = contexts(rng)
        n = len(kv_live)
        pages = -(-kv_live // BS)
        NB = int(pages.sum()) + 1
        q = jnp.asarray(rng.standard_normal((R, H, D)), jnp.bfloat16)
        kv = jnp.asarray(
            rng.standard_normal((L, NB, BS, 2 * KH, D)) * 0.5, jnp.bfloat16)
        pt = np.zeros((R, PAGES), np.int32)  # dead rows: the null page
        first = 1 + np.concatenate([[0], np.cumsum(pages)[:-1]])
        for i in range(n):
            pt[i, :pages[i]] = first[i] + np.arange(pages[i])
        kv_lens_np = np.zeros(R, np.int32)
        kv_lens_np[:n] = kv_live
        kv_lens = jnp.asarray(kv_lens_np)
        page_tables = jnp.asarray(pt)
        num_seqs = jnp.asarray([n], jnp.int32)
        cu = jnp.arange(R + 1, dtype=jnp.int32)
        order = decode_row_order(kv_lens, num_seqs)
        scale = D ** -0.5

        s_blk, kp = decode_tiling(R, BS, PAGES)
        blocks = dict(page_size=BS, num_seqs_per_block=s_blk,
                      num_kv_pages_per_block=kp)
        run, least = decode_tile_counts(kv_lens_np, n, **blocks)
        tile = BS * kp
        live_rows = -(-n // s_blk) * s_blk  # of the live blocks
        as_given = sum(
            max(1, -(-int(kv_lens_np[b:b + s_blk].max()) // tile))
            for b in range(0, live_rows, s_blk))
        padded = as_given + (R - live_rows) // s_blk
        print(f"\n{name}: {n} live rows of {R}, contexts "
              f"{int(kv_live.min())}-{int(kv_live.max())} (mean "
              f"{kv_live.mean():.0f}); tiles of {s_blk} rows x {tile} keys: "
              f"least {least:.1f}, run {run} in order, {as_given} as given, "
              f"{padded} with the dead blocks' (the parent's walk)")

        def chain(attn_fn):
            @jax.jit
            def f(q, kv):
                def body(i, acc):
                    return acc + attn_fn(q, kv, i % L).astype(jnp.float32)

                return jax.lax.fori_loop(
                    0, CALLS, body, jnp.zeros((R, H, D), jnp.float32))
            return f

        def decode_fn(mod_fn, order):
            extra = () if order is None else (order,)
            return lambda q, kv, li: mod_fn(
                q, kv, jnp.asarray(li, jnp.int32).reshape(1), kv_lens,
                page_tables, num_seqs, *extra, sm_scale=scale)

        kernels = {
            "decode, rows in order": decode_fn(decode_paged_attention, order),
            "decode, rows as given": decode_fn(decode_paged_attention, None),
            "ragged": lambda q, kv, li: ragged_paged_attention(
                q, kv, jnp.asarray(li, jnp.int32).reshape(1), kv_lens,
                page_tables, cu, num_seqs, sm_scale=scale),
        }
        if parent:
            kernels["decode, parent"] = decode_fn(
                parent.decode_paged_attention, None)
        outs = {}
        for label, fn in kernels.items():
            out, _ = _bench(f"{name} {label}", chain(fn), q, kv, CALLS)
            outs[label] = np.asarray(out)[:n]
        ref = outs["ragged"]
        for label, out in outs.items():
            err = float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))
            same = (label == "ragged" or np.array_equal(
                out, outs["decode, rows in order"]))
            print(f"    {label:24s} rel err vs ragged {err:.4f}"
                  + ("" if label == "ragged" else
                     f"; bit-equal to the ordered walk: {same}"))
            if err > 2e-2 or not same:
                failed.append(f"{name} {label}")
    if failed:
        raise SystemExit(f"decode kernel disagrees: {failed}")


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
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--parent", help="an unpacked parent commit")
    ap.add_argument("shapes", nargs="*", help=f"of {list(CELL_SHAPES)}")
    a = ap.parse_args(argv)
    if a.smoke or jax.default_backend() != "tpu":
        smoke_sweep()
    elif a.sweep:
        tpu_sweep()
    else:
        cell_shapes(a.parent, a.shapes)
    return 0


if __name__ == "__main__":
    sys.exit(main())
