"""Decode-specialized ragged attention kernel (``ops/rpa_decode_kernel.py``)
exact-equivalence tests against the XLA reference, in Pallas interpret mode
on CPU, plus the dispatcher eligibility contract: decode-only batches take
the sequence-pipelined kernel, everything else (mixed prefill+decode, LSE,
striped context, the env escape hatch, and every call that states a sliding
window) stays on the general ragged kernel.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vllm_tpu.ops.attention import (
    AttentionMetadata,
    dispatch_ragged_attention,
    kv_cache_shape,
    ref_ragged_paged_attention,
    write_kv,
)
from vllm_tpu.ops.rpa_decode_kernel import (
    decode_paged_attention,
    decode_row_order,
    decode_tile_counts,
)

# Small explicit blocks so interpret runs exercise multi-tile loops, the
# cross-program DMA chain, AND partial sequence blocks.
BLK = dict(num_seqs_per_block=2, num_kv_pages_per_block=2)


def _decode_case(rng, kv_lens, kh, h, d, bs, num_blocks, r_pad=None,
                 kv_dtype=jnp.float32, q_dtype=jnp.float32, num_layers=1,
                 layer=0, extra_tokens=0):
    """Build a decode-only batch: ONE query token per row at position
    kv_len - 1, rows past ``len(kv_lens)`` dead padding (zero kv_len,
    null block table). ``extra_tokens`` reserves block capacity for
    chained multi-step tests."""
    num_seqs = len(kv_lens)
    r = r_pad if r_pad is not None else num_seqs
    assert r >= num_seqs
    q = jnp.asarray(rng.standard_normal((r, h, d)), q_dtype)

    max_blocks = max(-(-(kv + extra_tokens) // bs) for kv in kv_lens) + 1
    block_tables = np.zeros((r, max_blocks), np.int32)
    kv_cache = jnp.asarray(
        rng.standard_normal(
            kv_cache_shape(num_layers, num_blocks, bs, kh, d)
        ),
        jnp.float32,
    ).astype(kv_dtype)

    positions = np.zeros(r, np.int32)
    slot_mapping = np.zeros(r, np.int32)
    seq_lens = np.zeros(r, np.int32)
    seq_lens[:num_seqs] = kv_lens

    next_block = 1
    for i in range(num_seqs):
        nb = -(-(kv_lens[i] + extra_tokens) // bs)
        blocks = np.arange(next_block, next_block + nb, dtype=np.int32)
        next_block += nb
        block_tables[i, :nb] = blocks
        pos = kv_lens[i] - 1
        positions[i] = pos
        slot_mapping[i] = blocks[pos // bs] * bs + pos % bs
    assert next_block <= num_blocks

    md = AttentionMetadata(
        positions=jnp.asarray(positions),
        slot_mapping=jnp.asarray(slot_mapping),
        block_tables=jnp.asarray(block_tables),
        seq_lens=jnp.asarray(seq_lens),
        query_start_loc=jnp.arange(r + 1, dtype=jnp.int32),
        token_req_idx=jnp.arange(r, dtype=jnp.int32),
        logits_indices=jnp.arange(r, dtype=jnp.int32),
        num_seqs=jnp.asarray([num_seqs], jnp.int32),
        decode_only=True,
    )
    k_new = jnp.asarray(rng.standard_normal((r, kh, d)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((r, kh, d)), jnp.float32)
    kv_cache = write_kv(
        kv_cache, jnp.int32(layer), k_new, v_new, md.slot_mapping
    )
    return q, kv_cache, md


def _run_decode_kernel(q, kv_cache, layer, md, scale, **kw):
    kw = {**BLK, **kw}
    return decode_paged_attention(
        q,
        kv_cache,
        jnp.asarray([layer], jnp.int32),
        md.seq_lens,
        md.block_tables,
        md.num_seqs,
        sm_scale=scale,
        interpret=True,
        **kw,
    )


@pytest.mark.parametrize(
    # GQA ratios 1, 2, 4; and 8 KV heads, which are two heads blocks of 4 in
    # float32 (a row's output leaves a heads block at a time)
    "kh,h", [(1, 1), (2, 4), (2, 8), (4, 4), (8, 8), (8, 16)]
)
@pytest.mark.parametrize("d", [64, 128])
def test_decode_kernel_matches_reference(kh, h, d):
    """Ragged decode batch incl. single-page short seqs and dead padding
    rows (r_pad > num_seqs): live rows match the XLA reference."""
    rng = np.random.default_rng(0)
    bs = 8
    kv_lens = [33, 1, 17, 2, 9]  # 1- and 2-token seqs: one page each
    q, kv_cache, md = _decode_case(
        rng, kv_lens, kh, h, d, bs, num_blocks=64, r_pad=8
    )
    scale = d ** -0.5
    got = _run_decode_kernel(
        q, kv_cache, 0, md, scale,
        order=decode_row_order(md.seq_lens, md.num_seqs))
    want = ref_ragged_paged_attention(q, kv_cache, jnp.int32(0), md, scale)
    n = len(kv_lens)
    np.testing.assert_allclose(
        np.asarray(got)[:n], np.asarray(want)[:n], rtol=2e-4, atol=2e-4
    )
    assert not np.asarray(got)[n:].any()


def test_decode_kernel_bf16_odd_gqa():
    """bf16 q/cache with an odd GQA ratio exercises the packed strided
    K/V load and the fold-to-f32 path."""
    rng = np.random.default_rng(1)
    kh, h, d, bs = 1, 3, 128, 8
    q, kv_cache, md = _decode_case(
        rng, [21, 5, 12], kh, h, d, bs, num_blocks=64,
        kv_dtype=jnp.bfloat16, q_dtype=jnp.bfloat16,
    )
    scale = d ** -0.5
    got = _run_decode_kernel(q, kv_cache, 0, md, scale)
    want = ref_ragged_paged_attention(q, kv_cache, jnp.int32(0), md, scale)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )


@pytest.mark.parametrize("fp8", [jnp.float8_e4m3fn, jnp.float8_e5m2])
def test_decode_kernel_fp8_kv_scale(fp8):
    """fp8 KV cache with dequant scales: kernel and reference dequantize
    identically."""
    rng = np.random.default_rng(2)
    kh, h, d, bs = 2, 4, 128, 8
    q, kv_cache, md = _decode_case(
        rng, [19, 7, 30], kh, h, d, bs, num_blocks=64, kv_dtype=fp8
    )
    scale = d ** -0.5
    got = _run_decode_kernel(
        q, kv_cache, 0, md, scale, k_scale=0.5, v_scale=2.0
    )
    want = ref_ragged_paged_attention(
        q, kv_cache, jnp.int32(0), md, scale, k_scale=0.5, v_scale=2.0
    )
    np.testing.assert_allclose(
        np.asarray(got)[:3], np.asarray(want)[:3], rtol=2e-2, atol=2e-2
    )


def dispatched(q, kv_cache, md, scale, layer=0, **kw):
    """``dispatch_ragged_attention``'s answer in interpret mode, and the
    names of the ``pallas_call``s it lowered the call to."""
    import vllm_tpu.ops.rpa_decode_kernel as dk
    import vllm_tpu.ops.rpa_kernel as rk

    names = []
    with pytest.MonkeyPatch.context() as mp:
        for module, fn in ((rk, "ragged_paged_attention"),
                           (dk, "decode_paged_attention")):
            mp.setattr(
                module, fn,
                lambda *a, _real=getattr(module, fn), **k: (
                    names.append(k["name"]), _real(*a, **k))[1])
        out = dispatch_ragged_attention(
            q, kv_cache, jnp.int32(layer), md, scale, allow_interpret=True,
            **kw)
    return out, names


def test_decode_only_rows_under_a_sliding_window():
    """What ``decode_paged_attention(sliding_window=16)`` held until the
    decode kernel lost its window: the same rows, through the dispatcher,
    which sends a call that states a window to the ragged kernel."""
    rng = np.random.default_rng(3)
    kh, h, d, bs = 2, 4, 128, 8
    q, kv_cache, md = _decode_case(
        rng, [60, 9, 41], kh, h, d, bs, num_blocks=64
    )
    scale = d ** -0.5
    got, names = dispatched(q, kv_cache, md, scale, sliding_window=16)
    assert names == ["rpa_kernel"]
    want = ref_ragged_paged_attention(
        q, kv_cache, jnp.int32(0), md, scale, sliding_window=16
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_decode_kernel_soft_cap_and_layer_indexing():
    rng = np.random.default_rng(4)
    kh, h, d, bs = 2, 4, 64, 8
    q, kv_cache, md = _decode_case(
        rng, [11, 26], kh, h, d, bs, num_blocks=32, num_layers=3, layer=2
    )
    scale = d ** -0.5
    got = _run_decode_kernel(q, kv_cache, 2, md, scale, soft_cap=30.0)
    want = ref_ragged_paged_attention(
        q, kv_cache, jnp.int32(2), md, scale, soft_cap=30.0
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_decode_kernel_successive_positions():
    """Successive single-position calls with K/V appended between them
    (what every decode-only step after another is) each match the
    reference."""
    import dataclasses

    rng = np.random.default_rng(5)
    kh, h, d, bs = 2, 4, 128, 8
    kv_lens = [17, 5, 40]
    q, kv_cache, md = _decode_case(
        rng, kv_lens, kh, h, d, bs, num_blocks=64, extra_tokens=3
    )
    scale = d ** -0.5
    r = q.shape[0]
    for step in range(3):
        got = _run_decode_kernel(q, kv_cache, 0, md, scale)
        want = ref_ragged_paged_attention(
            q, kv_cache, jnp.int32(0), md, scale
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4,
            err_msg=f"step {step}",
        )
        # Append the next token per sequence: pos = old kv_len.
        pos = np.asarray(md.seq_lens)
        bt = np.asarray(md.block_tables)
        slots = bt[np.arange(r), pos // bs] * bs + pos % bs
        k_new = jnp.asarray(rng.standard_normal((r, kh, d)), jnp.float32)
        v_new = jnp.asarray(rng.standard_normal((r, kh, d)), jnp.float32)
        kv_cache = write_kv(
            kv_cache, jnp.int32(0), k_new, v_new, jnp.asarray(slots)
        )
        q = jnp.asarray(rng.standard_normal((r, h, d)), jnp.float32)
        md = dataclasses.replace(
            md,
            positions=jnp.asarray(pos),
            slot_mapping=jnp.asarray(slots),
            seq_lens=jnp.asarray(pos + 1),
        )


def _with_live(md, n):
    """The step with its first ``n`` rows live."""
    import dataclasses

    return dataclasses.replace(md, num_seqs=jnp.asarray([n], jnp.int32))


def test_decode_kernel_chain_with_a_changing_block_count():
    """Successive calls over one cache whose live rows, and so whose live
    blocks (2 rows each: 3, 1, 4, 1 of the 4 a call's grid has), change
    from call to call, as a bucket's steps do: each call's copy chain and
    buffer parity are its own, and every call matches the reference."""
    rng = np.random.default_rng(13)
    kh, h, d, bs = 2, 4, 128, 8
    kv_lens = [17, 5, 40, 33, 9, 64, 2]
    q, kv_cache, md = _decode_case(
        rng, kv_lens, kh, h, d, bs, num_blocks=64, r_pad=8)
    scale = d ** -0.5
    for n in (5, 1, 7, 0):
        md_n = _with_live(md, n)
        got = _run_decode_kernel(
            q, kv_cache, 0, md_n, scale,
            order=decode_row_order(md_n.seq_lens, md_n.num_seqs))
        want = ref_ragged_paged_attention(
            q, kv_cache, jnp.int32(0), md_n, scale)
        np.testing.assert_allclose(
            np.asarray(got)[:n], np.asarray(want)[:n], rtol=2e-4, atol=2e-4,
            err_msg=f"{n} live rows")
        assert not np.asarray(got)[n:].any(), f"{n} live rows: dead rows"


# (live rows, rows of the bucket) of the live-tile walk's cases: none, fewer
# than a block, a block, a block and a row, and the cell's: 17 of 128, and
# a full bucket.
LIVE_ROWS = [(0, 8), (1, 8), (3, 8), (4, 8), (5, 8), (17, 128), (128, 128)]


def _contexts(rng, n):
    """One long row among short ones, a row of exactly a tile (2 pages of 8
    through the dispatcher off the chip), the rest a few pages or less."""
    kv_lens = rng.integers(1, 30, size=n)
    kv_lens[rng.integers(n)] = 16
    kv_lens[n // 2] = 90
    return [int(kv) for kv in kv_lens]


def _with_order(md):
    import dataclasses

    return dataclasses.replace(
        md, decode_order=decode_row_order(md.seq_lens, md.num_seqs))


@pytest.mark.parametrize("live,r_pad", LIVE_ROWS)
@pytest.mark.parametrize("layout", ["plain", "packed_64", "split_sinks"])
def test_the_walk_over_live_tiles(layout, live, r_pad):
    """The decode kernel through the dispatcher, under the step's
    ``decode_row_order``: every live row is the XLA reference's, every dead
    row finite, and a row's output does not depend on the rows it shares a
    block with: BIT-EQUAL whether the rows come as given and unordered, as
    given, or shuffled (which is what lets the order change nothing a
    client can read)."""
    import dataclasses

    rng = np.random.default_rng(14)
    kv_lens = _contexts(rng, max(live, 1))
    q, kv_cache, md, scale, kw, want = _layout_case(
        rng, layout, kv_lens, r_pad)
    if not live:  # (a case needs a row to size its tables by)
        md = _with_live(md, 0)
    got, names = dispatched(q, kv_cache, _with_order(md), scale, **kw)
    assert names == ["rpa_decode_kernel"]
    got = np.asarray(got)
    assert got.shape[0] == r_pad and np.isfinite(got).all()
    np.testing.assert_allclose(got[:live], want[:live], rtol=2e-4, atol=2e-4)
    unordered, _ = dispatched(q, kv_cache, md, scale, **kw)
    np.testing.assert_array_equal(np.asarray(unordered)[:live], got[:live])
    # The same rows in another arrangement: row i is now row perm[i].
    perm = np.arange(r_pad)
    perm[:live] = rng.permutation(live)
    shuffled = dataclasses.replace(
        md, seq_lens=md.seq_lens[perm], block_tables=md.block_tables[perm])
    moved, _ = dispatched(
        q[perm], kv_cache, _with_order(shuffled), scale, **kw)
    np.testing.assert_array_equal(np.asarray(moved)[:live], got[perm][:live])


def _tiles_the_kernel_runs(monkeypatch, kv_lens, r_pad, order=True):
    """Tiles the kernel's own loop waits for in one call (on its one heads
    block), counted by the interpreter: a callback a ``_TileCopy.wait``."""
    import vllm_tpu.ops.rpa_decode_kernel as dk

    waits = []
    real = dk._TileCopy.wait

    def counted(self):
        jax.debug.callback(lambda: waits.append(1))
        return real(self)

    monkeypatch.setattr(dk._TileCopy, "wait", counted)
    dk.decode_paged_attention.clear_cache()
    rng = np.random.default_rng(15)
    q, kv_cache, md = _decode_case(
        rng, kv_lens or [1], 2, 4, 128, 8, num_blocks=2 + sum(
            -(-kv // 8) for kv in kv_lens or [1]), r_pad=r_pad)
    md = _with_live(md, len(kv_lens))
    jax.block_until_ready(_run_decode_kernel(
        q, kv_cache, 0, md, 128 ** -0.5,
        order=decode_row_order(md.seq_lens, md.num_seqs) if order else None))
    jax.effects_barrier()
    monkeypatch.undo()
    dk.decode_paged_attention.clear_cache()
    return len(waits)


@pytest.mark.parametrize("kv_lens,r_pad", [
    ([], 8), ([40], 8), ([90, 3, 16, 7, 33], 8), ([16] * 8, 8),
    ([5, 70, 9, 2, 31, 64, 17, 1, 48], 32)],
    ids=["no_row", "one_row", "five_rows", "equal_rows", "nine_of_32"])
def test_decode_tile_counts_are_the_kernels_own(monkeypatch, kv_lens, r_pad):
    """``decode_tile_counts``'s ``run`` is what the kernel's loop runs under
    the step's order (2 rows a block, tiles of 2 pages of 8), dead blocks
    nothing; rows of equal context run the least their keys need; and
    without the order the same rows run more or as many."""
    blocks = dict(page_size=8, **BLK)
    run, least = decode_tile_counts(
        np.asarray(kv_lens, np.int32), len(kv_lens), **blocks)
    assert run == _tiles_the_kernel_runs(monkeypatch, kv_lens, r_pad)
    assert least == sum(-(-kv // 16) for kv in kv_lens) / 2
    assert run >= least
    if len(set(kv_lens)) == 1 and len(kv_lens) % 2 == 0:
        assert run == least
    assert run <= _tiles_the_kernel_runs(
        monkeypatch, kv_lens, r_pad, order=False)


@pytest.mark.parametrize("on_chip,window,want", [
    (True, None, {"run": 9 + 8, "least": 4.0 + 5.25}),
    (False, None, {"run": 0, "least": 0.0}),
    (True, 4096, {"run": 0, "least": 0.0}),
], ids=["decode_kernel", "no_kernel_here", "every_call_states_a_window"])
def test_the_runner_counts_a_decode_only_steps_tiles(
        monkeypatch, on_chip, window, want):
    """``ModelRunner._count_decode_tiles``, what a decode-only step adds to
    ``vllm:attn_decode_tiles_total``: one call's tiles by the blocks the
    dispatcher would run it with (4 rows, 128 keys of 16-token pages), and
    nothing where the step's plain call is not the decode kernel's."""
    import types

    import vllm_tpu.ops.attention as attention
    from vllm_tpu.worker.model_runner import ModelRunner

    monkeypatch.setattr(attention, "_on_tpu", lambda: on_chip)
    runner = types.SimpleNamespace(
        _attn_call_geometry=((32, 8, 128, 16), False),
        model=types.SimpleNamespace(sliding_window=window),
        attn_decode_tiles={"run": 0, "least": 0.0}, _decode_tilings={})
    # 5 rows: [900, 300, 200, 130] and [16] run 8 + 1 tiles of 128 keys;
    # their own 8 + 3 + 2 + 2 + 1, four rows a tile, are 4.
    ModelRunner._count_decode_tiles(
        runner, np.asarray([200, 900, 16, 130, 300], np.int32), 128, 64)
    # 6 rows, a step later: [700, 520, 400, 260] and [129, 1] run 6 + 2;
    # their own 6 + 5 + 4 + 3 + 2 + 1 are 5.25.
    ModelRunner._count_decode_tiles(
        runner, np.asarray([1, 520, 700, 129, 400, 260], np.int32), 128, 64)
    assert runner.attn_decode_tiles == want


# ----------------------------------------------------------------------
# Dispatcher eligibility (ops/attention.py)
# ----------------------------------------------------------------------


@pytest.fixture
def pallas_interpret_env(monkeypatch):
    import vllm_tpu.envs as envs

    def setenv(**kw):
        for key, val in kw.items():
            monkeypatch.setenv(key, val)
        envs.refresh()

    setenv(VLLM_TPU_PALLAS_INTERPRET="1")
    yield setenv
    monkeypatch.undo()
    envs.refresh()


def _spy(monkeypatch, module, name, call_real=True):
    calls = []
    real = getattr(module, name)

    def wrapper(*args, **kwargs):
        calls.append(name)
        if call_real:
            return real(*args, **kwargs)
        return jnp.zeros_like(args[0])

    monkeypatch.setattr(module, name, wrapper)
    return calls


def _dispatch(q, kv_cache, md, scale, **kw):
    return dispatch_ragged_attention(
        q, kv_cache, jnp.int32(0), md, scale, allow_interpret=True, **kw
    )


def test_dispatch_decode_only_takes_decode_kernel(
    monkeypatch, pallas_interpret_env
):
    import vllm_tpu.ops.rpa_decode_kernel as dk

    rng = np.random.default_rng(6)
    kh, h, d, bs = 2, 4, 128, 8
    q, kv_cache, md = _decode_case(
        rng, [9, 22], kh, h, d, bs, num_blocks=32
    )
    calls = _spy(monkeypatch, dk, "decode_paged_attention")
    got = _dispatch(q, kv_cache, md, d ** -0.5)
    assert calls, "decode-only batch did not route to the decode kernel"
    want = ref_ragged_paged_attention(
        q, kv_cache, jnp.int32(0), md, d ** -0.5
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def _layout_case(rng, layout, kv_lens, r_pad, window=None):
    """A decode-only step of the layout: the dispatcher's arguments, and
    the XLA reference's answer over the live rows (under ``window``)."""
    import dataclasses

    n, bs = len(kv_lens), 8
    kw = {}
    if layout == "split_sinks":
        from tests.models.test_split_kv_attention import _view, split_case

        kh, h, dk, dv = 2, 8, 96, 64
        q, kv_cache, md, _, _ = split_case(
            rng, [1] * n, kv_lens, kh, h, dk, dv, bs=bs, r_pad=r_pad)
        md = dataclasses.replace(md, decode_only=True)
        sinks = jnp.asarray(rng.standard_normal(h) + 1.0, jnp.float32)
        kw.update(sinks=sinks, num_kv_heads=kh, layer=1)
        scale = dk ** -0.5
        qp, view = _view(q, kv_cache, kh)
        want = ref_ragged_paged_attention(  # (its md holds live tokens only)
            qp[:n], view, jnp.int32(1), md, scale, sliding_window=window,
            sinks=sinks, head_major=True)
    else:
        kh, h, d = 2, 4, 128 if layout == "plain" else 64
        q, kv_cache, md = _decode_case(
            rng, kv_lens, kh, h, d, bs, num_blocks=2 + sum(
                -(-kv // bs) for kv in kv_lens), r_pad=r_pad)
        scale = d ** -0.5
        want = ref_ragged_paged_attention(
            q, kv_cache, jnp.int32(0), md, scale, sliding_window=window)
    return q, kv_cache, md, scale, kw, np.asarray(want)[:n]


# Contexts shorter than the window of 16, equal to it and past it, a floor
# inside a page (60 - 16 = 44) and on a page's edge (40 - 16 = 24: page 3 of
# 8 tokens), dead rows behind the live ones, and fewer live rows than the
# decode kernel's block of 4 on the chip.
ROWS = {
    "five_rows_of_sixteen": ([60, 9, 16, 40, 33], 16),
    "three_rows_of_four": ([41, 5, 24], 4),
}


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize(
    "window", [None, 16, 4096],
    ids=["no_window", "window_16", "window_past_every_context"])
@pytest.mark.parametrize("layout", ["plain", "packed_64", "split_sinks"])
def test_dispatch_decode_only_lowers_by_the_window(layout, window, rows):
    """A decode-only batch lowers to ``rpa_kernel<suffix>`` when the call
    states a window, whatever its length, and to ``rpa_decode_kernel
    <suffix>`` when it states none; either way the live rows are the XLA
    reference's. The decode kernel writes its dead rows (finite); the
    ragged kernel writes no row past the live ones, in a decode-only step
    as in a mixed step's padding, and what the interpreter leaves there is
    not a number: the runner reads neither. Under a window of 16 the
    pages wholly under a row's floor are the null page, as a window
    group's manager leaves them once it gave them back."""
    from tests.models.test_laguna import null_under_the_floor

    kv_lens, r_pad = ROWS[rows]
    n, bs = len(kv_lens), 8
    q, kv_cache, md, scale, kw, want = _layout_case(
        np.random.default_rng(12), layout, kv_lens, r_pad, window=window)
    kw.update(sliding_window=window, kernel_suffix="_kind")
    if window == 16:
        md = null_under_the_floor(md, window, bs)
    got, names = dispatched(q, kv_cache, md, scale, **kw)
    assert names == [
        ("rpa_decode_kernel" if window is None else "rpa_kernel") + "_kind"]
    assert got.shape == (r_pad, *want.shape[1:])
    np.testing.assert_allclose(
        np.asarray(got)[:n], want, rtol=2e-4, atol=2e-4)
    assert np.isfinite(np.asarray(got)[:n if window else r_pad]).all()


def test_dispatch_mixed_batch_takes_general_kernel(
    monkeypatch, pallas_interpret_env
):
    """A mixed prefill+decode batch (decode_only unset) must stay on the
    general ragged kernel even though some rows are decodes."""
    import dataclasses

    import vllm_tpu.ops.rpa_decode_kernel as dk
    import vllm_tpu.ops.rpa_kernel as rk

    rng = np.random.default_rng(7)
    kh, h, d, bs = 2, 4, 128, 8
    q, kv_cache, md = _decode_case(
        rng, [9, 22], kh, h, d, bs, num_blocks=32
    )
    md = dataclasses.replace(md, decode_only=False)
    decode_calls = _spy(monkeypatch, dk, "decode_paged_attention")
    # Routing only: the general kernel's numerics are
    # tests/models/test_ragged_paged_attention.py's business.
    general_calls = _spy(
        monkeypatch, rk, "ragged_paged_attention", call_real=False
    )
    _dispatch(q, kv_cache, md, d ** -0.5)
    assert general_calls and not decode_calls


def test_dispatch_lse_takes_general_kernel(
    monkeypatch, pallas_interpret_env
):
    import vllm_tpu.ops.rpa_decode_kernel as dk
    import vllm_tpu.ops.rpa_kernel as rk

    rng = np.random.default_rng(8)
    kh, h, d, bs = 2, 4, 128, 8
    q, kv_cache, md = _decode_case(
        rng, [9, 22], kh, h, d, bs, num_blocks=32
    )
    decode_calls = _spy(monkeypatch, dk, "decode_paged_attention")
    general_calls = _spy(
        monkeypatch, rk, "ragged_paged_attention", call_real=False
    )
    _dispatch(q, kv_cache, md, d ** -0.5, return_lse=True)
    assert general_calls and not decode_calls


def test_dispatch_env_escape_hatch(monkeypatch, pallas_interpret_env):
    import vllm_tpu.ops.rpa_decode_kernel as dk
    import vllm_tpu.ops.rpa_kernel as rk

    pallas_interpret_env(VLLM_TPU_DISABLE_DECODE_KERNEL="1")
    rng = np.random.default_rng(9)
    kh, h, d, bs = 2, 4, 128, 8
    q, kv_cache, md = _decode_case(
        rng, [9, 22], kh, h, d, bs, num_blocks=32
    )
    decode_calls = _spy(monkeypatch, dk, "decode_paged_attention")
    general_calls = _spy(
        monkeypatch, rk, "ragged_paged_attention", call_real=False
    )
    _dispatch(q, kv_cache, md, d ** -0.5)
    assert general_calls and not decode_calls


def test_dispatch_token_row_mismatch_takes_general_kernel(
    monkeypatch, pallas_interpret_env
):
    """decode_only metadata with T != R (defensive: a caller that didn't
    force t_pad == r_pad) must not reach the decode kernel."""
    import vllm_tpu.ops.rpa_decode_kernel as dk
    import vllm_tpu.ops.rpa_kernel as rk

    rng = np.random.default_rng(10)
    kh, h, d, bs = 2, 4, 128, 8
    q, kv_cache, md = _decode_case(
        rng, [9, 22], kh, h, d, bs, num_blocks=32
    )
    q_wide = jnp.concatenate([q, q], axis=0)  # T = 2R
    import dataclasses

    md = dataclasses.replace(
        md,
        query_start_loc=jnp.concatenate(
            [md.query_start_loc, md.query_start_loc[-1:].repeat(2)]
        ),
    )
    decode_calls = _spy(monkeypatch, dk, "decode_paged_attention")
    # The widened batch is deliberately inconsistent for the general
    # kernel too (block tables stay [R, P]); only routing is under test.
    general_calls = _spy(
        monkeypatch, rk, "ragged_paged_attention", call_real=False
    )
    _dispatch(q_wide, kv_cache, md, d ** -0.5)
    assert general_calls and not decode_calls


@pytest.mark.parametrize(
    "path", ["decode", "decode_window", "ragged", "ragged_lse"])
@pytest.mark.parametrize("d", [64, 128])
def test_dispatch_under_tp_mesh_runs_kernels_per_shard(
    pallas_interpret_env, path, d
):
    """Under tp > 1 the kernels run inside a shard_map over the mesh (a
    Mosaic call cannot be partitioned by GSPMD; ``tests/
    test_tpu_aot_compile.py`` asks the chip's compiler): each shard gets
    its own q heads and its own cache rows, and the result — and the LSE —
    equals the unsharded reference. ``decode`` states no window and runs
    the decode kernel a shard, under the step's order of the rows; ``decode_window`` is a decode-only batch
    under a window, the ragged kernel a shard, its window an array."""
    import dataclasses

    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    tp = 2
    mesh = Mesh(
        np.asarray(jax.devices()[:tp]).reshape(1, 1, 1, tp),
        ("dp", "pp", "cp", "tp"),
    )
    rng = np.random.default_rng(11)
    kh, h, bs = 2, 4, 8
    q, kv_cache, md = _decode_case(
        rng, [9, 22, 3], kh, h, d, bs, num_blocks=32, r_pad=4
    )
    scale = d ** -0.5
    lse = path == "ragged_lse"
    window = None if path == "decode" else 16
    md = dataclasses.replace(md, decode_only=path.startswith("decode"))
    want = ref_ragged_paged_attention(
        q, kv_cache, jnp.int32(0), md, scale, return_lse=lse,
        sliding_window=window,
    )
    if path == "decode":  # (the step's order of the rows, replicated)
        md = _with_order(md)
    md_tp = dataclasses.replace(md, tp_mesh=mesh)
    q_sh = jax.device_put(q, NamedSharding(mesh, P(None, "tp", None)))
    kv_sh = jax.device_put(
        kv_cache, NamedSharding(mesh, P(None, None, None, "tp", None))
    )
    got = jax.jit(
        lambda q, kv, md: _dispatch(
            q, kv, md, scale, return_lse=lse, sliding_window=window
        )
    )(q_sh, kv_sh, md_tp)
    n = 3
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g)[:n], np.asarray(w)[:n], rtol=2e-4, atol=2e-4
        )
    assert jax.tree.leaves(got)[0].sharding.spec == P(None, "tp", None)
