"""Keys and values of unlike widths in one page (the split layout of
``ops/attention.py::split_kv_cache_shape``) and a sink logit a q head, in
the XLA path and inside both Pallas kernels (interpret mode): against a
dense softmax written here from the K and V that were handed to
``write_kv``, never read back through the cache's layout."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.models.test_laguna import null_under_the_floor
from vllm_tpu.ops.attention import (
    AttentionMetadata,
    dispatch_ragged_attention,
    paged_attention,
    ref_ragged_paged_attention,
    split_kv_cache_shape,
    split_kv_lanes,
    write_kv,
)
from vllm_tpu.ops.rpa_kernel import ragged_paged_attention

BS = 8


def split_case(rng, q_lens, kv_lens, kh, h, dk, dv, bs=BS, num_layers=2,
               layer=1, r_pad=None):
    """A step over the split layout: every context token's K and V written
    through ``write_kv`` (in two calls, as a chunked prefill would), the
    query tokens the last ``q_len`` of each row. Returns the step's inputs
    and the dense ``k [R, ctx, KH, dk]``, ``v`` they hold."""
    n = len(kv_lens)
    r = r_pad or n
    t = int(sum(q_lens))
    blocks = [-(-kv // bs) for kv in kv_lens]
    num_blocks = sum(blocks) + 2
    tables = np.zeros((r, max(blocks) + 1), np.int32)
    ctx = max(kv_lens)
    k_all = rng.standard_normal((n, ctx, kh, dk)).astype(np.float32)
    v_all = rng.standard_normal((n, ctx, kh, dv)).astype(np.float32)
    cache = jnp.asarray(rng.standard_normal(
        split_kv_cache_shape(num_layers, num_blocks, bs, kh, dk, dv)), jnp.float32)
    nxt, slots_all, rows_all = 1, [], []
    positions, req_idx, slot_map = [], [], []
    start = np.zeros(r + 1, np.int32)
    for i, (ql, kv) in enumerate(zip(q_lens, kv_lens)):
        ids = np.arange(nxt, nxt + blocks[i], dtype=np.int32)
        nxt += blocks[i]
        tables[i, :blocks[i]] = ids
        pos = np.arange(kv)
        slots = ids[pos // bs] * bs + pos % bs
        slots_all.append(slots)
        rows_all.append(np.full(kv, i))
        positions += list(pos[kv - ql:])
        req_idx += [i] * ql
        slot_map += list(slots[kv - ql:])
        start[i + 1:] = start[i] + ql
    slots = np.concatenate(slots_all)
    rows = np.concatenate(rows_all)
    pos = np.concatenate([np.arange(kv) for kv in kv_lens])
    half = len(slots) // 2
    for part in (slice(0, half), slice(half, None)):
        cache = write_kv(
            cache, jnp.int32(layer), jnp.asarray(k_all[rows[part], pos[part]]),
            jnp.asarray(v_all[rows[part], pos[part]]), jnp.asarray(slots[part], jnp.int32))
    seq_lens = np.zeros(r, np.int32)
    seq_lens[:n] = kv_lens
    md = AttentionMetadata(
        positions=jnp.asarray(positions, jnp.int32),
        slot_mapping=jnp.asarray(slot_map, jnp.int32),
        block_tables=jnp.asarray(tables),
        seq_lens=jnp.asarray(seq_lens),
        query_start_loc=jnp.asarray(start),
        token_req_idx=jnp.asarray(req_idx, jnp.int32),
        logits_indices=jnp.asarray(start[1:] - 1),
        num_seqs=jnp.asarray([n], jnp.int32),
    )
    q = jnp.asarray(rng.standard_normal((max(t, r if r_pad else t), h, dk)), jnp.float32)
    return q, cache, md, k_all, v_all


def dense(q, k_all, v_all, md, scale, window=None, sinks=None):
    """``p_ij = exp(s_ij) / (exp(sink) + sum_k exp(s_ik))`` a row, in numpy."""
    q = np.asarray(q, np.float64)
    h, kh = q.shape[1], k_all.shape[2]
    out = np.zeros((len(md.positions), h, v_all.shape[-1]))
    for t, (p, i) in enumerate(zip(np.asarray(md.positions), np.asarray(md.token_req_idx))):
        lo = 0 if window is None else max(0, p - window + 1)
        for a in range(h):
            s = k_all[i, lo:p + 1, a // (h // kh)].astype(np.float64) @ q[t, a] * scale
            m = max(s.max(), -np.inf if sinks is None else float(sinks[a]))
            e = np.exp(s - m)
            den = e.sum() + (0.0 if sinks is None else np.exp(float(sinks[a]) - m))
            out[t, a] = (e / den) @ v_all[i, lo:p + 1, a // (h // kh)]
    return out


def test_the_split_shape_is_head_major_rows_of_a_padded_key_and_a_value():
    assert split_kv_lanes(192, 128) == 384 and split_kv_lanes(96, 64) == 192
    # 8 heads of 16 tokens and 4 heads of 32: one array for both kinds.
    assert (split_kv_cache_shape(3, 10, 16, 8, 192, 128)
            == split_kv_cache_shape(3, 10, 32, 4, 192, 128) == (3, 10, 128, 384))


def test_write_kv_zero_fills_the_keys_padding_and_leaves_other_rows():
    rng = np.random.default_rng(0)
    cache = jnp.asarray(rng.standard_normal(split_kv_cache_shape(2, 4, BS, 2, 96, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((3, 2, 96)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((3, 2, 64)), jnp.float32)
    slots = jnp.asarray([BS + 1, 3 * BS + 7, 2 * BS], jnp.int32)
    new = np.asarray(write_kv(cache, jnp.int32(1), k, v, slots)).reshape(2, 4, 2, BS, 192)
    old = np.asarray(cache).reshape(2, 4, 2, BS, 192)
    np.testing.assert_array_equal(new[0], old[0])
    for i, (page, off) in enumerate(((1, 1), (3, 7), (2, 0))):
        np.testing.assert_array_equal(new[1, page, :, off, :96], np.asarray(k[i]))
        assert not new[1, page, :, off, 96:128].any()
        np.testing.assert_array_equal(new[1, page, :, off, 128:], np.asarray(v[i]))
    changed = (new != old).any(-1)
    assert changed.sum() == 3 * 2  # a row a token and head, nothing else


WIDTHS = [(96, 64), (192, 128)]
MODES = [(None, False), (None, True), (12, False), (12, True)]
MODE_IDS = ["full", "full_sink", "window", "window_sink"]


@pytest.mark.parametrize("window,sink", MODES, ids=MODE_IDS)
@pytest.mark.parametrize("dk,dv", WIDTHS, ids=["96_64", "192_128"])
def test_the_xla_path_against_the_dense_softmax(dk, dv, window, sink):
    rng = np.random.default_rng(1)
    q, cache, md, k_all, v_all = split_case(rng, [1, 6, 1, 9], [30, 19, 5, 41], 2, 8, dk, dv)
    sinks = jnp.asarray(rng.standard_normal(8) + 1.0, jnp.float32) if sink else None
    scale = dk ** -0.5
    got = paged_attention(q, cache, jnp.int32(1), md, scale, sliding_window=window,
                          sinks=sinks, num_kv_heads=2)
    assert got.shape == (17, 8, dv)
    np.testing.assert_allclose(
        np.asarray(got), dense(q, k_all, v_all, md, scale, window, sinks), rtol=2e-5, atol=2e-5)


def test_a_sink_takes_its_share_of_a_row():
    """With one key a row, ``p = 1 / (1 + exp(sink - score))``: the sink
    is no no-op, and a very negative one is."""
    rng = np.random.default_rng(2)
    q, cache, md, k_all, v_all = split_case(rng, [1], [1], 1, 2, 96, 64)
    scale = 96 ** -0.5
    plain = paged_attention(q, cache, jnp.int32(1), md, scale, num_kv_heads=1)
    np.testing.assert_allclose(np.asarray(plain)[0, 0], v_all[0, 0, 0], rtol=1e-6)
    sinks = jnp.asarray([0.5, -1e9], jnp.float32)
    got = np.asarray(paged_attention(q, cache, jnp.int32(1), md, scale, sinks=sinks, num_kv_heads=1))
    score = float(np.asarray(q)[0, 0] @ k_all[0, 0, 0]) * scale
    np.testing.assert_allclose(got[0, 0], v_all[0, 0, 0] / (1 + np.exp(0.5 - score)), rtol=1e-5)
    np.testing.assert_allclose(got[0, 1], np.asarray(plain)[0, 1], rtol=1e-6)


def _ragged(q, cache5, md, scale, **kw):
    return ragged_paged_attention(
        q, cache5, jnp.asarray([1], jnp.int32), md.seq_lens, md.block_tables,
        md.query_start_loc, md.num_seqs, sm_scale=scale, interpret=True,
        num_kv_pages_per_block=2, num_queries_per_block=8, head_major=True, **kw)


def _view(q, cache, kh):
    """What the dispatch hands the kernels: q padded to the key's stored
    lanes, the cache seen ``[L, NB, KH, BS, lanes]``."""
    pad = -q.shape[-1] % 128
    nl, nb, rows, lanes = cache.shape
    return (jnp.pad(q, ((0, 0), (0, 0), (0, pad))),
            cache.reshape(nl, nb, kh, rows // kh, lanes))


@pytest.mark.parametrize("window,sink", MODES, ids=MODE_IDS)
@pytest.mark.parametrize("kh,h", [(2, 8), (4, 8)], ids=["4_a_kv_head", "2_a_kv_head"])
@pytest.mark.parametrize("dk,dv", WIDTHS, ids=["96_64", "192_128"])
def test_the_ragged_kernel_on_the_split_layout(dk, dv, kh, h, window, sink):
    rng = np.random.default_rng(3)
    q, cache, md, k_all, v_all = split_case(rng, [1, 6, 1, 9], [30, 19, 5, 41], kh, h, dk, dv)
    sinks = jnp.asarray(rng.standard_normal(h) + 1.0, jnp.float32) if sink else None
    scale = dk ** -0.5
    qp, view = _view(q, cache, kh)
    if window:
        md = null_under_the_floor(md, window, BS)
    got = _ragged(qp, view, md, scale, sinks=sinks,
                  sliding_window=None if window is None else jnp.int32(window))
    assert got.shape == (17, h, dv)
    np.testing.assert_allclose(
        np.asarray(got), dense(q, k_all, v_all, md, scale, window, sinks), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window,sink", MODES, ids=MODE_IDS)
@pytest.mark.parametrize("kh,h", [(2, 8), (4, 8)], ids=["4_a_kv_head", "2_a_kv_head"])
@pytest.mark.parametrize("dk,dv", WIDTHS, ids=["96_64", "192_128"])
def test_a_decode_only_step_on_the_split_layout(dk, dv, kh, h, window, sink):
    """As the dispatcher lowers it: the decode kernel for a full layer, the
    ragged kernel for a window layer (the decode kernel has no window)."""
    from tests.models.test_decode_attention import dispatched

    rng = np.random.default_rng(4)
    kv_lens = [33, 1, 17, 2, 9]
    q, cache, md, k_all, v_all = split_case(
        rng, [1] * 5, kv_lens, kh, h, dk, dv, r_pad=8)
    sinks = jnp.asarray(rng.standard_normal(h) + 1.0, jnp.float32) if sink else None
    scale = dk ** -0.5
    md = dataclasses.replace(md, decode_only=True)
    if window:
        md = null_under_the_floor(md, window, BS)
    got, names = dispatched(
        q, cache, md, scale, layer=1, sinks=sinks, num_kv_heads=kh,
        sliding_window=None if window is None else jnp.int32(window))
    assert names == ["rpa_kernel" if window else "rpa_decode_kernel"]
    assert got.shape == (8, h, dv)
    np.testing.assert_allclose(
        np.asarray(got)[:5], dense(q[:5], k_all, v_all, md, scale, window, sinks),
        rtol=2e-4, atol=2e-4)
    if not window:  # the decode kernel's dead rows: finite, never read
        assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
def test_a_row_whose_first_tile_is_wholly_masked(sink):
    """A decode row of a window layer whose floor lies inside the first
    KV block the kernel visits, past its first sub-tile: the tile's every
    key is masked, and with a sink the row's state must stay exact there
    (``m`` the sink's logit, ``l`` 1), not an average of the tile's
    values. Two pages a block of 8 tokens, a window of 5 at position 30:
    the block from 16 holds 16..31, keys 16..25 are masked."""
    rng = np.random.default_rng(5)
    q, cache, md, k_all, v_all = split_case(rng, [1, 1], [31, 14], 2, 4, 96, 64)
    sinks = jnp.asarray([2.0, -1.0, 0.5, 3.0], jnp.float32) if sink else None
    scale = 96 ** -0.5
    qp, view = _view(q, cache, 2)
    md = null_under_the_floor(md, 5, BS)
    want = dense(q, k_all, v_all, md, scale, 5, sinks)
    got = ragged_paged_attention(
        qp, view, jnp.asarray([1], jnp.int32), md.seq_lens, md.block_tables,
        md.query_start_loc, md.num_seqs, sm_scale=scale, interpret=True,
        num_kv_pages_per_block=2, num_queries_per_block=8, head_major=True,
        sinks=sinks, sliding_window=jnp.int32(5))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)
    # And as the decode-only step of that layer, through the dispatcher.
    from tests.models.test_decode_attention import dispatched

    dec, names = dispatched(
        q, cache, dataclasses.replace(md, decode_only=True), scale, layer=1,
        sinks=sinks, num_kv_heads=2, sliding_window=jnp.int32(5))
    assert names == ["rpa_kernel"]
    np.testing.assert_allclose(np.asarray(dec), want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
def test_a_sink_inside_the_kernels_of_the_layouts_there_were(sink):
    """The interleaved layout (one head_dim of 128) takes ``sinks`` too,
    in both kernels; without it the call is the one it was."""
    from tests.models.test_decode_attention import _decode_case, _run_decode_kernel
    from tests.models.test_ragged_paged_attention import _random_case, _run_kernel

    rng = np.random.default_rng(6)
    sinks = jnp.asarray(rng.standard_normal(8) + 1.0, jnp.float32) if sink else None
    q, cache, md = _random_case(rng, 3, [1, 7, 1], [22, 15, 9], 2, 8, 128, BS, num_blocks=32)
    scale = 128 ** -0.5
    want = ref_ragged_paged_attention(q, cache, jnp.int32(0), md, scale, sinks=sinks)
    np.testing.assert_allclose(
        np.asarray(_run_kernel(q, cache, 0, md, scale, sinks=sinks))[:9],
        np.asarray(want), rtol=2e-4, atol=2e-4)
    q, cache, md = _decode_case(rng, [22, 15, 9], 2, 8, 128, BS, num_blocks=32)
    want = ref_ragged_paged_attention(q, cache, jnp.int32(0), md, scale, sinks=sinks)
    np.testing.assert_allclose(
        np.asarray(_run_decode_kernel(q, cache, 0, md, scale, sinks=sinks)),
        np.asarray(want), rtol=2e-4, atol=2e-4)
    if sink:
        plain = ref_ragged_paged_attention(q, cache, jnp.int32(0), md, scale)
        assert not np.allclose(np.asarray(plain), np.asarray(want), atol=1e-2)


def test_the_dispatch_keeps_the_decode_kernel_for_a_sink_and_the_split_layout(monkeypatch):
    """``decode_ok`` stays true with ``sinks`` (they ask for no LSE) where
    the call states no window, and both kernels get the head-major view,
    the padded query and the names."""
    from vllm_tpu.ops import attention, rpa_decode_kernel, rpa_kernel

    seen = []
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    for module, fn in ((rpa_kernel, "ragged_paged_attention"),
                       (rpa_decode_kernel, "decode_paged_attention")):
        monkeypatch.setattr(
            module, fn, lambda q, kv, *a, name, sinks=None, head_major=False, **kw: (seen.append(
                (name, q.shape[-1], kv.shape[2:], sinks is not None, head_major)), q)[1])
    rng = np.random.default_rng(7)
    sinks = jnp.ones(8, jnp.float32)
    q, cache, md, *_ = split_case(rng, [1, 3], [9, 12], 2, 8, 192, 128)
    paged_attention(q, cache, 1, md, 1.0, sliding_window=4, kernel_suffix="_window",
                    sinks=sinks, num_kv_heads=2)
    q, cache, md, *_ = split_case(rng, [1, 1], [9, 12], 2, 8, 192, 128)
    md = dataclasses.replace(md, decode_only=True)
    paged_attention(q, cache, 1, md, 1.0, sliding_window=4, kernel_suffix="_window",
                    sinks=sinks, num_kv_heads=2)
    paged_attention(q, cache, 1, md, 1.0, sinks=sinks, num_kv_heads=2)
    paged_attention(q, cache, 1, md, 1.0, num_kv_heads=2)
    assert seen == [
        ("rpa_kernel_window", 256, (2, BS, 384), True, True),
        ("rpa_kernel_window", 256, (2, BS, 384), True, True),
        ("rpa_decode_kernel", 256, (2, BS, 384), True, True),
        ("rpa_decode_kernel", 256, (2, BS, 384), False, True),
    ]


def test_under_a_tensor_parallel_mesh_the_sinks_are_split_with_the_heads(monkeypatch):
    """``_call_attention_kernel`` under tp = 2: a shard gets its own q
    heads, its own KV heads of the head-major view and its own heads'
    sinks; the answer is the unsharded one."""
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    from jax.sharding import Mesh

    monkeypatch.setenv("VLLM_TPU_PALLAS_INTERPRET", "1")
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 1, 1, 2), ("dp", "pp", "cp", "tp"))
    rng = np.random.default_rng(8)
    sinks = jnp.asarray(rng.standard_normal(8) + 1.0, jnp.float32)
    scale = 96 ** -0.5
    # A mixed step and a decode-only one under a window (the ragged kernel a
    # shard), and a decode-only one without (the decode kernel a shard).
    for q_lens, kv_lens, decode, window in (
            ([1, 6, 1], [30, 19, 5], False, 12), ([1, 1, 1, 1], [30, 19, 5, 2], True, 12),
            ([1, 1, 1, 1], [30, 19, 5, 2], True, None)):
        q, cache, md, k_all, v_all = split_case(rng, q_lens, kv_lens, 2, 8, 96, 64)
        md = dataclasses.replace(md, tp_mesh=mesh, decode_only=decode)
        got = jax.jit(lambda q, cache, md, sinks: dispatch_ragged_attention(
            q, cache, jnp.int32(1), md, scale, sliding_window=window, sinks=sinks,
            num_kv_heads=2, allow_interpret=True))(q, cache, md, sinks)
        np.testing.assert_allclose(
            np.asarray(got), dense(q, k_all, v_all, md, scale, window, sinks), rtol=2e-4, atol=2e-4)
