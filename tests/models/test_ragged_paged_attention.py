"""Kernel-tier tests (SURVEY.md §4 tier 3): the XLA ragged paged attention
reference and the in-repo Pallas kernel (``ops/rpa_kernel.py``, interpret
mode on CPU) against the JAX-bundled reference implementation — over
prefill/decode mixes, layer indexing, head_dim {64, 128}, sliding window,
and the LSE output contract (``csrc/attention/merge_attn_states.cu``).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vllm_tpu.ops.attention import (
    AttentionMetadata,
    kv_cache_shape,
    packed_kv_layout,
    ref_ragged_paged_attention,
    write_kv,
)


def _to_interleaved(kv_layer, d):
    """Convert one layer of the framework cache to the JAX-bundled
    reference's interleaved [NB, BS, 2*KH, D] layout."""
    nb, bs, rows, lanes = kv_layer.shape
    if not packed_kv_layout(d):
        return kv_layer
    return kv_layer.reshape(nb, bs, rows, 2, d).reshape(nb, bs, 2 * rows, d)


def _random_case(rng, num_seqs, q_lens, kv_lens, kh, h, d, bs, num_blocks,
                 num_layers=1, layer=0):
    """Build a mixed prefill/decode batch. q tokens are the LAST q_len
    tokens of each request's kv_len context."""
    assert len(q_lens) == len(kv_lens) == num_seqs
    t = int(sum(q_lens))
    q = jnp.asarray(rng.standard_normal((t, h, d)), jnp.float32)

    max_blocks = max(-(-kv // bs) for kv in kv_lens) + 1
    block_tables = np.zeros((num_seqs, max_blocks), np.int32)
    kv_cache = jnp.asarray(
        rng.standard_normal(kv_cache_shape(num_layers, num_blocks, bs, kh, d)),
        jnp.float32,
    )

    positions = np.zeros(t, np.int32)
    token_req_idx = np.zeros(t, np.int32)
    slot_mapping = np.zeros(t, np.int32)
    seq_lens = np.asarray(kv_lens, np.int32)
    query_start_loc = np.zeros(num_seqs + 1, np.int32)

    next_block = 1
    offset = 0
    for i in range(num_seqs):
        nb = -(-kv_lens[i] // bs)
        blocks = np.arange(next_block, next_block + nb, dtype=np.int32)
        next_block += nb
        block_tables[i, :nb] = blocks
        pos = np.arange(kv_lens[i] - q_lens[i], kv_lens[i], dtype=np.int32)
        positions[offset : offset + q_lens[i]] = pos
        token_req_idx[offset : offset + q_lens[i]] = i
        slot_mapping[offset : offset + q_lens[i]] = blocks[pos // bs] * bs + pos % bs
        offset += q_lens[i]
        query_start_loc[i + 1] = offset
    assert next_block <= num_blocks

    md = AttentionMetadata(
        positions=jnp.asarray(positions),
        slot_mapping=jnp.asarray(slot_mapping),
        block_tables=jnp.asarray(block_tables),
        seq_lens=jnp.asarray(seq_lens),
        query_start_loc=jnp.asarray(query_start_loc),
        token_req_idx=jnp.asarray(token_req_idx),
        logits_indices=jnp.asarray(query_start_loc[1:] - 1),
        num_seqs=jnp.asarray([num_seqs], jnp.int32),
    )
    # Insert this step's K/V at the q token slots so cache + metadata agree.
    k_new = jnp.asarray(rng.standard_normal((t, kh, d)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((t, kh, d)), jnp.float32)
    kv_cache = write_kv(kv_cache, jnp.int32(layer), k_new, v_new, md.slot_mapping)
    return q, kv_cache, md


def _bundled_ref(q, kv_layer, md, n_seqs, **kw):
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
        ref_ragged_paged_attention as bundled,
    )

    kv_layer = _to_interleaved(kv_layer, q.shape[-1])
    return bundled(
        q, kv_layer, md.seq_lens, md.block_tables, md.query_start_loc,
        np.asarray([n_seqs], np.int32), **kw,
    )


CASES = [
    # (q_lens, kv_lens): pure decode, pure prefill, mixed, chunked prefill
    ([1, 1, 1], [17, 40, 5]),
    ([16, 24], [16, 24]),
    ([1, 13, 1, 8], [33, 13, 9, 30]),
    ([8], [32]),  # chunked prefill: last 8 tokens of a 32-token context
]


@pytest.mark.parametrize("q_lens,kv_lens", CASES)
@pytest.mark.parametrize("kh,h", [(2, 4), (1, 1)])
def test_ref_matches_bundled_kernel_reference(q_lens, kv_lens, kh, h):
    rng = np.random.default_rng(0)
    d, bs = 32, 8
    q, kv_cache, md = _random_case(
        rng, len(q_lens), q_lens, kv_lens, kh, h, d, bs, num_blocks=64
    )
    scale = d ** -0.5

    ours = ref_ragged_paged_attention(q, kv_cache, jnp.int32(0), md, scale)
    theirs = _bundled_ref(q, kv_cache[0], md, len(q_lens), sm_scale=scale)
    t_live = int(sum(q_lens))
    np.testing.assert_allclose(
        np.asarray(ours)[:t_live], np.asarray(theirs), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("q_lens,kv_lens", [([1, 5], [40, 25])])
def test_sliding_window(q_lens, kv_lens):
    rng = np.random.default_rng(1)
    kh, h, d, bs = 2, 4, 32, 8
    q, kv_cache, md = _random_case(
        rng, len(q_lens), q_lens, kv_lens, kh, h, d, bs, num_blocks=64
    )
    scale = d ** -0.5
    ours = ref_ragged_paged_attention(
        q, kv_cache, jnp.int32(0), md, scale, sliding_window=16
    )
    theirs = _bundled_ref(
        q, kv_cache[0], md, len(q_lens), sm_scale=scale, sliding_window=16
    )
    t_live = int(sum(q_lens))
    np.testing.assert_allclose(
        np.asarray(ours)[:t_live], np.asarray(theirs), rtol=2e-5, atol=2e-5
    )


def test_ref_layer_indexing():
    """The layer argument selects the right slice of the stacked cache."""
    rng = np.random.default_rng(2)
    kh, h, d, bs = 2, 4, 32, 8
    q, kv_cache, md = _random_case(
        rng, 2, [1, 4], [9, 12], kh, h, d, bs, num_blocks=16,
        num_layers=3, layer=2,
    )
    ours = ref_ragged_paged_attention(q, kv_cache, jnp.int32(2), md, d**-0.5)
    theirs = _bundled_ref(q, kv_cache[2], md, 2, sm_scale=d**-0.5)
    np.testing.assert_allclose(
        np.asarray(ours)[:5], np.asarray(theirs), rtol=2e-5, atol=2e-5
    )


# ----------------------------------------------------------------------
# In-repo Pallas kernel (interpret mode on CPU)
# ----------------------------------------------------------------------


def _run_kernel(q, kv_cache, layer, md, scale, **kw):
    from vllm_tpu.ops.rpa_kernel import ragged_paged_attention

    return ragged_paged_attention(
        q,
        kv_cache,
        jnp.asarray([layer], jnp.int32),
        md.seq_lens,
        md.block_tables,
        md.query_start_loc,
        md.num_seqs,
        sm_scale=scale,
        interpret=True,
        num_kv_pages_per_block=2,
        num_queries_per_block=8,
        **kw,
    )


@pytest.mark.parametrize("q_lens,kv_lens", CASES)
@pytest.mark.parametrize("d", [64, 128])
def test_pallas_kernel_interpret(q_lens, kv_lens, d):
    rng = np.random.default_rng(3)
    kh, h, bs = 2, 4, 8
    q, kv_cache, md = _random_case(
        rng, len(q_lens), q_lens, kv_lens, kh, h, d, bs, num_blocks=64
    )
    scale = d ** -0.5
    got = _run_kernel(q, kv_cache, 0, md, scale)
    want = _bundled_ref(q, kv_cache[0], md, len(q_lens), sm_scale=scale)
    t_live = int(sum(q_lens))
    np.testing.assert_allclose(
        np.asarray(got)[:t_live], np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_pallas_kernel_layer_indexing():
    rng = np.random.default_rng(4)
    kh, h, d, bs = 2, 4, 64, 8
    q, kv_cache, md = _random_case(
        rng, 2, [1, 6], [11, 14], kh, h, d, bs, num_blocks=16,
        num_layers=3, layer=1,
    )
    scale = d ** -0.5
    got = _run_kernel(q, kv_cache, 1, md, scale)
    want = _bundled_ref(q, kv_cache[1], md, 2, sm_scale=scale)
    np.testing.assert_allclose(
        np.asarray(got)[:7], np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_pallas_kernel_sliding_window():
    rng = np.random.default_rng(5)
    kh, h, d, bs = 2, 4, 128, 8
    q_lens, kv_lens = [1, 5], [40, 25]
    q, kv_cache, md = _random_case(
        rng, 2, q_lens, kv_lens, kh, h, d, bs, num_blocks=64
    )
    scale = d ** -0.5
    got = _run_kernel(q, kv_cache, 0, md, scale, sliding_window=16)
    want = _bundled_ref(
        q, kv_cache[0], md, 2, sm_scale=scale, sliding_window=16
    )
    np.testing.assert_allclose(
        np.asarray(got)[:6], np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_pallas_kernel_lse():
    """LSE output equals log-sum-exp of the masked scaled scores."""
    rng = np.random.default_rng(6)
    kh, h, d, bs = 2, 4, 64, 8
    q_lens, kv_lens = [1, 7, 3], [19, 23, 3]
    q, kv_cache, md = _random_case(
        rng, 3, q_lens, kv_lens, kh, h, d, bs, num_blocks=64
    )
    scale = d ** -0.5
    got, lse = _run_kernel(q, kv_cache, 0, md, scale, return_lse=True)
    t_live = int(sum(q_lens))

    # Reference LSE from the gather path's scores.
    pages = kv_cache[0][md.block_tables]
    r, b = md.block_tables.shape
    ctx = b * bs
    kv_req = pages.reshape(r, ctx, 2 * kh, d)
    k_all = kv_req[:, :, 0::2]
    k_t = k_all[np.asarray(md.token_req_idx)]
    qg = np.asarray(q).reshape(-1, kh, h // kh, d)
    scores = np.einsum("tkgd,tckd->tkgc", qg, np.asarray(k_t)) * scale
    ctx_pos = np.arange(ctx)[None, :]
    causal = ctx_pos <= np.asarray(md.positions)[:, None]
    scores = np.where(causal[:, None, None, :], scores, -np.inf)
    want_lse = np.log(np.sum(np.exp(scores), axis=-1)).reshape(-1, h)

    np.testing.assert_allclose(
        np.asarray(lse)[:t_live], want_lse[:t_live], rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("cp", [2, 4])
@pytest.mark.parametrize("d", [64, 128])
def test_pallas_kernel_striped_context(cp, d):
    """ctx_stride/ctx_phase striped view: per-rank kernel partials merge
    to the full-context answer (the CP fast path's contract). Includes a
    1-page seq so some ranks hold ZERO pages of it (dummy-block path)."""
    import dataclasses

    from vllm_tpu.ops.cp_attention import merge_attn_states

    rng = np.random.default_rng(7)
    kh, h, bs = 2, 4, 8
    q_lens = [1, 5, 2, 1]
    kv_lens = [40, 33, 3, 17]  # 3-token seq: 1 page -> zero on ranks > 0
    q, kv_cache, md = _random_case(
        rng, len(q_lens), q_lens, kv_lens, kh, h, d, bs, num_blocks=64
    )
    scale = d ** -0.5
    t_live = int(sum(q_lens))
    full = np.asarray(_run_kernel(q, kv_cache, 0, md, scale))[:t_live]

    bt = np.asarray(md.block_tables)
    b = bt.shape[1]
    b_local = -(-b // cp)
    outs_k, lses_k, outs_r, lses_r = [], [], [], []
    for rank in range(cp):
        cols = np.arange(b_local) * cp + rank
        valid = cols < b
        lbt = np.where(valid[None, :], bt[:, np.clip(cols, 0, b - 1)], 0)
        md_r = dataclasses.replace(md, block_tables=jnp.asarray(lbt))
        o_k, l_k = _run_kernel(
            q, kv_cache, 0, md_r, scale, return_lse=True,
            ctx_stride=cp, ctx_phase=rank,
        )
        o_r, l_r = ref_ragged_paged_attention(
            q, kv_cache, jnp.int32(0), md_r, scale, return_lse=True,
            ctx_stride=cp, ctx_phase=rank,
        )
        outs_k.append(np.asarray(o_k, np.float32)[:t_live])
        lses_k.append(np.asarray(l_k)[:t_live])
        outs_r.append(np.asarray(o_r, np.float32)[:t_live])
        lses_r.append(np.asarray(l_r)[:t_live])
        # Where a rank holds real context, kernel partials match the ref
        # (fully-masked rows differ only in the -huge lse encoding).
        live = lses_r[-1] > -1e30
        np.testing.assert_allclose(
            lses_k[-1][live], lses_r[-1][live], rtol=2e-4, atol=2e-4
        )

    merged_k = np.asarray(merge_attn_states(
        jnp.asarray(np.stack(outs_k)), jnp.asarray(np.stack(lses_k))
    ))
    merged_r = np.asarray(merge_attn_states(
        jnp.asarray(np.stack(outs_r)), jnp.asarray(np.stack(lses_r))
    ))
    np.testing.assert_allclose(merged_k, full, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(merged_r, full, rtol=3e-4, atol=3e-4)


# ----------------------------------------------------------------------
# The rungs of a visit, at the blocks the kernel runs on the chip: 32
# pages of 16 keys a KV block, narrow steps of 16 pages, and with 2 q
# heads a KV head in float32 a narrow window of 4 query tokens.
# ----------------------------------------------------------------------

RUNG_PAGE, RUNG_KV_PAGES, RUNG_Q = 16, 32, 32

RUNG_MIXES = {
    # A decode row alone in its query block; its last KV block holds 188.
    "decode_row_alone": ([1], [700], 1),
    # 31 decode rows and the first token of a chunk in one query block.
    "rows_and_chunk_head": ([1] * 31 + [40],
                            [3 + 17 * i for i in range(31)] + [75], 1),
    # The second sequence straddles two query blocks.
    "straddles_blocks": ([20, 30, 1], [20, 300, 9], 1),
    # Rows of one block of 4 under the mask over blocks, beside a chunk.
    "block_rows": ([4, 4, 8, 36], [40, 4, 260, 36], 4),
    # At the window's size (aligned: narrow), one over, inside a window,
    # and at its size across two windows (wide).
    "rung_borders": ([4, 5, 3, 4, 2, 4], [44, 5, 30, 4, 513, 90], 1),
    # Last KV blocks of 1 key, a full 512, and a lone full block.
    "last_block_1_and_512": ([1, 1, 6], [513, 1024, 512], 1),
}


RUNG_T, RUNG_R, RUNG_B, RUNG_NB = 96, 32, 66, 640  # one shape for every mix


def _rung_case(mix, d, kh=1, seed=11):
    q_lens, kv_lens, block_len = RUNG_MIXES[mix]
    rng = np.random.default_rng(seed)
    q, kv_cache, md = _random_case(
        rng, len(q_lens), q_lens, kv_lens, kh, 2 * kh, d, RUNG_PAGE, RUNG_NB
    )
    return q, kv_cache, md, sum(q_lens), block_len


def _run_rung_kernel(q, kv_cache, md, **kw):
    """The kernel on the case padded to one shape (tokens, rows, table
    width), so that the mixes share a compiled program."""
    from vllm_tpu.ops.rpa_kernel import ragged_paged_attention

    n, b = md.block_tables.shape
    pad_rows = lambda a, mode="constant": jnp.asarray(
        np.pad(np.asarray(a), (0, RUNG_R - n), mode=mode))
    return ragged_paged_attention(
        jnp.pad(q, ((0, RUNG_T - q.shape[0]), (0, 0), (0, 0))),
        kv_cache, jnp.asarray([0], jnp.int32), pad_rows(md.seq_lens),
        jnp.pad(md.block_tables, ((0, RUNG_R - n), (0, RUNG_B - b))),
        pad_rows(md.query_start_loc, "edge"), md.num_seqs,
        sm_scale=q.shape[-1] ** -0.5, interpret=True,
        num_kv_pages_per_block=RUNG_KV_PAGES, num_queries_per_block=RUNG_Q,
        **kw,
    )


def test_rung_mixes_hit_both_rungs():
    """The mixes above are not all wide (or all narrow) by accident."""
    from vllm_tpu.ops.rpa_kernel import ragged_tiling, visit_rung

    tiling = ragged_tiling(
        2, 2, 128, RUNG_PAGE, jnp.float32, jnp.float32, 64, 64,
        num_kv_pages_per_block=RUNG_KV_PAGES, num_queries_per_block=RUNG_Q,
    )
    assert (tiling.narrow_q, tiling.narrow_sub_pages,
            tiling.wide_sub_pages) == (4, 16, 32)

    def rungs(mix):
        q_lens = RUNG_MIXES[mix][0]
        ends = np.cumsum(q_lens)
        out = []
        for start, end in zip(ends - q_lens, ends):
            for blk in range(start // RUNG_Q, (end - 1) // RUNG_Q + 1):
                lo, hi = max(start - blk * RUNG_Q, 0), min(end - blk * RUNG_Q, RUNG_Q)
                out.append(bool(visit_rung(lo, hi, tiling.narrow_q)[0]))
        return out

    assert rungs("decode_row_alone") == [True]
    assert rungs("rows_and_chunk_head") == [True] * 32 + [False, False]
    assert rungs("straddles_blocks") == [False, False, False, True]
    assert rungs("block_rows") == [True, True, False, False, False]
    assert rungs("rung_borders") == [True, False, True, True, True, False]


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("mix", list(RUNG_MIXES))
def test_rungs_plain(mix, d):
    q, kv_cache, md, t, block_len = _rung_case(mix, d)
    got = _run_rung_kernel(q, kv_cache, md, block_len=block_len)
    want = ref_ragged_paged_attention(
        q, kv_cache, jnp.int32(0), md, d ** -0.5, block_len=block_len
    )
    np.testing.assert_allclose(
        np.asarray(got)[:t], np.asarray(want)[:t], rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("mix", [m for m in RUNG_MIXES if m != "block_rows"])
def test_rungs_sliding_window(mix, d):
    # A window the long rows' first KV block lies wholly below.
    q, kv_cache, md, t, _ = _rung_case(mix, d)
    got = _run_rung_kernel(q, kv_cache, md, sliding_window=100)
    want = ref_ragged_paged_attention(
        q, kv_cache, jnp.int32(0), md, d ** -0.5, sliding_window=100
    )
    np.testing.assert_allclose(
        np.asarray(got)[:t], np.asarray(want)[:t], rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("mix", list(RUNG_MIXES))
def test_rungs_lse(mix, d):
    q, kv_cache, md, t, block_len = _rung_case(mix, d)
    got, lse = _run_rung_kernel(
        q, kv_cache, md, block_len=block_len, return_lse=True
    )
    want, want_lse = ref_ragged_paged_attention(
        q, kv_cache, jnp.int32(0), md, d ** -0.5, block_len=block_len,
        return_lse=True,
    )
    np.testing.assert_allclose(
        np.asarray(got)[:t], np.asarray(want)[:t], rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(lse)[:t], np.asarray(want_lse)[:t], rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("mix", [m for m in RUNG_MIXES if m != "block_rows"])
def test_rungs_striped_context(mix, d):
    """ctx_stride 2: each rank's partial against the reference's, where
    the rank holds any of the row's context."""
    import dataclasses

    q, kv_cache, md, t, _ = _rung_case(mix, d)
    bt = np.asarray(md.block_tables)
    b = bt.shape[1]
    for rank in range(2):
        cols = np.arange(-(-b // 2)) * 2 + rank
        lbt = np.where((cols < b)[None, :], bt[:, np.clip(cols, 0, b - 1)], 0)
        md_r = dataclasses.replace(md, block_tables=jnp.asarray(lbt))
        got, lse = _run_rung_kernel(
            q, kv_cache, md_r, return_lse=True, ctx_stride=2, ctx_phase=rank
        )
        want, want_lse = ref_ragged_paged_attention(
            q, kv_cache, jnp.int32(0), md_r, d ** -0.5, return_lse=True,
            ctx_stride=2, ctx_phase=rank,
        )
        live = np.asarray(want_lse)[:t] > -1e30
        assert live.any()
        np.testing.assert_allclose(
            np.asarray(lse)[:t][live], np.asarray(want_lse)[:t][live],
            rtol=2e-4, atol=2e-4,
        )
        rows = live.all(axis=1)
        np.testing.assert_allclose(
            np.asarray(got)[:t][rows], np.asarray(want)[:t][rows],
            rtol=2e-4, atol=2e-4,
        )


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("mix", list(RUNG_MIXES))
def test_rungs_fp8_scales(mix, d):
    q, kv_cache, md, t, block_len = _rung_case(mix, d, kh=2)
    kv8 = kv_cache.astype(jnp.float8_e4m3fn)
    scales = dict(k_scale=0.5, v_scale=2.0)
    got = _run_rung_kernel(q, kv8, md, block_len=block_len, **scales)
    want = ref_ragged_paged_attention(
        q, kv8, jnp.int32(0), md, d ** -0.5, block_len=block_len, **scales
    )
    np.testing.assert_allclose(
        np.asarray(got)[:t], np.asarray(want)[:t], rtol=2e-4, atol=2e-4
    )


def _walk_pairs(q_lens, kv_lens, tiling, page_size, block_len):
    """(live, computed) pairs by walking the kernel's (query block,
    sequence, KV block) visits one by one with its own three functions."""
    from vllm_tpu.ops.rpa_kernel import (
        visit_key_extent, visit_rung, visit_sub_tiles,
    )

    live = computed = 0
    blk_keys = tiling.kv_pages_per_blk * page_size
    q_start = 0
    for q_len, kv_len in zip(q_lens, kv_lens):
        for j in range(q_len):
            pos = kv_len - q_len + j
            live += (pos // block_len + 1) * block_len
        for q_blk in range(q_start // tiling.q_per_blk,
                           (q_start + q_len - 1) // tiling.q_per_blk + 1
                           if q_len else 0):
            q_len_start = q_blk * tiling.q_per_blk
            tok_lo = max(q_start - q_len_start, 0)
            tok_hi = min(q_start + q_len - q_len_start, tiling.q_per_blk)
            narrow, _ = visit_rung(tok_lo, tok_hi, tiling.narrow_q)
            toks = tiling.narrow_q if narrow else tiling.q_per_blk
            sub = page_size * (tiling.narrow_sub_pages if narrow
                               else tiling.wide_sub_pages)
            last_pos = kv_len - q_len + q_len_start - q_start + tok_hi - 1
            key_hi = int(visit_key_extent(last_pos, kv_len, 1, block_len))
            kv_blk = 0
            while kv_blk * blk_keys < max(kv_len, 1):  # the kernel's loop
                computed += toks * sub * int(visit_sub_tiles(
                    key_hi, kv_blk * blk_keys, sub, blk_keys // sub))
                kv_blk += 1
        q_start += q_len
    return live, computed


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("tiling_kw,block_len", [
    # mistral7b.chat and sdar30b.chat on the chip, the CPU tests' blocks.
    (dict(heads=(32, 16), dtype="bfloat16", tokens=512, pages=512), 1),
    (dict(heads=(32, 8), dtype="bfloat16", tokens=512, pages=128), 4),
    (dict(heads=(2, 2), dtype="float32", tokens=64, pages=64,
          num_kv_pages_per_block=RUNG_KV_PAGES, num_queries_per_block=RUNG_Q), 1),
], ids=["mistral", "sdar_block_len_4", "interpret_blocks"])
def test_host_count_mirrors_the_kernels_rung_and_key_extent(
        seed, tiling_kw, block_len):
    """``count_ragged_pairs`` (what the runner adds to
    ``vllm:attn_ragged_pairs_total`` a step) against the walk of the
    kernel's visits by the functions the kernel itself calls."""
    from vllm_tpu.ops.rpa_kernel import count_ragged_pairs, ragged_tiling

    kw = dict(tiling_kw)
    (h, comb), dtype = kw.pop("heads"), kw.pop("dtype")
    tiling = ragged_tiling(
        h, comb, 128, 16, dtype, dtype, kw.pop("tokens"), kw.pop("pages"),
        block_len=block_len, **kw,
    )
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 24))
    q_lens = [block_len * int(x) for x in rng.choice(
        [1, 1, 1, 2, 3, 5, 40, 130], n)]
    kv_lens = [q + block_len * int(rng.integers(0, 600)) for q in q_lens]
    if seed == 0:  # a padded row: no query tokens, no visit
        q_lens.append(0)
        kv_lens.append(0)
    got = count_ragged_pairs(q_lens, kv_lens, tiling, page_size=16,
                             block_len=block_len)
    assert got == _walk_pairs(q_lens, kv_lens, tiling, 16, block_len)
    assert 0 < got[0] <= got[1]
