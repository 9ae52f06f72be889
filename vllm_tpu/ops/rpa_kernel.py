"""In-repo Pallas TPU kernel: ragged paged attention over the WHOLE cache.

Replaces ``csrc/attention/paged_attention_v1/v2.cu`` + the varlen flash
call of the reference's CUDA backend (``vllm/v1/attention/backends/
flash_attn.py:597``), and supersedes the thin wrapper around the
JAX-bundled kernel this repo shipped in round 1. Derived from the
Apache-2.0 ``jax.experimental.pallas.ops.tpu.ragged_paged_attention``
kernel (JAX Authors, 2025), with framework-specific extensions:

- **Layer-indexed HBM access**: ``kv_pages`` is the framework's full
  ``[L, NB, BS, 2*KH, D]`` cache and the layer index arrives as a scalar
  prefetch; pages are DMA'd from ``ref.at[layer, page]``. This lets the
  model carry ONE donated cache buffer through ``lax.scan`` (true
  in-place paged KV) instead of scanning per-layer slices, which
  double-buffers the cache (xs/ys) and materializes a full per-layer
  copy as the kernel operand every layer.
- **LSE output** (``return_lse=True``): per-(token, q-head) logsumexp of
  the attention scores — the ``merge_attn_states`` contract
  (``csrc/attention/merge_attn_states.cu``) context parallelism needs.
- **head_dim 64** supported (validated against the XLA reference in
  tests); round 1 silently fell back to a quadratic gather path.
- ``interpret=`` plumbs Pallas interpret mode for CPU-backend tests.
- fp8 KV: ``k_scale``/``v_scale`` dequantize pages on the fly.
- **A visit costs what is live in it.** The kernel walks (query block,
  sequence, KV block) visits. A sequence with a few query tokens in the
  block (a decode row of a mixed step, a row of one block under
  generation by diffusion) runs the flash step on ONE window of query
  tokens, every KV head's rows stacked into whole tiles, not on the whole
  query block a head; only the pages below the sequence's end are copied;
  and the keys are computed sub-tile by sub-tile up to the last one a
  live query sees. The rungs are static and in one program, chosen a
  visit from the scalars the kernel prefetches (``visit_rung``,
  ``visit_key_extent``, ``visit_sub_tiles``); ``count_ragged_pairs``
  counts a step's work with the same three on the host. Block sizes and
  scoped VMEM come from ``ragged_tiling``, not from JAX's tuned table.

Layout contract (``ops/attention.py``): K/V heads interleaved on axis 3
(``0::2`` = K, ``1::2`` = V) so one page's per-head K,V pair is
contiguous for the per-page DMA.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax._src import dtypes
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.dtype("float32")).max)

# Mosaic's scoped-VMEM budget for one kernel when ``vmem_limit_bytes`` is
# not given (v5e; the chip's compiler reports it as "limit 16.00M").
_DEFAULT_SCOPED_VMEM_BYTES = 16 << 20

# What a kernel may be given at most; the v5e's VMEM is 128 MiB.
_MAX_SCOPED_VMEM_BYTES = 64 << 20
# Block sizes where the caller names none, and the keys of one step of the
# narrow rung (``ragged_tiling`` says where they come from).
_ROWS_PER_HEAD_TILE = 512
_KEYS_PER_BLOCK = 512
_NARROW_SUB_KEYS = 256
# KV heads a step of the wide rung's loop over heads (of the unrolled 8,
# 4 keep three quarters of the overlap at a program the size of 1's).
_WIDE_HEADS_PER_STEP = 4


def _dtype_packing(dtype) -> int:
    return 32 // dtypes.itemsize_bits(dtype)


def strided_load_kv(ref, start, step, live=None):
    """Split interleaved K/V rows; handles sub-32-bit packed dtypes.

    ``ref`` is a flat ``[N_rows, lanes]`` VMEM view whose rows interleave
    K/V heads with period ``step``; returns the K rows starting at
    ``start`` (a multiple of the dtype's packing; may be traced) and the V
    rows starting at ``start + 1`` (lists, because a packed dtype yields
    several heads per 32-bit load). ``live`` (``[N_rows // step, 1]``
    bool) zeroes the rows it marks dead, on the 32-bit words as loaded:
    one select for K and V of every head in the word. Shared by the
    general ragged kernel and the decode-specialized kernel
    (``rpa_decode_kernel.py``)."""
    packing = _dtype_packing(ref.dtype)
    rows = ref.shape[0] // step
    if packing == 1:
        k = ref[pl.ds(start, rows, stride=step), :]
        v = ref[pl.ds(start + 1, rows, stride=step), :]
        if live is not None:
            k, v = jnp.where(live, k, 0), jnp.where(live, v, 0)
        return [k], [v]
    assert packing in (2, 4, 8)
    assert step % packing == 0
    k_list, v_list = [], []
    b_ref = ref.bitcast(jnp.uint32)
    b = b_ref[pl.ds(start // packing, rows, stride=step // packing), :]
    if live is not None:
        b = jnp.where(live, b, jnp.uint32(0))
    if ref.dtype == jnp.bfloat16:
        bk = b << 16
        bv = b & jnp.uint32(0xFFFF0000)
        k_list.append(pltpu.bitcast(bk, jnp.float32).astype(jnp.bfloat16))
        v_list.append(pltpu.bitcast(bv, jnp.float32).astype(jnp.bfloat16))
    else:
        bitwidth = 32 // packing
        dst = jnp.dtype(f"uint{bitwidth}")
        for i in range(0, packing, 2):
            bk = b >> (i * bitwidth)
            k_list.append(pltpu.bitcast(bk.astype(dst), ref.dtype))
            bv = b >> ((i + 1) * bitwidth)
            v_list.append(pltpu.bitcast(bv.astype(dst), ref.dtype))
    return k_list, v_list


def fold_on_2nd_minor(vec):
    """Fold leading axes into rows; casts to f32 when the second-minor
    axis is not divisible by the dtype packing (Mosaic reshape rule)."""
    assert vec.dtype in (jnp.bfloat16, jnp.float32)
    assert len(vec.shape) >= 2
    packing = _dtype_packing(vec.dtype)
    if vec.shape[-2] % packing != 0:
        vec = vec.astype(jnp.float32)
    return vec.reshape(-1, vec.shape[-1])


class RaggedTiling(NamedTuple):
    """The kernel's tile sizes, all static: the fetched KV block and the
    query block, and the rungs a visit is computed on. The narrow rung
    takes a sequence whose query tokens in the block lie in ONE aligned
    window of ``narrow_q`` tokens (a decode row, a row of one block under
    generation by diffusion over blocks); ``narrow_q`` 0 = no such rung.
    Keys are computed ``*_sub_pages`` pages at a time."""

    kv_pages_per_blk: int
    q_per_blk: int
    narrow_q: int
    narrow_sub_pages: int
    wide_sub_pages: int
    vmem_limit_bytes: int | None


# The host's count of a step's work (``count_ragged_pairs``) calls the
# three functions below with numpy arrays, the kernel with the scalars it
# prefetched: one choice of rung and of key extent, written once.


def visit_rung(tok_lo, tok_hi, narrow_q: int):
    """(narrow?, window) for a sequence whose query tokens in the block
    are ``[tok_lo, tok_hi)``."""
    window = tok_lo // max(narrow_q, 1)
    one_window = window == (tok_hi - 1) // max(narrow_q, 1)
    return (narrow_q > 0) & one_window, window


def visit_key_extent(last_pos, local_tokens, ctx_stride, block_len: int,
                     xp=jnp):
    """Local context tokens a visit computes up to: those the sequence
    holds, and under the whole-context view no further than the last live
    query of the visit sees (``last_pos``, its position). A striped
    context keeps the whole loop, as it keeps it for the window."""
    if block_len > 1:
        last_pos = (last_pos // block_len + 1) * block_len - 1
    return xp.where(
        ctx_stride == 1, xp.minimum(local_tokens, last_pos + 1), local_tokens
    )


def visit_sub_tiles(key_hi, kv_blk_start, sub_keys: int, max_sub_tiles: int,
                    xp=jnp):
    """Sub-tiles of ``sub_keys`` keys computed in the block that starts at
    ``kv_blk_start``; never none, so that a visit always leaves its rows
    of l/m/acc initialised."""
    return xp.clip(
        (key_hi - kv_blk_start + sub_keys - 1) // sub_keys, 1, max_sub_tiles
    )


def count_ragged_pairs(q_lens, kv_lens, tiling: RaggedTiling, *,
                       page_size: int, block_len: int = 1):
    """(live, computed) (query token, key) pairs a q head of one kernel
    call over these rows: the pairs the mask lets through, and the pairs
    the kernel's tiles compute for them, by its own choice of rung and of
    key extent (whole-context view, no sliding window). Host side, numpy:
    the runner counts every step with it."""
    q_lens = np.asarray(q_lens, np.int64)
    kv_lens = np.asarray(kv_lens, np.int64)
    ctx = kv_lens - q_lens
    if block_len > 1:
        blocks = q_lens // block_len
        seen = block_len * block_len * blocks * (blocks + 1) // 2
    else:
        seen = q_lens * (q_lens + 1) // 2
    live = int(np.sum(q_lens * ctx + seen))

    q_end = np.cumsum(q_lens)
    q_start = q_end - q_lens
    first_blk = q_start // tiling.q_per_blk
    visits = np.where(
        q_lens > 0, (q_end - 1) // tiling.q_per_blk - first_blk + 1, 0
    )
    seq = np.repeat(np.arange(len(q_lens)), visits)
    visits_before = np.cumsum(visits) - visits
    q_len_start = (
        first_blk[seq] + np.arange(len(seq)) - visits_before[seq]
    ) * tiling.q_per_blk
    tok_lo = np.maximum(q_start[seq] - q_len_start, 0)
    tok_hi = np.minimum(q_end[seq] - q_len_start, tiling.q_per_blk)
    narrow, _ = visit_rung(tok_lo, tok_hi, tiling.narrow_q)
    blk_pos = ctx[seq] + q_len_start - q_start[seq]
    key_hi = visit_key_extent(
        blk_pos + tok_hi - 1, kv_lens[seq], 1, block_len, xp=np
    )
    toks = np.where(narrow, tiling.narrow_q, tiling.q_per_blk)
    sub_keys = page_size * np.where(
        narrow, tiling.narrow_sub_pages, tiling.wide_sub_pages
    )
    blk_keys = page_size * tiling.kv_pages_per_blk
    num_blks = (np.maximum(kv_lens[seq], 1) + blk_keys - 1) // blk_keys
    # Blocks wholly under the extent, the one it ends in, and the blocks
    # past it, which still run their one sub-tile.
    full = np.minimum(key_hi // blk_keys, num_blks)
    rest = num_blks - full
    last = np.where(
        rest > 0,
        visit_sub_tiles(key_hi, full * blk_keys, sub_keys,
                        blk_keys // sub_keys, xp=np) * sub_keys,
        0,
    )
    keys = full * blk_keys + last + np.maximum(rest - 1, 0) * sub_keys
    return live, int(np.sum(toks * keys))


def _rpa_kernel(
    # Scalar prefetch
    kv_lens_ref,  # [max_num_seqs]
    page_indices_ref,  # [max_num_seqs, pages_per_seq]
    cu_q_lens_ref,  # [max_num_seqs + 1]
    seq_buf_idx_ref,  # [2] mutable (seq_idx, buf_idx) carried across grid
    num_seqs_ref,  # [1]
    layer_ref,  # [1]
    window_ref,  # [1] i32 sliding window; 0 = full attention (dynamic so a
    #            layer scan can alternate windowed/full layers, e.g. Gemma)
    ctx_ref,  # [2] i32 (stride, phase): striped context-parallel view —
    #         local page j holds GLOBAL context page j*stride + phase
    #         (stride 1, phase 0 = the whole context; see cp_attention.py)
    # Inputs
    q_ref,  # [num_q_per_blk, num_q_heads_per_blk, head_dim]
    kv_pages_hbm_ref,  # [L, NB, page_size, num_combined_kv_heads, head_dim]
    # Outputs
    o_ref,  # [num_q_per_blk, num_q_heads_per_blk, head_dim]
    *rest,
    sm_scale: float,
    soft_cap: float | None,
    mask_value: float,
    k_scale: float | None,
    v_scale: float | None,
    return_lse: bool,
    block_len: int,
    tiling: RaggedTiling,
    head_major: bool = False,
    has_sinks: bool = False,
):
    """One (heads block, query block) program: walks the (sequence, KV
    block) VISITS of its query block. A visit's work follows what is live
    in it (``visit_rung``, ``visit_sub_tiles``): a sequence whose tokens
    in this block fit one aligned window of ``tiling.narrow_q`` tokens
    runs the flash step on that window's rows alone, and the keys of the
    fetched block are computed sub-tile by sub-tile up to the last one a
    live query can see."""
    sink_ref = None
    if has_sinks:
        # [KH, rows of a window, 128] f32, an input after the cache: the
        # logit of each row's q head, the same in every window.
        sink_ref, o_ref, *rest = (o_ref, *rest)
    if return_lse:
        lse_ref, kv_bufs, sems, l_ref, m_ref, acc_ref, qf_ref = rest
    else:
        lse_ref = None
        kv_bufs, sems, l_ref, m_ref, acc_ref, qf_ref = rest

    num_q_per_blk, num_q_heads_per_blk, head_dim = q_ref.shape
    v_dim = o_ref.shape[-1]  # head_dim but in the split layout
    pages_per_seq = page_indices_ref.shape[-1]
    num_seqs = num_seqs_ref[0]
    layer = layer_ref[0]
    if head_major:
        # The split layout's page, [KH, page_size, Dk_pad + Dv]: a row is
        # a key and its value as a packed row is, the head a major index.
        _, num_kv_pages_per_blk, kv_head_rows_per_blk, page_size, kv_lanes = (
            kv_bufs.shape
        )
    else:
        _, num_kv_pages_per_blk, page_size, kv_head_rows_per_blk, kv_lanes = (
            kv_bufs.shape
        )
    # [.., KH, 2D] layout (head_dim 64), or the split one: K || V a row.
    packed = head_major or kv_lanes == 2 * head_dim
    num_combined_kv_heads_per_blk = (
        kv_head_rows_per_blk if not packed else 2 * kv_head_rows_per_blk
    )
    num_kv_heads_per_blk = num_combined_kv_heads_per_blk // 2
    num_kv_per_blk = num_kv_pages_per_blk * page_size
    num_q_heads_per_kv_head = num_q_heads_per_blk // num_kv_heads_per_blk
    heads_blk_idx, q_blk_idx = pl.program_id(0), pl.program_id(1)
    num_heads_blks = pl.num_programs(0)
    init_seq_idx = seq_buf_idx_ref[0]
    init_buf_idx = seq_buf_idx_ref[1]
    q_len_start = q_blk_idx * num_q_per_blk
    q_len_end = q_len_start + num_q_per_blk
    ctx_stride = ctx_ref[0]
    ctx_phase = ctx_ref[1]
    window = window_ref[0]

    def local_ctx(seq_idx):
        """(local page count, local context token count) of this rank's
        stripe of the seq's context. Local page j holds global page
        ``j*ctx_stride + ctx_phase``; only the seq's LAST global page is
        partial. stride 1/phase 0 degenerates to (all pages, kv_len)."""
        kv_len = kv_lens_ref[seq_idx]
        n_gp = pl.cdiv(kv_len, page_size)
        n_lp = jnp.where(
            n_gp > ctx_phase,
            lax.div(n_gp - ctx_phase + ctx_stride - 1, ctx_stride),
            0,
        )
        g_last = (n_lp - 1) * ctx_stride + ctx_phase
        last = jnp.minimum(page_size, kv_len - g_last * page_size)
        return n_lp, jnp.where(n_lp > 0, (n_lp - 1) * page_size + last, 0)

    def seq_start_blk(seq_idx):
        """First KV block the window can reach for this seq's queries.

        A function of seq_idx ONLY (not the q block) so the prefetch chain
        and the compute loop always agree on the DMA sequence. The seq's
        lowest query position is kv_len - q_len; its window floor is that
        minus (window - 1). Striped context (ctx_stride > 1) skips the
        window fast-path: the floor is in global tokens and local pages
        interleave, so start at 0 (the mask stays correct)."""
        q_len = cu_q_lens_ref[seq_idx + 1] - cu_q_lens_ref[seq_idx]
        first_tok = jnp.maximum(
            kv_lens_ref[seq_idx] - q_len - (window - 1), 0
        )
        return jnp.where(
            jnp.logical_and(window > 0, ctx_stride == 1),
            first_tok // num_kv_per_blk,
            0,
        )

    def page_copies(heads_blk_idx, seq_idx, kv_blk_idx, buf_idx, *, wait):
        """Start, or wait for, the copies HBM -> VMEM of one KV block's
        pages, layer-indexed: the pages below the sequence's end and no
        other, so a block's tail past the sequence costs no traffic. The
        count is a function of (seq_idx, kv_blk_idx) alone: the start in
        the prefetch chain and the wait in the visit agree page for page."""
        start_page = kv_blk_idx * num_kv_pages_per_blk
        end_page = jnp.minimum(pages_per_seq, local_ctx(seq_idx)[0])
        num_pages = jnp.clip(end_page - start_page, 0, num_kv_pages_per_blk)
        if num_heads_blks == 1:
            # No heads sub-slice: a lane-dim slice on an HBM memref whose
            # head_dim is below the 128-lane tile (e.g. 64) is rejected by
            # Mosaic, and with one heads block it would be a no-op anyway.
            src = kv_pages_hbm_ref
        else:
            heads_start = heads_blk_idx * num_combined_kv_heads_per_blk
            src = kv_pages_hbm_ref.at[
                :, :, :, pl.ds(heads_start, num_combined_kv_heads_per_blk), :
            ]

        def one_page(i, carry):
            copy = pltpu.make_async_copy(
                src.at[layer, page_indices_ref[seq_idx, start_page + i]],
                kv_bufs.at[buf_idx, i],
                sems.at[buf_idx],
            )
            if wait:
                copy.wait()
            else:
                copy.start()
            return carry

        lax.fori_loop(0, num_pages, one_page, 0)

    @pl.when(heads_blk_idx + q_blk_idx == 0)
    def prefetch_first_kv_blk():
        page_copies(
            heads_blk_idx, init_seq_idx, seq_start_blk(init_seq_idx),
            init_buf_idx, wait=False,
        )

    # q, l, m and acc live in VMEM as [windows, KV heads, rows of a window,
    # lanes], a KV head's rows token-major: the narrow rung's window over
    # all heads is one slab, a head's rows over all windows a strided one.
    num_windows, _, win_rows, _ = qf_ref.shape
    win_toks = win_rows // num_q_heads_per_kv_head

    @pl.when(q_len_start < cu_q_lens_ref[num_seqs])
    def fold_q_heads():
        # Once a program and not once a visit.
        for kv_head_idx in range(num_kv_heads_per_blk):
            q_head_idx = kv_head_idx * num_q_heads_per_kv_head
            qf_ref[:, kv_head_idx] = (
                fold_on_2nd_minor(
                    q_ref[:, q_head_idx : q_head_idx
                          + num_q_heads_per_kv_head, :]
                )
                .astype(qf_ref.dtype)
                .reshape(num_windows, win_rows, head_dim)
            )

    def is_cur_q_blk_needed(q_states):
        done, cur_seq_idx, _ = q_states
        should_run = jnp.logical_and(
            q_len_start < cu_q_lens_ref[num_seqs], cur_seq_idx < num_seqs
        )
        return jnp.logical_and(done == 0, should_run)

    def compute_with_cur_q_blk(q_states):
        done, cur_seq_idx, cur_buf_idx = q_states
        q_start = cu_q_lens_ref[cur_seq_idx]
        q_end = cu_q_lens_ref[cur_seq_idx + 1]
        q_len = q_end - q_start
        kv_len = kv_lens_ref[cur_seq_idx]
        # Loop bound in LOCAL context tokens; floor 1 so a rank holding
        # ZERO pages of a short seq still runs one fully-masked block —
        # the double-buffer prefetch chain stays uniform across ranks
        # (skipping a seq would desync buffer ownership) and the masked
        # pass initializes this seq's l/m/acc scratch rows.
        local_tokens = local_ctx(cur_seq_idx)[1]
        local_bound = jnp.maximum(local_tokens, 1)
        # This sequence's query tokens inside the block, [tok_lo, tok_hi).
        tok_lo = jnp.maximum(q_start - q_len_start, 0)
        tok_hi = jnp.minimum(q_end - q_len_start, num_q_per_blk)
        is_narrow, narrow_win = visit_rung(tok_lo, tok_hi, tiling.narrow_q)
        # Position of the first token of the block, were it this seq's.
        blk_pos = (kv_len - q_len) + q_len_start - q_start
        key_hi = visit_key_extent(
            blk_pos + tok_hi - 1, local_tokens, ctx_stride, block_len
        )
        # ``row_pos - window >= col`` masks the keys below the window; no
        # window = a floor no column reaches.
        window_floor = jnp.where(window > 0, window, jnp.int32(1 << 30))

        def get_next_prefetch_ids(heads_blk_idx, cur_seq_idx, kv_blk_idx,
                                  cur_buf_idx):
            next_kv_blk_idx = kv_blk_idx + 1
            is_last_kv_blk = next_kv_blk_idx * num_kv_per_blk >= local_bound
            is_seq_end_in_blk = q_end <= q_len_end
            next_seq_idx = lax.select(
                is_last_kv_blk,
                lax.select(is_seq_end_in_blk, cur_seq_idx + 1, cur_seq_idx),
                cur_seq_idx,
            )
            is_last_seq = next_seq_idx == num_seqs
            next_seq_idx = lax.select(is_last_seq, 0, next_seq_idx)
            next_kv_blk_idx = lax.select(
                is_last_kv_blk, seq_start_blk(next_seq_idx), next_kv_blk_idx
            )
            next_heads_blk_idx = lax.select(
                is_last_seq, heads_blk_idx + 1, heads_blk_idx
            )
            next_buf_idx = lax.select(cur_buf_idx == 0, 1, 0)
            return next_heads_blk_idx, next_seq_idx, next_kv_blk_idx, next_buf_idx

        def flash_attention(qs, ks, vs, l_win, m_win, acc_win, *, masked,
                            init, live, sink=None):
            """One online-softmax step over one sub-tile of keys for the
            KV heads in ``qs``/``ks``/``vs`` (their rows stacked, so that
            the softmax runs on whole tiles whatever a head's rows), on
            the [.., rows, lanes] slabs of the l/m/acc scratch that hold
            those rows; only the live rows are stored."""
            head_rows = qs[0].shape[0]
            rows = head_rows * len(qs)

            def load_with_init(ref, init_val):
                val = ref[...].reshape(rows, ref.shape[-1])
                return jnp.where(init, jnp.full_like(val, init_val), val)

            def store_live(ref, val):
                pltpu.store(
                    ref, val.reshape(ref.shape),
                    mask=jnp.broadcast_to(
                        live, (rows, ref.shape[-1])
                    ).reshape(ref.shape),
                )

            qk = jnp.concatenate([
                jnp.einsum("nd,md->nm", q, k,
                           preferred_element_type=jnp.float32)
                for q, k in zip(qs, ks)
            ], axis=0) * sm_scale
            if soft_cap is not None:
                qk = soft_cap * jnp.tanh(qk / soft_cap)
            qk = jnp.where(masked, mask_value, qk)
            m_curr = jnp.max(qk, axis=1, keepdims=True)
            s_curr = jnp.exp(qk - m_curr)
            qkv = jnp.concatenate([
                jnp.dot(s_curr[i * head_rows : (i + 1) * head_rows], v,
                        preferred_element_type=jnp.float32)
                for i, v in enumerate(vs)
            ], axis=0)
            lm_shape = (rows, 128)
            m_curr = jnp.broadcast_to(m_curr, lm_shape)
            l_curr = jnp.broadcast_to(
                s_curr.sum(axis=1, keepdims=True), lm_shape
            )
            if sink is None:
                m_prev = load_with_init(m_win, -jnp.inf)
                l_prev = load_with_init(l_win, 0.0)
            else:
                # The sink is the row's state before its first key: a
                # maximum of its logit and a denominator of exp(0).
                m_prev = jnp.where(
                    init, sink, m_win[...].reshape(rows, m_win.shape[-1]))
                l_prev = load_with_init(l_win, 1.0)
            m_next = jnp.maximum(m_prev, m_curr)
            store_live(m_win, m_next)
            alpha = jnp.exp(m_prev - m_next)
            beta = jnp.exp(m_curr - m_next)
            l_alpha = alpha * l_prev
            l_next = l_alpha + beta * l_curr
            l_next_safe = jnp.where(l_next == 0.0, 1.0, l_next)
            store_live(l_win, l_next_safe)

            def broadcast_to_shape(arr, shape):
                """Match the 128-lane l/m values to head_dim lanes. Every
                lane holds the same value, so head_dim < 128 (e.g. 64) takes
                a single lane and relies on implicit broadcasting — this is
                what unlocks head_dim 64 vs the upstream kernel."""
                if arr.shape == shape:
                    return arr
                if shape[1] < arr.shape[1]:
                    return arr[:, :1]
                # no-op concatenation (shape[1] is a multiple).
                return jnp.concatenate(
                    [arr for _ in range(shape[1] // arr.shape[1])], axis=1
                )

            o_curr = load_with_init(acc_win, 0.0)
            l_alpha = broadcast_to_shape(l_alpha, qkv.shape)
            beta = broadcast_to_shape(beta, qkv.shape)
            l_next_safe_b = broadcast_to_shape(l_next_safe, qkv.shape)
            out = (l_alpha * o_curr + beta * qkv) / l_next_safe_b
            store_live(acc_win, out)

        def visit(kv_buf, kv_blk_idx, *, win, sub_pages):
            """The flash steps of one (sequence, KV block) visit over the
            live sub-tiles of ``sub_pages`` pages of the fetched block: on
            the rows of window ``win`` (one window of query tokens, every KV
            head's rows in one step), or with ``win`` None on all the
            block's query tokens, a KV head a step."""
            narrow = win is not None
            group = num_q_heads_per_kv_head
            if narrow:
                # Rows: (KV head, token of the window, q head of the group).
                rows = num_kv_heads_per_blk * win_rows
                tok = win * win_toks + (
                    lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % win_rows
                ) // group
            else:
                rows = num_windows * win_rows
                tok = lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group
            sub_keys = sub_pages * page_size
            max_sub_tiles = num_kv_pages_per_blk // sub_pages
            kv_len_start = kv_blk_idx * num_kv_per_blk
            num_sub_tiles = visit_sub_tiles(
                key_hi, kv_len_start, sub_keys, max_sub_tiles
            )
            live = jnp.logical_and(tok >= tok_lo, tok < tok_hi)
            row_pos = blk_pos + tok
            if block_len > 1:
                # Causal over blocks: a query sees up to the last position
                # of its own block (a row's queries cover whole blocks, so
                # that position is inside kv_len).
                row_pos = (row_pos // block_len + 1) * block_len - 1
            row_floor = row_pos - window_floor
            if not packed:
                kv_ref = kv_buf.reshape(
                    max_sub_tiles,
                    sub_keys * num_combined_kv_heads_per_blk,
                    head_dim,
                )
                kv_packing = _dtype_packing(kv_ref.dtype)
                kv_load_step = max(1, kv_packing // 2)
            else:
                # Packed [.., KH, 2D] layout (head_dim 64): K and V are the
                # lane halves of one 128-lane row; split with aligned lane
                # slices (the interleaved layout's bitcast strided load
                # requires 128-lane base memrefs, which D=64 can't give).
                kv_ref = None
                kv_load_step = 1

            def sub_tile(sub_idx, carry):
                init = jnp.logical_and(
                    kv_blk_idx == cur_start_blk, sub_idx == 0
                )
                # Position arithmetic is in GLOBAL context coordinates:
                # local flat slot c maps to page (c // ps) * stride +
                # phase, offset c % ps. One row of columns and one column
                # of rows; the comparison broadcasts them.
                sub_start = kv_len_start + sub_idx * sub_keys
                col_flat = sub_start + lax.broadcasted_iota(
                    jnp.int32, (1, sub_keys), 1
                )
                col = (
                    (col_flat // page_size) * ctx_stride + ctx_phase
                ) * page_size + col_flat % page_size
                masked = jnp.logical_or(col > row_pos, col <= row_floor)
                # KV rows beyond the (striped) context were not copied:
                # zero them so the contraction stays NaN-free.
                kv_live = (
                    sub_start
                    + lax.broadcasted_iota(jnp.int32, (sub_keys, 1), 0)
                ) < local_tokens
                kw = dict(masked=masked, init=init, live=live)

                def load_kv(kv_head_chunk_idx):
                    """K and V of ``kv_load_step`` KV heads from the head
                    ``kv_head_chunk_idx`` on, dequantized."""
                    if kv_ref is not None:
                        k_list, v_list = strided_load_kv(
                            kv_ref.at[sub_idx], kv_head_chunk_idx * 2,
                            num_combined_kv_heads_per_blk, live=kv_live,
                        )
                    else:
                        sub = pl.ds(sub_idx * sub_pages, sub_pages)
                        kv_rows = (
                            kv_buf[sub, kv_head_chunk_idx, :, :] if head_major
                            else kv_buf[sub, :, kv_head_chunk_idx, :]
                        ).reshape(sub_keys, kv_lanes)
                        kv_rows = jnp.where(
                            kv_live, kv_rows.astype(jnp.float32), 0
                        ).astype(kv_rows.dtype)
                        k_list = [kv_rows[:, :head_dim]]
                        v_list = [kv_rows[:, head_dim:]]
                    if k_scale is not None:
                        k_list = [
                            (k.astype(jnp.float32) * k_scale).astype(
                                q_ref.dtype) for k in k_list
                        ]
                    if v_scale is not None:
                        v_list = [
                            (v.astype(jnp.float32) * v_scale).astype(
                                q_ref.dtype) for v in v_list
                        ]
                    return k_list, v_list

                def wide_heads(kv_head_chunk_idx, carry):
                    for i, (k, v) in enumerate(zip(*load_kv(
                            kv_head_chunk_idx))):
                        h = kv_head_chunk_idx + i
                        sink = None if sink_ref is None else jnp.broadcast_to(
                            sink_ref[h][None], (num_windows, win_rows, 128)
                        ).reshape(rows, 128)
                        flash_attention(
                            [qf_ref[:, h].reshape(rows, head_dim)], [k], [v],
                            l_ref.at[:, h], m_ref.at[:, h], acc_ref.at[:, h],
                            sink=sink, **kw,
                        )
                    return carry

                head_chunks = range(0, num_kv_heads_per_blk, kv_load_step)
                if narrow:
                    ks, vs = [], []
                    for kv_head_chunk_idx in head_chunks:
                        k_list, v_list = load_kv(kv_head_chunk_idx)
                        ks += k_list
                        vs += v_list
                    flash_attention(
                        [qf_ref[win, h] for h in range(len(ks))], ks, vs,
                        l_ref.at[win], m_ref.at[win],
                        acc_ref.at[win],
                        sink=None if sink_ref is None
                        else sink_ref[...].reshape(rows, 128), **kw,
                    )
                elif packed and not head_major:
                    # The packed layout's head is a sublane index.
                    for kv_head_chunk_idx in head_chunks:
                        wide_heads(kv_head_chunk_idx, 0)
                else:
                    # A loop over the KV heads, a few a step: a wide tile
                    # is work enough for the scheduler, and the program a
                    # start compiles is a third of the unrolled one.
                    unroll = math.gcd(_WIDE_HEADS_PER_STEP, len(head_chunks))

                    def some_heads(c, carry):
                        for u in range(unroll):
                            wide_heads((c * unroll + u) * kv_load_step, carry)
                        return carry

                    lax.fori_loop(
                        0, len(head_chunks) // unroll, some_heads, 0
                    )
                return carry

            if max_sub_tiles == 1:
                sub_tile(0, 0)
            else:
                lax.fori_loop(0, num_sub_tiles, sub_tile, 0)

        def is_valid_kv_blk_in_cur_seq(kv_states):
            kv_blk_idx, _ = kv_states
            return kv_blk_idx * num_kv_per_blk < local_bound

        def compute_with_kv_blk_in_cur_seq(kv_states):
            kv_blk_idx, cur_buf_idx = kv_states
            next_ids = get_next_prefetch_ids(
                heads_blk_idx, cur_seq_idx, kv_blk_idx, cur_buf_idx
            )
            next_heads_blk_idx, next_seq_idx, next_kv_blk_idx, next_buf_idx = (
                next_ids
            )

            @pl.when(next_heads_blk_idx < num_heads_blks)
            def prefetch_next_kv_blk():
                page_copies(
                    next_heads_blk_idx, next_seq_idx, next_kv_blk_idx,
                    next_buf_idx, wait=False,
                )

            page_copies(
                heads_blk_idx, cur_seq_idx, kv_blk_idx, cur_buf_idx,
                wait=True,
            )
            kv_buf = kv_bufs.at[cur_buf_idx]  # [pages, page, rows, lanes]
            wide = dict(win=None, sub_pages=tiling.wide_sub_pages)
            if tiling.narrow_q == 0:
                visit(kv_buf, kv_blk_idx, **wide)
            else:
                @pl.when(is_narrow)
                def narrow_visit():
                    visit(kv_buf, kv_blk_idx, win=narrow_win,
                          sub_pages=tiling.narrow_sub_pages)

                @pl.when(jnp.logical_not(is_narrow))
                def wide_visit():
                    visit(kv_buf, kv_blk_idx, **wide)

            return kv_blk_idx + 1, next_buf_idx

        cur_start_blk = seq_start_blk(cur_seq_idx)
        _, next_buf_idx = lax.while_loop(
            is_valid_kv_blk_in_cur_seq,
            compute_with_kv_blk_in_cur_seq,
            (cur_start_blk, cur_buf_idx),
        )
        next_seq_idx = lax.select(q_end <= q_len_end, cur_seq_idx + 1,
                                  cur_seq_idx)
        done = lax.select(q_end < q_len_end, done, 1)
        return done, next_seq_idx, next_buf_idx

    _, seq_idx, buf_idx = lax.while_loop(
        is_cur_q_blk_needed,
        compute_with_cur_q_blk,
        (0, init_seq_idx, init_buf_idx),
    )
    seq_buf_idx_ref[0] = lax.select(seq_idx < num_seqs, seq_idx, 0)
    seq_buf_idx_ref[1] = buf_idx
    for kv_head_idx in range(num_kv_heads_per_blk):
        q_head_idx = kv_head_idx * num_q_heads_per_kv_head
        o_ref[:, q_head_idx : q_head_idx + num_q_heads_per_kv_head, :] = (
            acc_ref[:, kv_head_idx]
            .reshape(num_q_per_blk, num_q_heads_per_kv_head, v_dim)
            .astype(o_ref.dtype)
        )
    if return_lse:
        # lse = m + log(l): scratch blocks are [KH_blk, numq*ratio, 128]
        # (value broadcast over lanes); the host-side wrapper slices lane 0
        # and rearranges to [T, num_q_heads].
        for kv_head_idx in range(num_kv_heads_per_blk):
            lse_ref[kv_head_idx] = (
                m_ref[:, kv_head_idx] + jnp.log(l_ref[:, kv_head_idx])
            ).reshape(lse_ref.shape[1:])


def _validate(q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs,
              head_major=False):
    _, num_q_heads, head_dim = q.shape
    _, _, _, kv_rows, kv_lanes = kv_pages.shape
    if head_major:  # split [.., KH, BS, Dk_pad + Dv], q padded to Dk_pad
        num_kv_heads, head_dim_k = kv_pages.shape[2], head_dim
        if kv_lanes <= head_dim or head_dim % 128:
            raise ValueError(
                f"split layout: {kv_lanes=} must hold a key of {head_dim=} "
                "whole lane tiles and a value")
    elif kv_lanes == 2 * head_dim:  # packed [.., KH, 2D]
        num_kv_heads, head_dim_k = kv_rows, kv_lanes // 2
    else:
        assert kv_rows % 2 == 0
        num_kv_heads, head_dim_k = kv_rows // 2, kv_lanes
    max_num_seqs, pages_per_seq = page_indices.shape
    if num_seqs.shape != (1,):
        raise ValueError(f"{num_seqs.shape=} must be (1,)")
    if head_dim_k != head_dim:
        raise ValueError(f"Q head_dim {head_dim} != K/V head_dim {head_dim_k}")
    if kv_lens.shape != (max_num_seqs,):
        raise ValueError(f"{kv_lens.shape=} != ({max_num_seqs},)")
    if cu_q_lens.shape != (max_num_seqs + 1,):
        raise ValueError(f"{cu_q_lens.shape=} != ({max_num_seqs + 1},)")
    for name, arr in (("kv_lens", kv_lens), ("page_indices", page_indices),
                      ("cu_q_lens", cu_q_lens)):
        if arr.dtype != jnp.int32:
            raise ValueError(f"{name} must be int32, got {arr.dtype}")
    if num_q_heads % num_kv_heads != 0:
        raise ValueError(f"{num_q_heads=} % {num_kv_heads=} != 0")


def _min_heads_per_blk(num_q_heads, num_combined_kv_heads, q_dtype, kv_dtype):
    q_packing = _dtype_packing(q_dtype)
    kv_packing = _dtype_packing(kv_dtype)

    def xla_tileable(x, packing):
        if x % packing != 0:
            return False
        x //= packing
        return x in (1, 2, 4, 8) or x % 8 == 0

    if not xla_tileable(num_combined_kv_heads, kv_packing):
        raise ValueError(
            f"{num_combined_kv_heads=} cannot be XLA fully tiled"
        )
    assert num_combined_kv_heads % 2 == 0
    ratio = num_q_heads // (num_combined_kv_heads // 2)
    max_kv_tiling = 8 * kv_packing
    min_combined = (
        max_kv_tiling
        if num_combined_kv_heads % max_kv_tiling == 0
        else num_combined_kv_heads
    )
    min_q_heads = min_combined // 2 * ratio
    if xla_tileable(min_q_heads, q_packing):
        return min_q_heads, min_combined
    return num_q_heads, num_combined_kv_heads


def _heads_per_blk(num_q_heads, num_combined_kv_heads, head_dim, q_dtype,
                   kv_dtype):
    """(q heads, combined KV heads) of one heads block."""
    if head_dim % 128 == 0:
        return _min_heads_per_blk(
            num_q_heads, num_combined_kv_heads, q_dtype, kv_dtype
        )
    # Packed layout (head_dim 64): one heads block, no HBM heads slicing (a
    # lane-dim or sub-tile memref slice is rejected by Mosaic).
    return num_q_heads, num_combined_kv_heads


def _fit_kv_pages_to_vmem(
    num_kv_pages_per_blk: int,
    vmem_budget_bytes: int,
    *,
    num_q_per_blk: int,
    num_q_heads_per_blk: int,
    num_combined_kv_heads_per_blk: int,
    head_dim: int,
    page_size: int,
    q_itemsize: int,
    kv_itemsize: int,
) -> tuple[int, int]:
    """Halve the KV block until the kernel's VMEM need fits the budget;
    returns (pages, the need in bytes).

    The need is estimated from the block shapes as ``9/8 * (1.5 * kv
    double buffer + 2 * (q, out, acc, folded q, l, m blocks) + the
    per-kv-head f32 score tiles) + 1 MiB``: the coefficients bound from
    above the smallest ``vmem_limit_bytes`` the v5e compiler accepted
    (MiB, bf16 q, D=128, page 16; pages x queries). 32q/16 combined heads:
    32x32: 12, 32x64: 16, 32x128: 26, 64x128: 40, 128x128: 56, 8x128: 14,
    32x256: 46, and with the LSE output 13, 18, 30, 44, 60, 18, 54; fp8 KV
    32x32: 9, 32x128: 24, 128x128: 46; 32q/8 with a mask over blocks of 4
    32x32: 9, 32x128: 25, 128x128: 55; a tp=4 shard (8q/4) 32x32: 3,
    32x128: 8, 128x128: 19. ``tests/test_tpu_aot_compile.py`` holds the
    result to the compiler.
    """
    num_kv_heads_per_blk = num_combined_kv_heads_per_blk // 2
    ratio = num_q_heads_per_blk // num_kv_heads_per_blk
    q_blk_elems = num_q_per_blk * num_q_heads_per_blk * head_dim
    lm_bytes = num_kv_heads_per_blk * num_q_per_blk * ratio * 128 * 4
    q_side = q_blk_elems * (3 * q_itemsize + 4) + 2 * lm_bytes

    def need(pages: int) -> int:
        kv_tokens = pages * page_size
        kv_double_buf = (
            2 * kv_tokens * num_combined_kv_heads_per_blk * head_dim
            * kv_itemsize
        )
        scores = num_kv_heads_per_blk * num_q_per_blk * ratio * kv_tokens * 4
        total = kv_double_buf * 3 // 2 + 2 * q_side + scores
        return total * 9 // 8 + (1 << 20)

    while num_kv_pages_per_blk > 1 and need(num_kv_pages_per_blk) > (
        vmem_budget_bytes
    ):
        num_kv_pages_per_blk //= 2
    return num_kv_pages_per_blk, need(num_kv_pages_per_blk)


def _narrow_tile_tokens(q_dtype, ratio: int) -> int:
    """The fewest query tokens whose ``ratio`` rows each fill whole sublane
    tiles of q."""
    tile_rows = 8 * _dtype_packing(q_dtype)
    return tile_rows // math.gcd(tile_rows, ratio)


def ragged_tiling(
    num_q_heads: int,
    num_combined_kv_heads: int,
    head_dim: int,
    page_size: int,
    q_dtype,
    kv_dtype,
    num_q_tokens: int,
    pages_per_seq: int,
    *,
    block_len: int = 1,
    num_kv_pages_per_block: int | None = None,
    num_queries_per_block: int | None = None,
    vmem_limit_bytes: int | None = None,
) -> RaggedTiling:
    """The tile sizes ``ragged_paged_attention`` runs these shapes with
    (a tp shard's head counts where the kernel runs a shard).

    The blocks are the caller's, or chosen from the shapes by what a probe
    of the kernel on the v5e and runs of both cells found (``PERF.md``, PR
    32): as many query tokens a block as give a KV head's wide tile 512
    rows (128 tokens at 4 q heads a KV head, 64 at 8; never under 32 nor
    over 128) -- a prefill chunk's keys are fetched and its tiles' fixed
    work paid once a query block, but past 512 rows the kernel gains
    nothing and the NEXT kernel of the step loses (``gmm`` 5% at 1024
    rows) -- KV blocks of 512 keys, and the scoped VMEM the two need asked
    of the compiler, up to 64 MiB of the chip's 128; past that the KV
    block halves. The rungs follow: the narrow
    window is the fewest tokens that hold a decode row (or a row of
    ``block_len`` tokens) and whose rows fill whole sublane tiles of q;
    its keys go 256 at a time, the wide rung's a whole block at a time (a
    smaller step repeats the softmax's fixed work, which costs the chunk
    more than the dead keys of its last block)."""
    q_dtype, kv_dtype = jnp.dtype(q_dtype), jnp.dtype(kv_dtype)
    ratio = num_q_heads // (num_combined_kv_heads // 2)
    num_q_per_blk = num_queries_per_block
    num_kv_pages_per_blk = num_kv_pages_per_block
    if num_q_per_blk is None or num_kv_pages_per_blk is None:
        num_q_heads_per_blk, num_combined_kv_heads_per_blk = _heads_per_blk(
            num_q_heads, num_combined_kv_heads, head_dim, q_dtype, kv_dtype
        )
        num_q_per_blk = min(
            max(32, min(128, _ROWS_PER_HEAD_TILE // ratio)), num_q_tokens
        )
        # Whole sublane tiles of q rows (a group of 6 q heads a KV head
        # gives 85 tokens, taken as 80), so that the narrow rung below
        # divides the block; the groups that divide 512 are as they were.
        num_q_per_blk -= num_q_per_blk % _narrow_tile_tokens(q_dtype, ratio)
        num_kv_pages_per_blk, need = _fit_kv_pages_to_vmem(
            min(max(1, _KEYS_PER_BLOCK // page_size), pages_per_seq),
            vmem_limit_bytes or _MAX_SCOPED_VMEM_BYTES,
            num_q_per_blk=num_q_per_blk,
            num_q_heads_per_blk=num_q_heads_per_blk,
            num_combined_kv_heads_per_blk=num_combined_kv_heads_per_blk,
            head_dim=head_dim,
            page_size=page_size,
            q_itemsize=q_dtype.itemsize,
            kv_itemsize=kv_dtype.itemsize,
        )
        if vmem_limit_bytes is None and need > _DEFAULT_SCOPED_VMEM_BYTES:
            vmem_limit_bytes = need
    tile_toks = _narrow_tile_tokens(q_dtype, ratio)
    narrow_q = -(-block_len // tile_toks) * tile_toks
    if narrow_q >= num_q_per_blk or num_q_per_blk % narrow_q:
        narrow_q = 0
    return RaggedTiling(
        kv_pages_per_blk=num_kv_pages_per_blk,
        q_per_blk=num_q_per_blk,
        narrow_q=narrow_q,
        narrow_sub_pages=math.gcd(
            num_kv_pages_per_blk, max(1, _NARROW_SUB_KEYS // page_size)
        ),
        wide_sub_pages=num_kv_pages_per_blk,
        vmem_limit_bytes=vmem_limit_bytes,
    )


@functools.partial(
    jax.jit,
    static_argnames=[
        "sm_scale", "mask_value", "num_kv_pages_per_block",
        "num_queries_per_block", "vmem_limit_bytes",
        "soft_cap", "k_scale", "v_scale", "return_lse", "interpret",
        "block_len", "name", "head_major",
    ],
)
def ragged_paged_attention(
    q: jax.Array,  # [max_num_batched_tokens, num_q_heads, head_dim]
    kv_pages: jax.Array,  # [L, total_pages, page_size, 2*KH, head_dim]
    layer: jax.Array,  # i32[1]
    kv_lens: jax.Array,  # i32[max_num_seqs]
    page_indices: jax.Array,  # i32[max_num_seqs, pages_per_seq]
    cu_q_lens: jax.Array,  # i32[max_num_seqs + 1]
    num_seqs: jax.Array,  # i32[1]
    *,
    sm_scale: float = 1.0,
    sliding_window=None,  # int | traced i32 scalar | None; 0/None = full
    soft_cap: float | None = None,
    mask_value: float | None = None,
    k_scale: float | None = None,
    v_scale: float | None = None,
    num_kv_pages_per_block: int | None = None,
    num_queries_per_block: int | None = None,
    vmem_limit_bytes: int | None = None,
    return_lse: bool = False,
    interpret: bool = False,
    ctx_stride=1,
    ctx_phase=0,
    block_len: int = 1,
    name: str = "rpa_kernel",
    sinks: jax.Array | None = None,  # f32[num_q_heads]
    head_major: bool = False,
):
    """Mixed prefill+decode flash attention over the paged KV cache.

    ``sinks``: a logit a q head that every row's running maximum starts at
    (its denominator at 1, its accumulator at 0) where they start at minus
    infinity and 0 without. ``head_major``: ``kv_pages`` is the split
    layout's view ``[L, NB, KH, page_size, Dk_pad + Dv]`` and ``q`` padded
    to ``Dk_pad`` lanes (``ops/attention.py::split_kv_cache_shape``); the
    output is ``[T, H, Dv]``.

    ``name`` is the ``pallas_call``'s, which a profiler trace prints
    (``decode_paged_attention`` says when it is not the default).

    ``block_len`` > 1 makes the mask causal over blocks of that many
    positions (``col // block_len <= row // block_len``); every row's
    query tokens must then cover whole blocks, and no window applies.

    Returns ``out [T, H, D]``, or ``(out, lse [T, H] f32)`` with
    ``return_lse=True``.

    ``ctx_stride``/``ctx_phase`` (ints or traced i32 scalars) give the
    kernel a striped context-parallel view: ``page_indices`` is a rank's
    LOCAL table whose column j holds global context page
    ``j*ctx_stride + ctx_phase``; ``kv_lens`` stays GLOBAL (query
    positions derive from it). The contract matches
    ``ref_ragged_paged_attention`` and ``cp_attention.cp_write_and_attend``.
    """
    _validate(q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs,
              head_major)
    assert sinks is None or not return_lse
    if mask_value is None:
        mask_value = DEFAULT_MASK_VALUE
    num_q_tokens, num_q_heads, head_dim = q.shape
    _, _, page_size, kv_rows, kv_lanes = kv_pages.shape
    v_dim = head_dim
    if head_major:
        kv_rows, page_size = kv_pages.shape[2:4]
        v_dim = kv_lanes - head_dim
    # [.., KH, 2D] layout (head_dim 64), or the split one: K || V a row.
    packed = head_major or kv_lanes == 2 * head_dim
    num_combined_kv_heads = 2 * kv_rows if packed else kv_rows
    num_kv_heads = num_combined_kv_heads // 2
    _, pages_per_seq = page_indices.shape
    if head_major:
        # One heads block: the estimate of VMEM below then counts a key
        # and a value of head_dim lanes each, a third over the rows held.
        num_q_heads_per_blk = num_q_heads
        num_combined_kv_heads_per_blk = num_combined_kv_heads
    else:
        num_q_heads_per_blk, num_combined_kv_heads_per_blk = _heads_per_blk(
            num_q_heads, num_combined_kv_heads, head_dim, q.dtype,
            kv_pages.dtype
        )
    tiling = ragged_tiling(
        num_q_heads, num_combined_kv_heads, head_dim, page_size, q.dtype,
        kv_pages.dtype, num_q_tokens, pages_per_seq, block_len=block_len,
        num_kv_pages_per_block=num_kv_pages_per_block,
        num_queries_per_block=num_queries_per_block,
        vmem_limit_bytes=vmem_limit_bytes,
    )
    num_q_per_blk = tiling.q_per_blk
    num_kv_pages_per_blk = tiling.kv_pages_per_blk
    num_q_heads_per_kv_head = num_q_heads // num_kv_heads
    num_q_blks = pl.cdiv(num_q_tokens, num_q_per_blk)
    num_kv_heads_per_blk = num_combined_kv_heads_per_blk // 2
    assert num_q_heads_per_blk % num_q_heads_per_kv_head == 0
    num_heads_blks = num_q_heads // num_q_heads_per_blk
    grid = (num_heads_blks, num_q_blks)

    def q_index_map(heads_blk_idx, q_blk_idx, *_):
        return (q_blk_idx, heads_blk_idx, 0)

    q_block_spec = pl.BlockSpec(
        (num_q_per_blk, num_q_heads_per_blk, head_dim), q_index_map
    )
    in_specs = [q_block_spec, pl.BlockSpec(memory_space=pl.ANY)]
    rows_per_blk = num_q_per_blk * num_q_heads_per_kv_head
    lm_shape = (num_kv_heads_per_blk, rows_per_blk, 128)
    out_specs = [q_block_spec]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if v_dim != head_dim:
        out_specs = [pl.BlockSpec(
            (num_q_per_blk, num_q_heads_per_blk, v_dim), q_index_map)]
        out_shape = [jax.ShapeDtypeStruct(
            (num_q_tokens, num_q_heads, v_dim), q.dtype)]
    if return_lse:
        out_specs.append(
            pl.BlockSpec(lm_shape, lambda h, qb, *_: (h, qb, 0))
        )
        out_shape.append(
            jax.ShapeDtypeStruct(
                (num_heads_blks * num_kv_heads_per_blk,
                 num_q_blks * rows_per_blk, 128),
                jnp.float32,
            )
        )
    kv_rows_per_blk = (
        num_combined_kv_heads_per_blk // 2
        if packed
        else num_combined_kv_heads_per_blk
    )
    # q, acc, l and m by window of the narrow rung (one window where
    # there is none), KV head and the window's rows, token-major.
    win_toks = tiling.narrow_q or num_q_per_blk
    win_rows = win_toks * num_q_heads_per_kv_head
    win_shape = (num_q_per_blk // win_toks, num_kv_heads_per_blk, win_rows)
    page_shape = (
        (kv_rows_per_blk, page_size) if head_major
        else (page_size, kv_rows_per_blk)
    )
    scratch_shapes = [
        pltpu.VMEM(
            (2, num_kv_pages_per_blk, *page_shape, kv_lanes), kv_pages.dtype
        ),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.VMEM((*win_shape, 128), jnp.float32),  # l
        pltpu.VMEM((*win_shape, 128), jnp.float32),  # m
        pltpu.VMEM((*win_shape, v_dim), jnp.float32),  # acc
        pltpu.VMEM((*win_shape, head_dim), q.dtype),  # q, folded
    ]
    operands = [q, kv_pages]
    if sinks is not None:
        # A row of a KV head's window is (token, q head of its group).
        operands.append(jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(
                num_kv_heads, 1, num_q_heads_per_kv_head, 1),
            (num_kv_heads, win_toks, num_q_heads_per_kv_head, 128),
        ).reshape(num_kv_heads, win_rows, 128))
        in_specs.append(pl.BlockSpec(
            (num_kv_heads_per_blk, win_rows, 128),
            lambda h, qb, *_: (h, 0, 0)))
    window = jnp.asarray(
        0 if sliding_window is None else sliding_window, jnp.int32
    ).reshape(1)
    ctx = jnp.stack([
        jnp.asarray(ctx_stride, jnp.int32),
        jnp.asarray(ctx_phase, jnp.int32),
    ])
    scalar_prefetches = (
        kv_lens,
        page_indices,
        cu_q_lens,
        jnp.array((0, 0), jnp.int32),  # seq_idx, buf_idx
        num_seqs,
        layer.astype(jnp.int32).reshape(1),
        window,
        ctx,
    )
    kernel = pl.pallas_call(
        functools.partial(
            _rpa_kernel,
            sm_scale=sm_scale,
            soft_cap=soft_cap,
            mask_value=mask_value,
            k_scale=k_scale,
            v_scale=v_scale,
            return_lse=return_lse,
            block_len=block_len,
            tiling=tiling,
            head_major=head_major,
            has_sinks=sinks is not None,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalar_prefetches),
            in_specs=in_specs,
            out_specs=out_specs,
            grid=grid,
            scratch_shapes=scratch_shapes,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=tiling.vmem_limit_bytes,
        ),
        out_shape=out_shape,
        name=name,
        interpret=interpret,
    )

    outs = kernel(*scalar_prefetches, *operands)
    if not return_lse:
        return outs[0]
    out, lse_raw = outs
    # [KH, num_q_blks*numq*ratio, 128] lane-0 -> [T, H].
    lse = lse_raw[:, :, 0]  # [KH, T*ratio]
    lse = lse.reshape(num_kv_heads, -1, num_q_heads_per_kv_head)
    lse = jnp.transpose(lse, (1, 0, 2)).reshape(-1, num_q_heads)
    return out, lse[:num_q_tokens]
