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

Layout contract (``ops/attention.py``): K/V heads interleaved on axis 3
(``0::2`` = K, ``1::2`` = V) so one page's per-head K,V pair is
contiguous for the per-page DMA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax._src import dtypes
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.ragged_paged_attention.tuned_block_sizes import (  # noqa: E501
    get_tuned_block_sizes,
)

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.dtype("float32")).max)

# Mosaic's scoped-VMEM budget for one kernel when ``vmem_limit_bytes`` is
# not given (v5e; the chip's compiler reports it as "limit 16.00M").
_DEFAULT_SCOPED_VMEM_BYTES = 16 << 20


def _dtype_packing(dtype) -> int:
    return 32 // dtypes.itemsize_bits(dtype)


def strided_load_kv(ref, start, step):
    """Split interleaved K/V rows; handles sub-32-bit packed dtypes.

    ``ref`` is a flat ``[N_rows, lanes]`` VMEM view whose rows interleave
    K/V heads with period ``step``; returns the K rows starting at
    ``start`` and the V rows starting at ``start + 1`` (lists, because a
    packed dtype yields several heads per 32-bit load). Shared by the
    general ragged kernel and the decode-specialized kernel
    (``rpa_decode_kernel.py``)."""
    packing = _dtype_packing(ref.dtype)
    if packing == 1:
        return [ref[start::step, :]], [ref[start + 1 :: step, :]]
    assert packing in (2, 4, 8)
    assert step % packing == 0
    k_list, v_list = [], []
    b_ref = ref.bitcast(jnp.uint32)
    b = b_ref[start // packing :: step // packing, :]
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


class _PageCopy:
    """Async copy of one KV block's pages HBM -> VMEM, layer-indexed."""

    def __init__(self, pages_hbm_ref, vmem_buf, sem, page_indices_ref,
                 layer, seq_id, start_page_idx, end_page_idx):
        self._vmem_buf = vmem_buf
        self._copies = []
        for i in range(vmem_buf.shape[0]):
            page_idx = start_page_idx + i
            page_idx = lax.select(page_idx < end_page_idx, page_idx, 0)
            self._copies.append(
                pltpu.make_async_copy(
                    pages_hbm_ref.at[layer, page_indices_ref[seq_id, page_idx]],
                    vmem_buf.at[i],
                    sem,
                )
            )

    def start(self):
        for c in self._copies:
            c.start()

    def wait(self):
        for c in self._copies:
            c.wait()
        return self._vmem_buf


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
    block_len: int = 1,
):
    if return_lse:
        lse_ref, kv_bufs, sems, l_ref, m_ref, acc_ref = rest
    else:
        lse_ref = None
        kv_bufs, sems, l_ref, m_ref, acc_ref = rest

    num_q_per_blk, num_q_heads_per_blk, head_dim = q_ref.shape
    pages_per_seq = page_indices_ref.shape[-1]
    num_seqs = num_seqs_ref[0]
    layer = layer_ref[0]
    _, num_kv_pages_per_blk, page_size, kv_head_rows_per_blk, kv_lanes = (
        kv_bufs.shape
    )
    packed = kv_lanes == 2 * head_dim  # [.., KH, 2D] layout (head_dim 64)
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
        window = window_ref[0]
        q_len = cu_q_lens_ref[seq_idx + 1] - cu_q_lens_ref[seq_idx]
        first_tok = jnp.maximum(
            kv_lens_ref[seq_idx] - q_len - (window - 1), 0
        )
        return jnp.where(
            jnp.logical_and(window > 0, ctx_stride == 1),
            first_tok // num_kv_per_blk,
            0,
        )

    def make_page_copy(heads_blk_idx, seq_idx, kv_blk_idx, buf_idx):
        start_page = kv_blk_idx * num_kv_pages_per_blk
        end_page = jnp.minimum(pages_per_seq, local_ctx(seq_idx)[0])
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
        return _PageCopy(
            src,
            kv_bufs.at[buf_idx],
            sems.at[buf_idx],
            page_indices_ref,
            layer,
            seq_idx,
            start_page,
            end_page,
        )

    @pl.when(heads_blk_idx + q_blk_idx == 0)
    def prefetch_first_kv_blk():
        make_page_copy(
            heads_blk_idx, init_seq_idx, seq_start_blk(init_seq_idx),
            init_buf_idx,
        ).start()

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
        local_bound = jnp.maximum(local_ctx(cur_seq_idx)[1], 1)

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

        def flash_attention(q, k, v, head_l_ref, head_m_ref, head_acc_ref, *,
                            kv_blk_idx, start_blk):
            assert q.shape == (num_q_per_blk * num_q_heads_per_kv_head, head_dim)
            assert k.shape == v.shape == (num_kv_per_blk, head_dim)
            kv_len_start = kv_blk_idx * num_kv_per_blk

            def masked_store(ref, val, start, end, group=1):
                iota = lax.broadcasted_iota(jnp.int32, ref.shape, 0) // group
                pltpu.store(
                    ref, val, mask=jnp.logical_and(iota >= start, iota < end)
                )

            def load_with_init(ref, init_val):
                return jnp.where(
                    kv_blk_idx == start_blk, jnp.full_like(ref, init_val),
                    ref[...],
                )

            # KV rows beyond the (striped) context are garbage; zero them
            # so the contraction stays NaN-free. Position arithmetic is in
            # GLOBAL context coordinates: local flat slot c maps to page
            # (c // ps) * stride + phase, offset c % ps.
            kv_flat = kv_len_start + lax.broadcasted_iota(
                jnp.int32, k.shape, 0
            )
            kv_gpos = (
                (kv_flat // page_size) * ctx_stride + ctx_phase
            ) * page_size + kv_flat % page_size
            kv_mask = kv_gpos < kv_len
            k = jnp.where(kv_mask, k.astype(jnp.float32), 0).astype(k.dtype)
            v = jnp.where(kv_mask, v.astype(jnp.float32), 0).astype(v.dtype)

            qk = (
                jnp.einsum("nd,md->nm", q, k,
                           preferred_element_type=jnp.float32)
                * sm_scale
            )
            store_start = jnp.maximum(q_start - q_len_start, 0)
            store_end = jnp.minimum(q_end - q_len_start, num_q_per_blk)

            row_ids = (
                (kv_len - q_len)
                + q_len_start
                - q_start
                + lax.broadcasted_iota(
                    jnp.int32,
                    (num_q_per_blk * num_q_heads_per_kv_head, num_kv_per_blk),
                    0,
                )
                // num_q_heads_per_kv_head
            )
            col_flat = kv_len_start + lax.broadcasted_iota(
                jnp.int32,
                (num_q_per_blk * num_q_heads_per_kv_head, num_kv_per_blk),
                1,
            )
            col_ids = (
                (col_flat // page_size) * ctx_stride + ctx_phase
            ) * page_size + col_flat % page_size
            if block_len > 1:
                # Causal over blocks: a query sees up to the last position
                # of its own block (a row's queries cover whole blocks, so
                # that position is inside kv_len).
                row_ids = (row_ids // block_len + 1) * block_len - 1
            causal_mask = row_ids < col_ids
            window = window_ref[0]
            causal_mask = jnp.logical_or(
                causal_mask,
                (row_ids - window >= col_ids) & (window > 0),
            )
            if soft_cap is not None:
                qk = soft_cap * jnp.tanh(qk / soft_cap)
            qk += jnp.where(causal_mask, mask_value, 0.0)
            m_curr = jnp.max(qk, axis=1, keepdims=True)
            s_curr = jnp.exp(qk - m_curr)
            qkv = jnp.dot(s_curr, v, preferred_element_type=jnp.float32)
            lm_store_shape = head_m_ref.shape
            m_curr = jnp.broadcast_to(m_curr, lm_store_shape)
            l_curr = jnp.broadcast_to(
                s_curr.sum(axis=1, keepdims=True), lm_store_shape
            )
            m_prev = load_with_init(head_m_ref, -jnp.inf)
            l_prev = load_with_init(head_l_ref, 0.0)
            m_next = jnp.maximum(m_prev, m_curr)
            masked_store(head_m_ref, m_next, store_start, store_end,
                         num_q_heads_per_kv_head)
            alpha = jnp.exp(m_prev - m_next)
            beta = jnp.exp(m_curr - m_next)
            l_alpha = alpha * l_prev
            l_next = l_alpha + beta * l_curr
            l_next_safe = jnp.where(l_next == 0.0, 1.0, l_next)
            masked_store(head_l_ref, l_next_safe, store_start, store_end,
                         num_q_heads_per_kv_head)

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

            o_curr = load_with_init(head_acc_ref, 0.0).reshape(-1, head_dim)
            l_alpha = broadcast_to_shape(l_alpha, qkv.shape)
            beta = broadcast_to_shape(beta, qkv.shape)
            l_next_safe_b = broadcast_to_shape(l_next_safe, qkv.shape)
            out = (l_alpha * o_curr + beta * qkv) / l_next_safe_b
            masked_store(head_acc_ref, out.reshape(head_acc_ref.shape),
                         store_start, store_end)

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
                make_page_copy(
                    next_heads_blk_idx, next_seq_idx, next_kv_blk_idx,
                    next_buf_idx,
                ).start()

            kv_buf = make_page_copy(
                heads_blk_idx, cur_seq_idx, kv_blk_idx, cur_buf_idx
            ).wait()  # [pages, page_size, head rows, lanes]
            if not packed:
                kv_ref = kv_buf.reshape(
                    num_kv_pages_per_blk * page_size
                    * num_combined_kv_heads_per_blk,
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
            for kv_head_chunk_idx in range(0, num_kv_heads_per_blk,
                                           kv_load_step):
                if kv_ref is not None:
                    k_list, v_list = strided_load_kv(
                        kv_ref, kv_head_chunk_idx * 2,
                        num_combined_kv_heads_per_blk,
                    )
                else:
                    rows = kv_buf[:, :, kv_head_chunk_idx, :]
                    k_list = [rows[..., :head_dim].reshape(-1, head_dim)]
                    v_list = [rows[..., head_dim:].reshape(-1, head_dim)]
                for step_idx in range(kv_load_step):
                    k = k_list[step_idx]
                    v = v_list[step_idx]
                    if k_scale is not None:
                        k = (k.astype(jnp.float32) * k_scale).astype(
                            q_ref.dtype
                        )
                    if v_scale is not None:
                        v = (v.astype(jnp.float32) * v_scale).astype(
                            q_ref.dtype
                        )
                    kv_head_idx = kv_head_chunk_idx + step_idx
                    q_head_idx = kv_head_idx * num_q_heads_per_kv_head
                    q = fold_on_2nd_minor(
                        q_ref[:, q_head_idx : q_head_idx
                              + num_q_heads_per_kv_head, :]
                    )
                    flash_attention(
                        q, k, v,
                        l_ref.at[kv_head_idx],
                        m_ref.at[kv_head_idx],
                        acc_ref.at[
                            :, q_head_idx : q_head_idx
                            + num_q_heads_per_kv_head, :
                        ],
                        kv_blk_idx=kv_blk_idx,
                        start_blk=cur_start_blk,
                    )
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
    o_ref[...] = acc_ref[...].astype(q_ref.dtype)
    if return_lse:
        # lse = m + log(l): scratch blocks are [KH_blk, numq*ratio, 128]
        # (value broadcast over lanes); the host-side wrapper slices lane 0
        # and rearranges to [T, num_q_heads].
        lse_ref[...] = m_ref[...] + jnp.log(l_ref[...])


def _validate(q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs):
    _, num_q_heads, head_dim = q.shape
    _, _, _, kv_rows, kv_lanes = kv_pages.shape
    if kv_lanes == 2 * head_dim:  # packed [.., KH, 2D]
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
) -> int:
    """Halve the KV block until the kernel's VMEM need fits the budget.

    The tuned table's entries (and its untuned (128, 32) default, which
    is what Llama-3-8B's 32q/8kv heads get) assume nothing about this
    kernel's extra scratch, and at 8B widths the default overflows the
    chip's 16 MiB scoped limit. The need is estimated from the block
    shapes as ``1.5 * kv double buffer + 2 * (q, out, acc, l, m blocks)
    + the per-kv-head f32 score tiles``: the coefficients bound from
    above the smallest ``vmem_limit_bytes`` the v5e compiler accepted
    (MiB, bf16 q, 32q/16 combined heads, D=128, page 16; pages x queries):
    8x32: 5, 32x32: 11, 64x32: 17, 128x32: 28, 8x128: 14, 32x64: 18;
    fp8 KV 32x32: 7, 64x32: 10; a tp=4 shard (8q/4) 128x32: 7.
    ``tests/test_tpu_aot_compile.py`` holds the result to the compiler.
    """
    num_kv_heads_per_blk = num_combined_kv_heads_per_blk // 2
    ratio = num_q_heads_per_blk // num_kv_heads_per_blk
    q_blk_elems = num_q_per_blk * num_q_heads_per_blk * head_dim
    lm_bytes = num_kv_heads_per_blk * num_q_per_blk * ratio * 128 * 4
    q_side = q_blk_elems * (2 * q_itemsize + 4) + 2 * lm_bytes

    def need(pages: int) -> int:
        kv_tokens = pages * page_size
        kv_double_buf = (
            2 * kv_tokens * num_combined_kv_heads_per_blk * head_dim
            * kv_itemsize
        )
        scores = num_kv_heads_per_blk * num_q_per_blk * ratio * kv_tokens * 4
        return kv_double_buf * 3 // 2 + 2 * q_side + scores

    while num_kv_pages_per_blk > 1 and need(num_kv_pages_per_blk) > (
        vmem_budget_bytes
    ):
        num_kv_pages_per_blk //= 2
    return num_kv_pages_per_blk


@functools.partial(
    jax.jit,
    static_argnames=[
        "sm_scale", "mask_value", "num_kv_pages_per_block",
        "num_queries_per_block", "vmem_limit_bytes",
        "soft_cap", "k_scale", "v_scale", "return_lse", "interpret",
        "block_len",
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
):
    """Mixed prefill+decode flash attention over the paged KV cache.

    ``block_len`` > 1 makes the mask causal over blocks of that many
    positions (``col // block_len <= row // block_len``); every row's
    query tokens must then cover whole blocks, and no window applies.
    1 leaves the program as it was.

    Returns ``out [T, H, D]``, or ``(out, lse [T, H] f32)`` with
    ``return_lse=True``.

    ``ctx_stride``/``ctx_phase`` (ints or traced i32 scalars) give the
    kernel a striped context-parallel view: ``page_indices`` is a rank's
    LOCAL table whose column j holds global context page
    ``j*ctx_stride + ctx_phase``; ``kv_lens`` stays GLOBAL (query
    positions derive from it). The contract matches
    ``ref_ragged_paged_attention`` and ``cp_attention.cp_write_and_attend``.
    """
    _validate(q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs)
    if mask_value is None:
        mask_value = DEFAULT_MASK_VALUE
    num_q_tokens, num_q_heads, head_dim = q.shape
    _, _, page_size, kv_rows, kv_lanes = kv_pages.shape
    packed = kv_lanes == 2 * head_dim  # [.., KH, 2D] layout (head_dim 64)
    num_combined_kv_heads = 2 * kv_rows if packed else kv_rows
    num_kv_heads = num_combined_kv_heads // 2
    _, pages_per_seq = page_indices.shape
    if not packed:
        num_q_heads_per_blk, num_combined_kv_heads_per_blk = (
            _min_heads_per_blk(
                num_q_heads, num_combined_kv_heads, q.dtype, kv_pages.dtype
            )
        )
    else:
        # Packed layout: one heads block, no HBM heads slicing (a lane-dim
        # or sub-tile memref slice is rejected by Mosaic).
        num_q_heads_per_blk = num_q_heads
        num_combined_kv_heads_per_blk = num_combined_kv_heads
    num_q_per_blk = num_queries_per_block
    num_kv_pages_per_blk = num_kv_pages_per_block
    if num_q_per_blk is None or num_kv_pages_per_blk is None:
        num_kv_pages_per_blk, num_q_per_blk = get_tuned_block_sizes(
            q.dtype,
            kv_pages.dtype,
            num_q_heads_per_blk,
            num_combined_kv_heads_per_blk // 2,
            head_dim,
            page_size,
            num_q_tokens,
            pages_per_seq,
        )
        num_kv_pages_per_blk = _fit_kv_pages_to_vmem(
            min(num_kv_pages_per_blk, pages_per_seq),
            vmem_limit_bytes or _DEFAULT_SCOPED_VMEM_BYTES,
            num_q_per_blk=num_q_per_blk,
            num_q_heads_per_blk=num_q_heads_per_blk,
            num_combined_kv_heads_per_blk=num_combined_kv_heads_per_blk,
            head_dim=head_dim,
            page_size=page_size,
            q_itemsize=q.dtype.itemsize,
            kv_itemsize=kv_pages.dtype.itemsize,
        )
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
    lm_shape = (num_kv_heads_per_blk,
                num_q_per_blk * num_q_heads_per_kv_head, 128)
    out_specs = [q_block_spec]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if return_lse:
        out_specs.append(
            pl.BlockSpec(lm_shape, lambda h, qb, *_: (h, qb, 0))
        )
        out_shape.append(
            jax.ShapeDtypeStruct(
                (num_heads_blks * num_kv_heads_per_blk,
                 num_q_blks * num_q_per_blk * num_q_heads_per_kv_head, 128),
                jnp.float32,
            )
        )
    kv_rows_per_blk = (
        num_combined_kv_heads_per_blk // 2
        if packed
        else num_combined_kv_heads_per_blk
    )
    scratch_shapes = [
        pltpu.VMEM(
            (2, num_kv_pages_per_blk, page_size, kv_rows_per_blk, kv_lanes),
            kv_pages.dtype,
        ),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.VMEM(lm_shape, jnp.float32),  # l
        pltpu.VMEM(lm_shape, jnp.float32),  # m
        pltpu.VMEM((num_q_per_blk, num_q_heads_per_blk, head_dim),
                   jnp.float32),  # acc
    ]
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
            **({"block_len": block_len} if block_len > 1 else {}),
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
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        out_shape=out_shape,
        name="rpa_kernel",
        interpret=interpret,
    )

    outs = kernel(*scalar_prefetches, q, kv_pages)
    if not return_lse:
        return outs[0]
    out, lse_raw = outs
    # [KH, num_q_blks*numq*ratio, 128] lane-0 -> [T, H].
    lse = lse_raw[:, :, 0]  # [KH, T*ratio]
    lse = lse.reshape(num_kv_heads, -1, num_q_heads_per_kv_head)
    lse = jnp.transpose(lse, (1, 0, 2)).reshape(-1, num_q_heads)
    return out, lse[:num_q_tokens]
