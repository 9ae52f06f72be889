"""Decode-specialized Pallas TPU kernel: sequence-pipelined paged attention.

The general ragged kernel (``rpa_kernel.py``) walks sequences with a
per-sequence ``while_loop``: one double-buffered DMA chain *within* a
sequence, but every sequence boundary serializes a DMA wait plus a tiny
``[G, D] x [D, ctx]`` contraction. At decode shapes (q_len == 1 for
every row, short-to-medium contexts) that is ~2k serial iterations per
layer per step and measures ~40x off the KV-read roofline — the analog
of the reference's dedicated ``paged_attention_v1/v2.cu`` decode path
next to its unified varlen flash kernel.

This kernel flips the loop structure for the decode-only case:

- **Grid** ``(kv_head_blocks, sequence_blocks)``: each program owns a
  block of ``num_seqs_per_block`` sequences, not one ragged q span.
- **DMAs pipelined ACROSS sequences**: one KV *tile* =
  ``num_kv_pages_per_block`` pages of *every* sequence in the block,
  issued as one batch of parallel page copies into a single
  double-buffered VMEM scratch. While tile *t* is being contracted,
  tile *t+1* — or the first tile of the *next* sequence block, chained
  across grid programs like the general kernel's ``seq_buf_idx`` — is
  already in flight. Per-sequence DMA latency no longer serializes.
- **One MXU contraction per tile**: the per-sequence ``q_i @ K_i^T``
  matvecs are concatenated into a single 2D
  ``[S*G, D] x [D, S*KV_TILE]`` cross-product dot with a block-diagonal
  sequence mask (Mosaic only lowers 2D ``dot_general``; the off-diagonal
  FLOPs are free — decode attention is bandwidth-bound and the MXU is
  otherwise idle).
- **Online softmax carried as loop values** (per kv-head ``m``/``l``/
  ``acc`` tuples in the ``fori_loop`` carry) instead of masked scratch
  stores; the accumulator is rescaled once per tile and normalized once
  at the end.

Contract (the decode-only fast path of ``ops/attention.py``):

- ``q [R, H, D]`` — exactly one token per scheduled row, row i == seq i
  (the runner forces ``t_pad == r_pad`` for decode-only batches);
- ``kv_lens [R]`` — context length *including* the current token, so
  causality degenerates to ``pos < kv_len`` (no q-position arithmetic);
- rows at or beyond ``num_seqs`` are dead: they read the null page and
  produce finite garbage, exactly like the general kernel's padding.

fp8 KV dequant (``k_scale``/``v_scale``) and the packed ``[.., KH, 2D]``
head_dim-64 layout are handled identically to the general kernel (shared
``strided_load_kv``). No LSE output and no sliding mask — callers needing
either (context parallelism, tree verification, a layer that keeps the
last N keys) stay on the general kernel, which starts a row at its
floor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_tpu.ops.rpa_kernel import (
    DEFAULT_MASK_VALUE,
    _dtype_packing,
    _min_heads_per_blk,
    fold_on_2nd_minor,
    strided_load_kv,
)


class _TileCopy:
    """Async copies of ONE KV tile for a whole sequence block.

    ``num_seqs_per_block * num_kv_pages_per_block`` page copies issued
    together (this parallel issue is the point of the kernel); columns
    past a sequence's last page — and every column of a dead row — are
    clamped to page column 0 so the copy count per tile is uniform and
    the double-buffer chain never desyncs across grid programs."""

    def __init__(self, src_hbm_ref, vmem_buf, sem, page_indices_ref,
                 layer, tile_idx, kp, seq_cols):
        # vmem_buf: [S_BLK * KP, PS, rows, lanes]; seq_cols: per local
        # sequence (clamped row index into page_indices, end page).
        self._vmem_buf = vmem_buf
        self._copies = []
        for s, (row, end) in enumerate(seq_cols):
            for j in range(kp):
                col = tile_idx * kp + j
                col = lax.select(col < end, col, 0)
                self._copies.append(
                    pltpu.make_async_copy(
                        src_hbm_ref.at[layer, page_indices_ref[row, col]],
                        vmem_buf.at[s * kp + j],
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


def _decode_kernel(
    # Scalar prefetch
    kv_lens_ref,  # [R] context length incl. the current token
    page_indices_ref,  # [R, pages_per_seq]
    num_seqs_ref,  # [1]
    layer_ref,  # [1]
    # Inputs
    q_ref,  # [S_BLK, num_q_heads_per_blk, head_dim]
    kv_pages_hbm_ref,  # [L, NB, page_size, kv_rows, kv_lanes]
    # [with sinks: sink_ref [KH_blk, S_BLK * G, 128] f32, then] Outputs
    o_ref,  # [S_BLK, num_q_heads_per_blk, head_dim]
    # Scratch
    kv_bufs,  # [2, S_BLK * KP, page_size, kv_rows_per_blk, kv_lanes]
    sems,  # DMA semaphores (2,)
    *rest,
    sm_scale: float,
    soft_cap: float | None,
    mask_value: float,
    k_scale: float | None,
    v_scale: float | None,
    head_major: bool = False,
):
    sink_ref = None
    if rest:  # the logit of each row's q head, an input after the cache
        sink_ref, o_ref, kv_bufs, sems = o_ref, kv_bufs, sems, *rest
    s_blk, num_q_heads_per_blk, head_dim = q_ref.shape
    v_dim = o_ref.shape[-1]  # head_dim but in the split layout
    r_max = kv_lens_ref.shape[0]
    pages_per_seq = page_indices_ref.shape[1]
    num_seqs = num_seqs_ref[0]
    layer = layer_ref[0]
    if head_major:  # the split layout's page, [KH, page_size, Dk_pad + Dv]
        _, skp, kv_rows_per_blk, page_size, kv_lanes = kv_bufs.shape
    else:
        _, skp, page_size, kv_rows_per_blk, kv_lanes = kv_bufs.shape
    kp = skp // s_blk
    kv_tile = kp * page_size  # context tokens per sequence per tile
    # [.., KH, 2D] layout (head_dim 64), or the split one: K || V a row.
    packed = head_major or kv_lanes == 2 * head_dim
    num_combined_kv_heads_per_blk = (
        2 * kv_rows_per_blk if packed else kv_rows_per_blk
    )
    num_kv_heads_per_blk = num_combined_kv_heads_per_blk // 2
    g = num_q_heads_per_blk // num_kv_heads_per_blk
    sg = s_blk * g
    skv = s_blk * kv_tile
    heads_blk_idx = pl.program_id(0)
    seq_blk_idx = pl.program_id(1)
    num_heads_blks = pl.num_programs(0)
    num_seq_blks = pl.num_programs(1)

    def seq_kv_len(seq_idx):
        """Context length of a global sequence row; 0 beyond the live
        count (dead rows attend nothing and their K/V is zeroed)."""
        idx = jnp.minimum(seq_idx, r_max - 1)
        return jnp.where(seq_idx < num_seqs, kv_lens_ref[idx], 0)

    def seq_end_page(seq_idx):
        kv_len = seq_kv_len(seq_idx)
        return jnp.minimum(pl.cdiv(kv_len, page_size), pages_per_seq)

    def block_tiles(blk_idx):
        """Tiles of a sequence block, walked from tile 0. A pure function
        of the scalar prefetches and blk_idx ONLY, so the DMA prefetch
        chain and the compute loop always agree. The floor is 1: a block
        of dead/empty rows still runs one fully-masked tile, keeping
        buffer ownership uniform."""
        t_end = jnp.int32(1)
        for s in range(s_blk):
            t_end = jnp.maximum(
                t_end, pl.cdiv(seq_end_page(blk_idx * s_blk + s), kp))
        return t_end

    def make_tile_copy(h_blk, b_blk, tile_idx, slot):
        if num_heads_blks == 1:
            # No heads sub-slice (Mosaic rejects lane-dim slices below
            # the 128-lane tile, and it would be a no-op anyway).
            src = kv_pages_hbm_ref
        else:
            heads_start = h_blk * num_combined_kv_heads_per_blk
            src = kv_pages_hbm_ref.at[
                :, :, :, pl.ds(heads_start, num_combined_kv_heads_per_blk), :
            ]
        seq_cols = []
        for s in range(s_blk):
            seq_idx = b_blk * s_blk + s
            seq_cols.append((
                jnp.minimum(seq_idx, r_max - 1),
                seq_end_page(seq_idx),
            ))
        return _TileCopy(
            src, kv_bufs.at[slot], sems.at[slot], page_indices_ref,
            layer, tile_idx, kp, seq_cols,
        )

    t_end = block_tiles(seq_blk_idx)

    def start_parity():
        """Double-buffer parity at this program's first tile: the total
        tile-loop trip count of every EARLIER grid program, mod 2.

        Derived arithmetically instead of carrying a mutable scalar-
        prefetch ref across programs (the general kernel's
        ``seq_buf_idx`` trick): parity is a pure function of the grid
        position and the scalar prefetches, which also holds in
        interpret mode, where cross-program scalar mutations do not
        persist."""

        def add_iters(blk_idx, acc):
            return acc + block_tiles(blk_idx)

        before = lax.fori_loop(0, seq_blk_idx, add_iters, jnp.int32(0))
        if num_heads_blks > 1:
            per_heads_blk = lax.fori_loop(
                0, num_seq_blks, add_iters, jnp.int32(0)
            )
            before = before + heads_blk_idx * per_heads_blk
        return lax.rem(before, 2)

    @pl.when(heads_blk_idx + seq_blk_idx == 0)
    def prefetch_first_tile():
        make_tile_copy(0, 0, jnp.int32(0), 0).start()

    def next_prefetch_ids(tile_idx):
        """Grid-order successor of (heads_blk, seq_blk, tile): next tile
        in this block, else the next block's first tile, else the next
        heads block's first block (mirrors the general kernel's
        cross-program chain)."""
        nt = tile_idx + 1
        last_tile = nt >= t_end
        nb0 = seq_blk_idx + 1
        wrap = nb0 >= num_seq_blks
        nb = lax.select(
            last_tile, lax.select(wrap, 0, nb0), seq_blk_idx
        )
        nh = lax.select(
            jnp.logical_and(last_tile, wrap),
            heads_blk_idx + 1,
            heads_blk_idx,
        )
        nt = lax.select(last_tile, 0, nt)
        return nh, nb, nt

    # Tile-invariant geometry: the block-diagonal sequence mask and the
    # per-column/-row context lengths of this block's sequences.
    kv_len_blk = [
        seq_kv_len(seq_blk_idx * s_blk + s) for s in range(s_blk)
    ]
    rows_iota = lax.broadcasted_iota(jnp.int32, (sg, skv), 0)
    cols_iota = lax.broadcasted_iota(jnp.int32, (sg, skv), 1)
    same_seq = (rows_iota // g) == (cols_iota // kv_tile)
    col_off = cols_iota % kv_tile  # position offset within the seq tile
    kv_len_cols = jnp.concatenate(
        [
            kv_len_blk[s] * jnp.ones((1, kv_tile), jnp.int32)
            for s in range(s_blk)
        ],
        axis=1,
    )  # [1, SKV]
    kv_len_rows = jnp.concatenate(
        [
            kv_len_blk[s] * jnp.ones((kv_tile, 1), jnp.int32)
            for s in range(s_blk)
        ],
        axis=0,
    )  # [SKV, 1]
    kv_row_off = lax.broadcasted_iota(jnp.int32, (skv, 1), 0) % kv_tile

    # Per-kv-head query rows [S*G, D]; row r belongs to sequence r // g.
    q_heads = [
        fold_on_2nd_minor(q_ref[:, i * g : (i + 1) * g, :])
        for i in range(num_kv_heads_per_blk)
    ]

    def tile_body(tile_idx, carry):
        buf_idx, head_states = carry
        nh, nb, nt = next_prefetch_ids(tile_idx)

        @pl.when(nh < num_heads_blks)
        def prefetch_next_tile():
            make_tile_copy(nh, nb, nt, 1 - buf_idx).start()

        kv_buf = make_tile_copy(
            heads_blk_idx, seq_blk_idx, tile_idx, buf_idx
        ).wait()  # [S*KP, page_size, rows, lanes]

        # Context positions of this tile's columns and the combined mask:
        # block-diagonal x causal (pos < kv_len, q sits at kv_len - 1).
        # Dead rows have kv_len 0 => fully masked.
        pos = tile_idx * kv_tile + col_off  # [SG, SKV]
        keep = same_seq & (pos < kv_len_cols)
        # K/V rows past the context are DMA'd garbage; zero them so the
        # contraction stays NaN-free.
        kv_valid = (
            tile_idx * kv_tile + kv_row_off
        ) < kv_len_rows  # [SKV, 1]

        if not packed:
            kv_ref = kv_buf.reshape(
                skp * page_size * num_combined_kv_heads_per_blk, head_dim
            )
            kv_packing = _dtype_packing(kv_ref.dtype)
            kv_load_step = max(1, kv_packing // 2)
        else:
            kv_ref = None
            kv_load_step = 1
        new_states = list(head_states)
        for chunk_idx in range(0, num_kv_heads_per_blk, kv_load_step):
            if kv_ref is not None:
                k_list, v_list = strided_load_kv(
                    kv_ref, chunk_idx * 2, num_combined_kv_heads_per_blk
                )
            else:
                # Packed [.., KH, 2D]: K/V are the lane halves of one
                # 128-lane row.
                rows = (kv_buf[:, chunk_idx, :, :] if head_major
                        else kv_buf[:, :, chunk_idx, :])
                k_list = [rows[..., :head_dim].reshape(-1, head_dim)]
                v_list = [rows[..., head_dim:].reshape(-1, v_dim)]
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
                k = jnp.where(kv_valid, k.astype(jnp.float32), 0.0).astype(
                    k.dtype
                )
                v = jnp.where(kv_valid, v.astype(jnp.float32), 0.0).astype(
                    v.dtype
                )
                kv_head_idx = chunk_idx + step_idx
                # ONE 2D cross-product contraction for the whole block;
                # the block-diagonal mask kills cross-sequence terms.
                s_qk = (
                    jnp.einsum(
                        "nd,md->nm", q_heads[kv_head_idx], k,
                        preferred_element_type=jnp.float32,
                    )
                    * sm_scale
                )
                if soft_cap is not None:
                    s_qk = soft_cap * jnp.tanh(s_qk / soft_cap)
                # Masked entries become a CONSTANT floor and their
                # probabilities are zeroed explicitly. Unlike the general
                # kernel (whose per-seq loop never visits a tile fully
                # past a sequence's context), a sequence here runs every
                # tile of its BLOCK — additive masking would let the raw
                # score spread of a fully-masked tile leak into m/l.
                s_qk = jnp.where(keep, s_qk, mask_value)
                m_prev, l_prev, acc_prev = new_states[kv_head_idx]
                m_curr = jnp.max(s_qk, axis=1, keepdims=True)
                m_next = jnp.maximum(m_prev, m_curr)
                alpha = jnp.exp(m_prev - m_next)
                p = jnp.where(keep, jnp.exp(s_qk - m_next), 0.0)
                l_next = alpha * l_prev + jnp.sum(
                    p, axis=1, keepdims=True
                )
                acc_next = alpha * acc_prev + jnp.dot(
                    p, v, preferred_element_type=jnp.float32
                )
                new_states[kv_head_idx] = (m_next, l_next, acc_next)
        return 1 - buf_idx, tuple(new_states)

    # A sink is a row's state before its first key: a maximum of its
    # logit and a denominator of exp(0).
    init_states = tuple(
        (
            jnp.full((sg, 1), mask_value, jnp.float32) if sink_ref is None
            else sink_ref[i][:, :1],  # m
            jnp.full((sg, 1), 0.0 if sink_ref is None else 1.0,
                     jnp.float32),  # l
            jnp.zeros((sg, v_dim), jnp.float32),  # acc
        )
        for i in range(num_kv_heads_per_blk)
    )
    _, final_states = lax.fori_loop(
        0, t_end, tile_body, (start_parity(), init_states)
    )

    outs = []
    for kv_head_idx in range(num_kv_heads_per_blk):
        _, l, acc = final_states[kv_head_idx]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        outs.append((acc / l_safe).reshape(s_blk, g, v_dim))
    o_ref[...] = jnp.concatenate(outs, axis=1).astype(q_ref.dtype)


def _validate(q, kv_pages, kv_lens, page_indices, num_seqs, head_major):
    num_rows, num_q_heads, head_dim = q.shape
    _, _, _, kv_rows, kv_lanes = kv_pages.shape
    if head_major:  # split [.., KH, BS, Dk_pad + Dv], q padded to Dk_pad
        num_kv_heads = kv_pages.shape[2]
        if kv_lanes <= head_dim or head_dim % 128:
            raise ValueError(
                f"split layout: {kv_lanes=} must hold a key of {head_dim=} "
                "whole lane tiles and a value")
    elif kv_lanes == 2 * head_dim:  # packed [.., KH, 2D]
        num_kv_heads = kv_rows
    else:
        assert kv_rows % 2 == 0
        num_kv_heads = kv_rows // 2
    if num_seqs.shape != (1,):
        raise ValueError(f"{num_seqs.shape=} must be (1,)")
    if kv_lens.shape != (num_rows,):
        raise ValueError(
            f"{kv_lens.shape=} != ({num_rows},) — the decode kernel "
            f"requires exactly one token per row (t_pad == r_pad)"
        )
    if page_indices.shape[0] != num_rows:
        raise ValueError(f"{page_indices.shape=} rows != {num_rows}")
    for name, arr in (("kv_lens", kv_lens), ("page_indices", page_indices)):
        if arr.dtype != jnp.int32:
            raise ValueError(f"{name} must be int32, got {arr.dtype}")
    if num_q_heads % num_kv_heads != 0:
        raise ValueError(f"{num_q_heads=} % {num_kv_heads=} != 0")


@functools.partial(
    jax.jit,
    static_argnames=[
        "sm_scale", "mask_value", "soft_cap", "k_scale", "v_scale",
        "num_seqs_per_block", "num_kv_pages_per_block",
        "vmem_limit_bytes", "interpret", "name", "head_major",
    ],
)
def decode_paged_attention(
    q: jax.Array,  # [R, num_q_heads, head_dim] — ONE token per row
    kv_pages: jax.Array,  # [L, total_pages, page_size, kv_rows, kv_lanes]
    layer: jax.Array,  # i32[1]
    kv_lens: jax.Array,  # i32[R], context incl. the current token
    page_indices: jax.Array,  # i32[R, pages_per_seq]
    num_seqs: jax.Array,  # i32[1]
    *,
    sm_scale: float = 1.0,
    soft_cap: float | None = None,
    mask_value: float | None = None,
    k_scale: float | None = None,
    v_scale: float | None = None,
    num_seqs_per_block: int | None = None,
    num_kv_pages_per_block: int | None = None,
    vmem_limit_bytes: int | None = None,
    interpret: bool = False,
    name: str = "rpa_decode_kernel",
    sinks: jax.Array | None = None,  # f32[num_q_heads]
    head_major: bool = False,
):
    """Decode-only flash attention over the paged KV cache.

    ``sinks`` and ``head_major`` as ``ragged_paged_attention`` has them: a
    logit a q head that a row's softmax state starts at, and the split
    layout's view ``[L, NB, KH, page_size, Dk_pad + Dv]`` under a query
    padded to ``Dk_pad``, which returns ``[R, H, Dv]``.

    ``name`` is the ``pallas_call``'s, which a profiler trace prints: a
    model that runs this kernel for two kinds of layer gives one of them
    another (``dispatch_ragged_attention``, ``kernel_suffix``).

    Semantically identical to ``ragged_paged_attention`` restricted to
    ``q_len == 1`` for every row (``cu_q_lens == arange``); returns
    ``out [R, H, D]``. See the module docstring for the pipelining
    design. Rows at or beyond ``num_seqs[0]`` produce finite garbage.
    """
    _validate(q, kv_pages, kv_lens, page_indices, num_seqs, head_major)
    if mask_value is None:
        mask_value = DEFAULT_MASK_VALUE
    num_rows, num_q_heads, head_dim = q.shape
    _, _, page_size, kv_rows, kv_lanes = kv_pages.shape
    v_dim = head_dim
    if head_major:
        kv_rows, page_size = kv_pages.shape[2:4]
        v_dim = kv_lanes - head_dim
    packed = head_major or kv_lanes == 2 * head_dim
    num_combined_kv_heads = 2 * kv_rows if packed else kv_rows
    _, pages_per_seq = page_indices.shape
    if not packed:  # (the split layout, as the packed one: one heads block)
        num_q_heads_per_blk, num_combined_kv_heads_per_blk = (
            _min_heads_per_blk(
                num_q_heads, num_combined_kv_heads, q.dtype, kv_pages.dtype
            )
        )
    else:
        num_q_heads_per_blk = num_q_heads
        num_combined_kv_heads_per_blk = num_combined_kv_heads

    if num_seqs_per_block is None:
        num_seqs_per_block = 4
    s_blk = max(1, min(num_seqs_per_block, num_rows))
    if num_kv_pages_per_block is None:
        # Target a ~128-token KV tile per sequence: big enough to shape
        # the contraction, small enough that short decode contexts don't
        # over-fetch.
        num_kv_pages_per_block = max(1, 128 // page_size)
    kp = max(1, min(num_kv_pages_per_block, pages_per_seq))

    num_heads_blks = num_q_heads // num_q_heads_per_blk
    num_seq_blks = pl.cdiv(num_rows, s_blk)
    grid = (num_heads_blks, num_seq_blks)

    def q_index_map(heads_blk_idx, seq_blk_idx, *_):
        return (seq_blk_idx, heads_blk_idx, 0)

    q_block_spec = pl.BlockSpec(
        (s_blk, num_q_heads_per_blk, head_dim), q_index_map
    )
    kv_rows_per_blk = (
        num_combined_kv_heads_per_blk // 2
        if packed
        else num_combined_kv_heads_per_blk
    )
    page_shape = (
        (kv_rows_per_blk, page_size) if head_major
        else (page_size, kv_rows_per_blk)
    )
    scratch_shapes = [
        pltpu.VMEM((2, s_blk * kp, *page_shape, kv_lanes), kv_pages.dtype),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    operands = [q, kv_pages]
    in_specs = [q_block_spec, pl.BlockSpec(memory_space=pl.ANY)]
    if sinks is not None:
        # Row r of a KV head is (sequence r // g, q head r % g of its group).
        num_kv_heads = num_combined_kv_heads // 2
        g = num_q_heads // num_kv_heads
        operands.append(jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(num_kv_heads, 1, g, 1),
            (num_kv_heads, s_blk, g, 128),
        ).reshape(num_kv_heads, s_blk * g, 128))
        in_specs.append(pl.BlockSpec(
            (num_combined_kv_heads_per_blk // 2, s_blk * g, 128),
            lambda h, sb, *_: (h, 0, 0)))
    out_block_spec = q_block_spec if v_dim == head_dim else pl.BlockSpec(
        (s_blk, num_q_heads_per_blk, v_dim), q_index_map)
    scalar_prefetches = (
        kv_lens,
        page_indices,
        num_seqs,
        layer.astype(jnp.int32).reshape(1),
    )
    kernel = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            sm_scale=sm_scale,
            soft_cap=soft_cap,
            mask_value=mask_value,
            k_scale=k_scale,
            v_scale=v_scale,
            head_major=head_major,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalar_prefetches),
            in_specs=in_specs,
            out_specs=[out_block_spec],
            grid=grid,
            scratch_shapes=scratch_shapes,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        out_shape=[jax.ShapeDtypeStruct(
            (num_rows, num_q_heads, v_dim), q.dtype)],
        name=name,
        interpret=interpret,
    )
    return kernel(*scalar_prefetches, *operands)[0]
