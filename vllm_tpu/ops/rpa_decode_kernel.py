"""Decode-specialized Pallas TPU kernel: sequence-pipelined paged attention.

The general ragged kernel (``rpa_kernel.py``) visits a sequence at a time:
a decode row is one visit of its narrow rung, its keys fetched and
contracted for that row alone. This kernel is the decode-only step's plain
call (one token a row, no window, no LSE), and takes FOUR rows a visit — the
analog of the reference's dedicated ``paged_attention_v1/v2.cu`` decode
path next to its unified varlen flash kernel:

- **Grid** ``(kv_head_blocks, sequence_blocks)``: each program owns a
  block of ``num_seqs_per_block`` sequences, not one ragged q span.
- **The walk visits live tiles only.** Live rows are the first
  ``num_seqs``, so the live blocks are the first
  ``max(1, cdiv(num_seqs, num_seqs_per_block))``. A program past them
  starts no copy of keys, awaits none and runs no tile: it sends zeros to
  its rows of the output and is done, and the copy chain and the buffer
  parity pass over it. Within the live blocks, position ``p`` of the walk
  serves row ``order[p]`` (``decode_row_order``: the live rows by context,
  longest first, computed once a step), so that the rows of a block end
  together: a block runs the tiles of its LONGEST row. q rows are fetched
  and output rows written each at its own place in the step (one-row
  views of q under the order; the kernel's own copies out), so a caller
  sees its own row order whatever the walk's. ``decode_tile_counts`` is the
  host's count of the tiles a call runs beside the least its rows need.
- **DMAs pipelined ACROSS sequences**: one KV *tile* =
  ``num_kv_pages_per_block`` pages of *every* sequence in the block,
  issued as one batch of parallel page copies into a single
  double-buffered VMEM scratch. While tile *t* is being contracted,
  tile *t+1* — or the first tile of the *next* live sequence block,
  chained across grid programs like the general kernel's ``seq_buf_idx``
  — is already in flight. Per-sequence DMA latency no longer serializes.
- **One MXU contraction per tile**: the per-sequence ``q_i @ K_i^T``
  matvecs are concatenated into a single 2D
  ``[S*G, D] x [D, S*KV_TILE]`` cross-product dot with a block-diagonal
  sequence mask (Mosaic only lowers 2D ``dot_general``; the off-diagonal
  FLOPs are free — decode attention is bandwidth-bound and the MXU is
  otherwise idle).
- **Online softmax carried as loop values** (per kv-head ``m``/``l``/
  ``acc`` tuples in the ``fori_loop`` carry) instead of masked scratch
  stores; the accumulator is rescaled once per tile and normalized once
  at the end. A tile that holds none of a row's keys leaves the row's
  state as it was, so a row's output is the same whatever rows share its
  block.

What a tile costs: 4 rows x 8 pages are copied whatever they hold (a column
past a row's end is its column 0 again, so that every tile is the same
count of copies), at Llama-8B's 8 KV heads 2 MB, 2.6 us of the v5e's HBM;
every KV head's ``[512, 128]`` keys and values go through float32 to zero
what lies past a row's context (only a row's last tile holds any), and the
cross product computes 16 rows where 4 are wanted. Both are per-tile costs
this file still pays (PERF.md section 7).

Contract (the decode-only fast path of ``ops/attention.py``):

- ``q [R, H, D]`` — exactly one token per scheduled row, row i == seq i
  (the runner forces ``t_pad == r_pad`` for decode-only batches);
- ``kv_lens [R]`` — context length *including* the current token, so
  causality degenerates to ``pos < kv_len`` (no q-position arithmetic);
- rows at or beyond ``num_seqs`` are dead: no key of theirs is read and
  their output is zeros.

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
import numpy as np
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
        # sequence (its row of page_indices, end page).
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


def _live_blocks(num_seqs, s_blk):
    """Sequence blocks that hold a live row; block 0 always counts (with no
    live row at all it runs one masked tile and writes its rows' zeros)."""
    return jnp.maximum(pl.cdiv(num_seqs, s_blk), 1)


def _row_at(pos, order_ref, num_seqs):
    """The step's row served at position ``pos`` of the walk: ``order``
    places the live rows, a dead position is its own row."""
    return jnp.where(pos < num_seqs, order_ref[pos], pos)


def _decode_kernel(
    # Scalar prefetch
    kv_lens_ref,  # [R] context length incl. the current token
    page_indices_ref,  # [R, pages_per_seq]
    num_seqs_ref,  # [1]
    layer_ref,  # [1]
    order_ref,  # [R] the row each position of the walk serves
    # Inputs: S_BLK views of q, one row each
    # [num_q_heads_per_blk, head_dim], then
    # kv_pages_hbm_ref [L, NB, page_size, kv_rows, kv_lanes]
    # [with sinks: sink_ref [KH_blk, S_BLK * G, 128] f32]; the output
    # o_hbm_ref [R, num_q_heads, v_dim] in HBM; the scratch
    # kv_bufs [2, S_BLK * KP, page_size, kv_rows_per_blk, kv_lanes],
    # sems DMA (2,), q_buf [S_BLK, num_q_heads_per_blk, head_dim],
    # o_buf and zero_buf [S_BLK, num_q_heads_per_blk, v_dim], o_sems DMA (2,)
    *refs,
    s_blk: int,
    sm_scale: float,
    soft_cap: float | None,
    mask_value: float,
    k_scale: float | None,
    v_scale: float | None,
    head_major: bool = False,
):
    q_refs, refs = refs[:s_blk], refs[s_blk:]
    kv_pages_hbm_ref, *sink, o_hbm_ref = refs[:-6]
    sink_ref = sink[0] if sink else None
    kv_bufs, sems, q_buf, o_buf, zero_buf, o_sems = refs[-6:]
    _, num_q_heads_per_blk, head_dim = q_buf.shape
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
    # A value is a key wide but in the split layout. A row of ``o_buf`` is
    # whole lane tiles (a head of 64 fills half of one): Mosaic copies no
    # row of 64 lanes out, so such a row leaves with lanes nobody reads.
    v_dim = kv_lanes - head_dim if head_major else head_dim
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
    # The walk visits the live blocks only: a program past them runs no
    # tile, and the DMA chain and the buffer parity skip it.
    num_live_blks = _live_blocks(num_seqs, s_blk)

    def seq_row(pos):
        return _row_at(pos, order_ref, num_seqs)

    def seq_kv_len(pos):
        """Context length of the row at a position of the walk; 0 beyond
        the live count (dead rows attend nothing and their K/V is
        zeroed)."""
        return jnp.where(pos < num_seqs, kv_lens_ref[seq_row(pos)], 0)

    def seq_end_page(pos):
        kv_len = seq_kv_len(pos)
        return jnp.minimum(pl.cdiv(kv_len, page_size), pages_per_seq)

    def block_tiles(blk_idx):
        """Tiles of a LIVE sequence block, walked from tile 0: those of
        its longest row. A pure function of the scalar prefetches and
        blk_idx ONLY, so the DMA prefetch chain and the compute loop
        always agree. The floor of 1 is block 0's when no row is live: it
        runs one fully-masked tile, keeping buffer ownership uniform."""
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
            pos = b_blk * s_blk + s
            seq_cols.append((seq_row(pos), seq_end_page(pos)))
        return _TileCopy(
            src, kv_bufs.at[slot], sems.at[slot], page_indices_ref,
            layer, tile_idx, kp, seq_cols,
        )

    def heads_of(dst):
        """This heads block's heads of rows of the output."""
        if num_heads_blks == 1:
            return dst
        return dst.at[:, pl.ds(heads_blk_idx * num_q_heads_per_blk,
                               num_q_heads_per_blk)]

    def out_copies(b_blk):
        """A live block's rows of ``o_buf``, each to its own row of the
        output."""
        return [
            pltpu.make_async_copy(
                o_buf.at[pl.ds(s, 1)],
                heads_of(o_hbm_ref.at[pl.ds(seq_row(b_blk * s_blk + s), 1)]),
                o_sems.at[0])
            for s in range(s_blk)
        ]

    def zeros_copy(b_blk):
        """Zeros to a dead block's rows of the output, which are the
        block's own places (``_row_at``)."""
        return pltpu.make_async_copy(
            zero_buf, heads_of(o_hbm_ref.at[pl.ds(b_blk * s_blk, s_blk)]),
            o_sems.at[1])

    @pl.when(heads_blk_idx + seq_blk_idx == 0)
    def first_program():
        make_tile_copy(0, 0, jnp.int32(0), 0).start()
        zero_buf[...] = jnp.zeros(zero_buf.shape, zero_buf.dtype)

    @pl.when(seq_blk_idx >= num_live_blks)
    def dead_block():
        # Every row of the block is dead: no copy of keys is started or
        # awaited and no tile runs. Its rows of the output are written
        # zeros (finite, as the rows of a live block past the live count
        # come out), and the last program awaits those writes.
        zeros_copy(seq_blk_idx).start()

    @pl.when(seq_blk_idx < num_live_blks)
    def live_block():
        """One live sequence block: its tiles from 0 to its longest row's
        last, then its rows' outputs sent to their own rows."""
        t_end = block_tiles(seq_blk_idx)

        def start_parity():
            """Double-buffer parity at this program's first tile: the total
            tile-loop trip count of every EARLIER live program, mod 2.

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
                    0, num_live_blks, add_iters, jnp.int32(0)
                )
                before = before + heads_blk_idx * per_heads_blk
            return lax.rem(before, 2)

        def next_prefetch_ids(tile_idx):
            """Successor of (heads_blk, seq_blk, tile) in the walk: next tile
            in this block, else the next LIVE block's first tile, else the
            next heads block's first block (mirrors the general kernel's
            cross-program chain)."""
            nt = tile_idx + 1
            last_tile = nt >= t_end
            nb0 = seq_blk_idx + 1
            wrap = nb0 >= num_live_blks
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

        # The block's rows of q, each fetched from its own row of the
        # step, and per kv head the query rows [S*G, D]; row r belongs to
        # sequence r // g.
        for s in range(s_blk):
            q_buf[s] = q_refs[s][...]
        q_heads = [
            fold_on_2nd_minor(q_buf[:, i * g : (i + 1) * g, :])
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
                            q_buf.dtype
                        )
                    if v_scale is not None:
                        v = (v.astype(jnp.float32) * v_scale).astype(
                            q_buf.dtype
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
                    # score spread of a fully-masked tile leak into m/l. A
                    # tile that holds none of a row's keys leaves its state
                    # as it was (alpha 1, p 0), so a row's output does not
                    # depend on the rows it shares a block with.
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
        # The rows leave through ONE buffer: the live program before this one
        # has had this program's tiles to send its own.
        sent = out_copies(seq_blk_idx)

        @pl.when(heads_blk_idx + seq_blk_idx > 0)
        def await_previous_rows():
            for c in sent:
                c.wait()

        o_buf[:, :, :v_dim] = jnp.concatenate(outs, axis=1).astype(o_buf.dtype)
        for c in sent:
            c.start()

        @pl.when(jnp.logical_and(heads_blk_idx == num_heads_blks - 1,
                                 seq_blk_idx == num_live_blks - 1))
        def await_last_rows():
            for c in sent:
                c.wait()

    @pl.when(jnp.logical_and(heads_blk_idx == num_heads_blks - 1,
                             seq_blk_idx == num_seq_blks - 1))
    def await_dead_rows():
        def await_block(_, carry):
            zeros_copy(seq_blk_idx).wait()  # (equal sizes: one for any)
            return carry

        lax.fori_loop(
            0, num_heads_blks * (num_seq_blks - num_live_blks), await_block,
            0)


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
    return num_kv_heads


def decode_tiling(num_rows, page_size, pages_per_seq,
                  num_seqs_per_block=None, num_kv_pages_per_block=None):
    """(sequences a block, pages a tile of one sequence) the kernel runs a
    call of these shapes with: 4 rows, and a tile of some 128 tokens a row
    (big enough to shape the contraction, small enough that short decode
    contexts don't over-fetch), unless the caller names others."""
    if num_seqs_per_block is None:
        num_seqs_per_block = 4
    if num_kv_pages_per_block is None:
        num_kv_pages_per_block = max(1, 128 // page_size)
    return (max(1, min(num_seqs_per_block, num_rows)),
            max(1, min(num_kv_pages_per_block, pages_per_seq)))


def decode_row_order(kv_lens: jax.Array, num_seqs: jax.Array) -> jax.Array:
    """The order the kernel's walk serves a step's rows in, i32[R]: the
    live rows by context, longest first, so that the four rows of a block
    end together; then the dead rows, each in its own place. Computed once
    a step, beside ``kv_lens``. Any permutation of the live rows is a
    valid order (each row's output is the same under all of them); this
    one runs the fewest tiles."""
    rows = jnp.arange(kv_lens.shape[0], dtype=jnp.int32)
    key = jnp.where(rows < num_seqs[0], -kv_lens, 1)
    return jnp.argsort(key, stable=True).astype(jnp.int32)


def decode_tile_counts(kv_lens, num_seqs: int, *, page_size: int,
                       num_seqs_per_block: int,
                       num_kv_pages_per_block: int) -> tuple[int, float]:
    """Tiles (a block of rows x the pages of one tile) a call RUNS on a
    heads block under ``decode_row_order``, and the LEAST its bytes need:
    every live row's own ``ceil(context / tile)``, summed, over the rows a
    block. NumPy, on the host: the runner's count of the kernel's work a
    decode-only step (``vllm:attn_decode_tiles_total``). ``run`` mirrors
    the kernel's ``block_tiles`` over its live blocks."""
    tile = page_size * num_kv_pages_per_block
    own = -(-np.sort(np.asarray(kv_lens[:num_seqs], np.int64))[::-1] // tile)
    longest = own[::num_seqs_per_block]  # of each live block
    run = int(np.maximum(longest, 1).sum()) if num_seqs else 1
    return run, float(own.sum()) / num_seqs_per_block


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
    order: jax.Array | None = None,  # i32[R], ``decode_row_order``
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
    design. Rows at or beyond ``num_seqs[0]`` come back as zeros.

    ``order`` is the step's ``decode_row_order``: position ``p`` of the
    kernel's walk serves row ``order[p]`` (only its first ``num_seqs[0]``
    entries, a permutation of the live rows, are read). q goes in and the
    output comes back in the step's own row order whatever it says, and a
    row's output does not depend on it; ``None`` walks the rows as given.
    """
    num_kv_heads = _validate(
        q, kv_pages, kv_lens, page_indices, num_seqs, head_major)
    if mask_value is None:
        mask_value = DEFAULT_MASK_VALUE
    rows_in, heads_in, _ = q.shape
    g_in = heads_in // num_kv_heads
    g_whole = next(n for n in range(g_in, g_in + 8) if (num_kv_heads * n) % 8 == 0)
    if g_whole != g_in:
        # A row of q and of the output is [heads, lanes], tiles of 8 heads,
        # and the kernel copies whole rows: Mosaic refuses 20 heads of a
        # tile's 24. Each KV head's group is filled up with query heads of
        # zeros (5 a KV head become 6), whose outputs are dropped below.
        fill = ((0, 0), (0, g_whole - g_in))
        q = jnp.pad(q.reshape(rows_in, num_kv_heads, g_in, -1),
                    ((0, 0), *fill, (0, 0))).reshape(
                        rows_in, num_kv_heads * g_whole, -1)
        if sinks is not None:
            sinks = jnp.pad(sinks.reshape(num_kv_heads, g_in), fill).reshape(-1)
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

    s_blk, kp = decode_tiling(
        num_rows, page_size, pages_per_seq, num_seqs_per_block,
        num_kv_pages_per_block)
    if order is None:
        order = jnp.arange(num_rows, dtype=jnp.int32)
    pad = -num_rows % s_blk
    if pad:  # whole blocks of rows: the rows added are dead
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        kv_lens = jnp.pad(kv_lens, (0, pad))
        page_indices = jnp.pad(page_indices, ((0, pad), (0, 0)))
        order = jnp.concatenate([
            order, jnp.arange(num_rows, num_rows + pad, dtype=jnp.int32)])

    num_heads_blks = num_q_heads // num_q_heads_per_blk
    num_seq_blks = (num_rows + pad) // s_blk
    grid = (num_heads_blks, num_seq_blks)

    def q_row_spec(s):
        """Row ``s`` of a block: the row of q the walk serves there. A
        program past the live blocks names the last live block's rows
        again, so that nothing is fetched for it."""

        def index_map(heads_blk_idx, seq_blk_idx, kv_lens_ref,
                      page_indices_ref, num_seqs_ref, layer_ref, order_ref):
            n = num_seqs_ref[0]
            blk = jnp.minimum(seq_blk_idx, _live_blocks(n, s_blk) - 1)
            return _row_at(blk * s_blk + s, order_ref, n), heads_blk_idx, 0

        return pl.BlockSpec((None, num_q_heads_per_blk, head_dim), index_map)

    kv_rows_per_blk = (
        num_combined_kv_heads_per_blk // 2
        if packed
        else num_combined_kv_heads_per_blk
    )
    page_shape = (
        (kv_rows_per_blk, page_size) if head_major
        else (page_size, kv_rows_per_blk)
    )
    # The output's rows are whole lane tiles (the kernel says why); the
    # lanes past ``v_dim`` are dropped below.
    o_lanes = -(-v_dim // 128) * 128
    out_rows = (s_blk, num_q_heads_per_blk, o_lanes)
    scratch_shapes = [
        pltpu.VMEM((2, s_blk * kp, *page_shape, kv_lanes), kv_pages.dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.VMEM((s_blk, num_q_heads_per_blk, head_dim), q.dtype),
        pltpu.VMEM(out_rows, q.dtype),  # a live block's rows on their way
        pltpu.VMEM(out_rows, q.dtype),  # zeros, the dead blocks' rows
        pltpu.SemaphoreType.DMA((2,)),  # live rows', dead rows' writes
    ]
    operands = [q] * s_blk + [kv_pages]
    in_specs = [q_row_spec(s) for s in range(s_blk)] + [
        pl.BlockSpec(memory_space=pl.ANY)]
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
    scalar_prefetches = (
        kv_lens,
        page_indices,
        num_seqs,
        layer.astype(jnp.int32).reshape(1),
        order,
    )
    kernel = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            s_blk=s_blk,
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
            # Written by the kernel's own copies, a row to its own place.
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            grid=grid,
            scratch_shapes=scratch_shapes,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        out_shape=[jax.ShapeDtypeStruct(
            (num_rows + pad, num_q_heads, o_lanes), q.dtype)],
        name=name,
        interpret=interpret,
    )
    out = kernel(*scalar_prefetches, *operands)[0][:num_rows, :, :v_dim]
    if g_whole != g_in:
        out = out.reshape(num_rows, num_kv_heads, g_whole, -1)[
            :, :, :g_in].reshape(num_rows, heads_in, -1)
    return out
