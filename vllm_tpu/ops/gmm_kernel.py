"""In-repo Pallas TPU kernel: the grouped matmul of a mixture of experts.

``out[rows of group g] = lhs[rows of group g] @ rhs[g]`` for rows sorted by
group. Derived from the Apache-2.0 ``jax.experimental.pallas.ops.tpu.
megablox.gmm`` (JAX Authors, 2024) and the same call as
``layers/moe.py::_grouped_moe`` made of it: bf16 (or f32) operands, float32
accumulation and output, a row tile of 128, rows past the groups never
visited (their output is whatever the buffer held), empty groups never
read. What differs is how ``rhs`` arrives:

- **The weights' copy never waits for a visit that needs no copy.** The
  kernel walks (column tile, visit) pairs, a visit one (group, row tile)
  pair. A group whose rows straddle two row tiles is visited twice in a
  row with the same matrix. megablox hands ``rhs`` to Pallas's pipeline,
  which looks one grid step ahead: during the first of the two visits the
  next step needs the same block, so NO copy is in flight, and the next
  group's starts only with the second. Here ``rhs`` stays in HBM and the
  kernel moves it (as ``rpa_kernel.py`` moves its pages): the DISTINCT
  blocks of the call form one flat chain over the column tiles, a block's
  first visit starts the copy of the block ``slots - 1`` places on and
  waits for its own, a resident visit touches no semaphore. Only the
  call's first block is exposed.
- A block holds the depth whole (``tiles_k`` = 1 in megablox's terms): no
  second fetch of a matrix that a straddle revisits. A visit's product is
  summed over depth chunks of ``tk`` in order, in megablox's own
  statements, so the float32 sums are megablox's at depth tiles of ``tk``
  (bit for bit in interpret mode).
- Empty groups are squeezed out on the way in (``visit_metadata``): the
  chain is over the groups that hold rows, which is what lets a first
  visit name the block ``slots - 1`` places on.

``count_visits`` counts a call's visits on the host with the kernel's own
arithmetic (``vllm:moe_gmm_visits_total``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_tpu.ops.rpa_kernel import _DEFAULT_SCOPED_VMEM_BYTES

ROW_TILE = 128


def takes(k: int, n: int, tk: int, tn: int) -> bool:
    """Whether the kernel takes an expert's ``[k, n]`` matrix in depth
    chunks of ``tk`` and column tiles of ``tn``: whole (8, 128) tiles of
    every operand."""
    return tk % 128 == 0 and k % tk == 0 and tn % 128 == 0 and n % tn == 0


def visit_metadata(group_sizes: jnp.ndarray, m: int):
    """The walk of one column tile, from ``group_sizes [G]`` over ``m``
    sorted rows. A BLOCK is a group that holds rows, in order; a VISIT is a
    (block, row tile) pair, a block's visits adjacent.

    Returns ``(block_offsets [G + 1], block_group [G], visit_block [V],
    visit_tile [V], counts [2])``: the rows ``[block_offsets[b],
    block_offsets[b + 1])`` are block ``b``'s and ``block_group[b]`` its
    group; visit ``v`` is of block ``visit_block[v]`` on row tile
    ``visit_tile[v]``; ``counts`` = (blocks, visits). ``V = m // tm + G -
    1`` is the most there can be; entries past the counts are padding."""
    g, tm = group_sizes.shape[0], ROW_TILE
    tiles_m = m // tm
    v = tiles_m + g - 1
    hit = group_sizes > 0
    num_blocks = jnp.sum(hit, dtype=jnp.int32)
    block_group = jnp.nonzero(hit, size=g, fill_value=0)[0].astype(jnp.int32)
    sizes = jnp.where(jnp.arange(g) < num_blocks, group_sizes[block_group], 0)
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    starts = ends - sizes
    tiles = jnp.where(sizes > 0, (ends + tm - 1) // tm - starts // tm, 0)
    first_visit = jnp.cumsum(tiles, dtype=jnp.int32) - tiles
    visit_block = jnp.repeat(
        jnp.arange(g, dtype=jnp.int32), tiles, total_repeat_length=v)
    visit_tile = (starts // tm - first_visit)[visit_block] + jnp.arange(
        v, dtype=jnp.int32)
    visit_tile = jnp.clip(visit_tile, 0, tiles_m - 1)
    block_offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    counts = jnp.stack([num_blocks, jnp.sum(tiles, dtype=jnp.int32)])
    return block_offsets, block_group, visit_block, visit_tile, counts


def count_visits(group_sizes, column_tiles: int):
    """A call's visits by what they do to the weights, on the host:
    ``(first, resident)`` for ``group_sizes [..., G]`` (any leading axes,
    a call each, summed). A first visit waits for its block and starts a
    copy; a resident visit runs on the matrix its block's first brought."""
    sizes = np.asarray(group_sizes, np.int64)
    ends = np.cumsum(sizes, axis=-1)
    starts = ends - sizes
    visits = np.where(
        sizes > 0, -(-ends // ROW_TILE) - starts // ROW_TILE, 0).sum()
    first = np.count_nonzero(sizes)
    return int(first) * column_tiles, int(visits - first) * column_tiles


def _gmm_kernel(
    # Scalar prefetch.
    block_offsets_ref,  # [G + 1]
    block_group_ref,  # [G]
    visit_block_ref,  # [V]
    visit_tile_ref,  # [V]
    counts_ref,  # [2]: blocks, visits
    # Inputs.
    lhs_ref,  # [tm, k]
    rhs_hbm_ref,  # [G, k, n], in HBM
    # Output.
    out_ref,  # [tm, tn]
    # Scratch.
    bufs,  # [slots, k, tn]
    sems,  # DMA [slots]
    acc_ref,  # [tm, tn] f32, or None where one chunk is the depth
    *,
    tiles_n: int,
    tk: int,
):
    slots, k, tn = bufs.shape
    tm = lhs_ref.shape[0]
    n_i, v = pl.program_id(0), pl.program_id(1)
    num_blocks = counts_ref[0]
    block = visit_block_ref[v]
    # Place in the flat chain of distinct blocks, over the column tiles.
    place = n_i * num_blocks + block

    def copy_of(place_n, place_block, slot):
        src = rhs_hbm_ref.at[block_group_ref[place_block]]
        if tiles_n > 1:
            src = src.at[:, pl.ds(pl.multiple_of(place_n * tn, tn), tn)]
        return pltpu.make_async_copy(src, bufs.at[slot], sems.at[slot])

    def start_copy(ahead, from_n, from_block):
        """Start the copy of the block ``ahead`` places past (from_n,
        from_block), if the chain reaches that far."""
        to_n, to_block = from_n, from_block + ahead
        for _ in range(ahead):
            wrap = to_block >= num_blocks
            to_block = jnp.where(wrap, to_block - num_blocks, to_block)
            to_n = to_n + wrap.astype(jnp.int32)

        @pl.when(to_n < tiles_n)
        def _():
            to_place = to_n * num_blocks + to_block
            copy_of(to_n, to_block, lax.rem(to_place, slots)).start()

    slot = lax.rem(place, slots)
    is_first = jnp.logical_or(
        v == 0, visit_block_ref[jnp.maximum(v - 1, 0)] != block)

    @pl.when(is_first)
    def fetch():
        @pl.when(place == 0)
        def prime():
            for ahead in range(slots - 1):
                start_copy(ahead, 0, 0)

        start_copy(slots - 1, n_i, block)
        copy_of(0, 0, slot).wait()

    # bf16 x bf16 on the MXU as it is; anything else in float32 (megablox's
    # rule).
    both_bf16 = lhs_ref.dtype == bufs.dtype == jnp.bfloat16
    dtype = jnp.bfloat16 if both_bf16 else jnp.float32

    def product(depth):
        return jnp.dot(
            lhs_ref[:, depth:depth + tk].astype(dtype),
            bufs[slot, depth:depth + tk].astype(dtype),
            preferred_element_type=jnp.float32)

    if acc_ref is None:
        acc = product(0)
    else:
        # megablox's own statements a depth tile, so that the compiler
        # makes the same float32 sums of them.
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for depth in range(0, k, tk):
            acc_ref[...] += product(depth)
        acc = acc_ref[...]
    rows = visit_tile_ref[v] * tm + lax.broadcasted_iota(
        jnp.int32, (tm, tn), 0)
    mine = jnp.logical_and(
        rows >= block_offsets_ref[block], rows < block_offsets_ref[block + 1])
    out_ref[...] = jnp.where(mine, acc, out_ref[...])


def slots_for(block_bytes: int) -> int:
    """Slots of the weights in VMEM. Two keep one copy in flight behind
    the block at work, which is enough while a block's copy outlasts its
    visits' matmuls; a group that straddles row tiles can take longer than
    its copy, and a third slot lets the copies run on through it: 1-2% of
    a call at blocks of 2-4 MB, nothing at 7-8 MB (PERF.md PR 54)."""
    return 3 if block_bytes <= 4 << 20 else 2


def vmem_bytes(k: int, tn: int, slots: int, itemsize: int = 2) -> int:
    """What a call holds in VMEM: the weights' slots, lhs and out double-
    buffered by the pipeline, a visit's product."""
    tm = ROW_TILE
    return (slots * k * tn * itemsize + 2 * tm * k * itemsize
            + 3 * tm * tn * 4)


@functools.partial(
    jax.jit, static_argnames=("tk", "tn", "slots", "interpret"))
def gmm(
    lhs: jnp.ndarray,  # [M, K], rows sorted by group, M a multiple of 128
    rhs: jnp.ndarray,  # [G, K, N]
    group_sizes: jnp.ndarray,  # [G] i32, sum <= M
    *,
    tk: int | None = None,  # depth chunk of the sums, divides K; None = K
    tn: int,  # column tile, divides N
    slots: int | None = None,  # of the weights in VMEM; None = slots_for
    interpret: bool = False,
) -> jnp.ndarray:
    """``out[M, N]`` f32 with ``out[rows of g] = lhs[rows of g] @ rhs[g]``;
    rows past ``sum(group_sizes)`` are not written."""
    m, k = lhs.shape
    g, _, n = rhs.shape
    tm = ROW_TILE
    tk = k if tk is None else tk
    if m % tm or not takes(k, n, tk, tn) or rhs.shape[1] != k:
        raise ValueError(
            f"gmm takes whole tiles: lhs {lhs.shape}, rhs {rhs.shape}, "
            f"depth chunk {tk}, column tile {tn}")
    if group_sizes.dtype != jnp.int32:
        raise ValueError(f"group_sizes must be int32: {group_sizes.dtype}")
    tiles_n = n // tn
    if slots is None:
        slots = slots_for(k * tn * rhs.dtype.itemsize)
    metadata = visit_metadata(group_sizes, m)
    num_visits = metadata[-1][1]

    # Mosaic's own temporaries on top of what the call holds.
    need = vmem_bytes(k, tn, slots, rhs.dtype.itemsize) + (2 << 20)
    vmem_limit_bytes = need if need > _DEFAULT_SCOPED_VMEM_BYTES else None

    def lhs_index(n_i, v, offsets, group, visit_block, visit_tile, counts):
        return visit_tile[v], 0

    def out_index(n_i, v, offsets, group, visit_block, visit_tile, counts):
        return visit_tile[v], n_i

    return pl.pallas_call(
        functools.partial(_gmm_kernel, tiles_n=tiles_n, tk=tk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(metadata),
            in_specs=[
                pl.BlockSpec((tm, k), lhs_index),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            grid=(tiles_n, num_visits),
            scratch_shapes=[
                pltpu.VMEM((slots, k, tn), rhs.dtype),
                pltpu.SemaphoreType.DMA((slots,)),
                # The sums' accumulator where the depth goes in chunks.
                None if tk == k else pltpu.VMEM((tm, tn), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # The chain of copies crosses the column tiles in order.
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(lhs.size * lhs.dtype.itemsize * tiles_n
                            + min(g, m) * k * n * rhs.dtype.itemsize
                            + m * n * 4),
        ),
        name="gmm",
        interpret=interpret,
    )(*metadata, lhs, rhs)
