"""Sort-free fused sampling: shared primitives + a Pallas TPU kernel.

The reference stack (``vllm/v1/sample/`` + ``csrc/sampler.cu``) implements
top-k/top-p by *sorting the full vocab per row* every decode step. At the
scored shape (batch 128 x 128k vocab) that is a large sort network plus
several ``[R, V]`` float32 materializations round-tripping HBM per step.
This module replaces the sorts with *rank-space bisection*:

- **top-k** — the k-th largest logit is found by a 31-step MSB-first radix
  walk over a monotonic float->int32 ordinal space, each step one integer
  compare-and-count over the row. Integer counting is order-independent,
  so the result is *exact* (bit-identical to a sorted selection) in any
  tiling and on any backend.
- **top-p** — probabilities never materialize. Working in Gumbel-ready
  weight space ``w = exp(scaled - rowmax)`` (max w == 1.0 exactly), the
  nucleus cutoff is the smallest weight whose strictly-greater mass drops
  below ``top_p * sum(w)``; found by the same 31-step bisection over the
  raw bits of ``w`` (non-negative floats order as integers). All float
  sums go through one fixed halving-tree reduction so every caller —
  the XLA reference path, the interpret-mode kernel, the TPU kernel —
  accumulates in the same order.
- **min-p** — ``w >= min_p`` directly (``max w == 1`` makes the reference
  semantics ``p >= min_p * p_max`` a single elementwise compare).
- **Gumbel noise** — a counter-based Threefry-2x32 stream keyed by the
  row's ``(seed0, seed1)`` pair and the vocab position, so any vocab tile
  of the stream can be (re)generated independently inside the kernel and
  bit-identically on the reference path. ``sample/sampler.py`` defines
  the seeded-request stream in terms of THIS function.

The Pallas kernel (``fused_sample``) grids over row blocks, streams the
logits (and penalty state) from HBM in double-buffered vocab tiles into a
VMEM-resident ``[row_block, V2]`` scratch, then runs the whole epilogue —
penalties, temperature, top-k, top-p/min-p, Gumbel argmax, greedy argmax —
without touching HBM again: one logits read, one ``[R]`` token write (and,
asked for, the ``[R]`` probabilities of the tokens drawn), no sorted vocab,
no probability tensor. A row block at or past the caller's count of active
rows reads nothing and writes zeros. Because every reduction above is
order-independent (or routed through the shared tree), the kernel is
bit-exact against ``sample/sampler.py`` in interpret mode; tests assert
it per sampling-parameter combination.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Masked-out tokens (matches sample/sampler.py's _NEG_INF): large-negative
# but finite, so downstream adds can't produce inf - inf NaNs. Vocab
# padding uses -inf: exp() maps it to exactly 0.0 and argmax ties resolve
# to the first (real) position.
MASK_VALUE = -1e30

# Lanes of Gumbel noise generated per Threefry batch; each 2x32 block
# yields two lanes (out0 -> first half of the tile, out1 -> second half).
_NOISE_TILE = 4096
# Default vocab tile streamed per DMA in the kernel's load phase.
_LOGITS_TILE = 2048


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def padded_vocab(vocab: int) -> int:
    """Compute width V2 >= 128, power of two (``row_sum``'s halving tree
    wants one)."""
    return max(128, next_pow2(vocab))


# Up to this many padded lanes the epilogue computes at a power of two and
# sums by ``row_sum``'s halving tree. Past it a power of two wastes up to
# half the row (151936 would pad to 262144), so ``compute_width`` pads to
# whole noise tiles and the kernel sums by ``tiled_row_sum``.
MAX_POW2_WIDTH = 131072

# Rows of the vocabulary a grid step of the kernel holds in VMEM.
_ROW_BLOCK = 8


def vmem_estimate(row_blk: int, width: int) -> int:
    """Bytes of VMEM the kernel asks for: the epilogue keeps the
    [row_blk, width] f32 scratch plus a handful of same-shaped temporaries
    (weights, ordinals, noise, masks) live; 8x the scratch covers what the
    v5e compiler asked for at R=128, V=128256 (16.06 MiB with penalties at
    row_blk 8, past the 16 MiB default) and at R=512, V=151936 with the
    probability returned (between 20 and 24 MiB against the estimate's 38;
    ``tests/test_tpu_aot_compile.py`` holds both to the compiler)."""
    return 8 * row_blk * width * 4


# The least VMEM a TensorCore has among the chips this kernel can serve at
# all (JAX's own table, ``jax/_src/pallas/mosaic/tpu_info.py``: v5p and
# TPU7x 64 MiB; v5e and v6e 128 MiB; the 16 MiB of v4 and older are under
# the estimate at the old bound of 131072 lanes, 32 MiB, and never ran it).
_LEAST_VMEM_BYTES = 64 << 20

# The widest row the kernel takes: the widest whole number of noise tiles
# whose ``vmem_estimate`` at the default row block fits that VMEM (262144
# lanes). Past it the XLA epilogue is the only path.
MAX_KERNEL_WIDTH = (
    _LEAST_VMEM_BYTES // vmem_estimate(_ROW_BLOCK, _NOISE_TILE) * _NOISE_TILE
)


def compute_width(vocab: int) -> int:
    """The width the epilogue computes at, kernel and XLA path alike:
    ``padded_vocab`` up to ``MAX_POW2_WIDTH``, else the vocabulary rounded
    up to whole noise tiles (151936 -> 155648, not 262144)."""
    v2 = padded_vocab(vocab)
    if v2 <= MAX_POW2_WIDTH:
        return v2
    return -(-vocab // _NOISE_TILE) * _NOISE_TILE


def plain_sum(x: jnp.ndarray) -> jnp.ndarray:
    """[..., N] -> [..., 1] in whatever order XLA picks: one fused pass
    over its operands (``row_sum``'s halving tree writes every level out),
    for the XLA epilogue at widths past ``MAX_POW2_WIDTH``."""
    return jnp.sum(x, axis=-1, keepdims=True)


def noise_tile(v2: int) -> int:
    return min(_NOISE_TILE, v2)


# ---------------------------------------------------------------------------
# Order-independent row reductions (shared by reference path and kernel).
# ---------------------------------------------------------------------------


def row_sum(x: jnp.ndarray) -> jnp.ndarray:
    """[..., N] -> [..., 1] float sum with a FIXED halving-tree order.

    N must be a power of two >= 128. Pairing lane i with lane i + N/2
    down to width 128, then a native 128-lane reduce, makes the
    accumulation order a function of N alone — identical between the
    [R, V2] reference call and the [row_block, V2] kernel call.
    """
    n = x.shape[-1]
    assert n >= 128 and (n & (n - 1)) == 0, n
    while n > 128:
        h = n // 2
        x = x[..., :h] + x[..., h:n]
        n = h
    return jnp.sum(x, axis=-1, keepdims=True)


def tiled_row_sum(x: jnp.ndarray) -> jnp.ndarray:
    """[..., N] -> [..., 1] for N any whole number of noise tiles, in one
    FIXED order: the tiles added lane for lane, left to right, then
    ``row_sum`` over the one tile that is left."""
    n = x.shape[-1]
    assert n % _NOISE_TILE == 0, n
    acc = x[..., :_NOISE_TILE]
    for i in range(1, n // _NOISE_TILE):
        acc = acc + x[..., i * _NOISE_TILE : (i + 1) * _NOISE_TILE]
    return row_sum(acc)


def kernel_sum_fn(width: int):
    """The fixed order of summation the kernel uses at a compute width
    (and the reference it is compared with bit for bit)."""
    return row_sum if width <= MAX_POW2_WIDTH else tiled_row_sum


def row_max(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.max(x, axis=-1, keepdims=True)  # exact in any order


def row_count(mask: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(mask.astype(jnp.int32), axis=-1, keepdims=True)


def row_argmax(x: jnp.ndarray) -> jnp.ndarray:
    """First-occurrence argmax as max + min-index — identical tie
    behavior on every backend and under any tiling."""
    n = x.shape[-1]
    m = row_max(x)
    idx = lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.min(jnp.where(x == m, idx, n), axis=-1, keepdims=True)


def float_ord(x: jnp.ndarray) -> jnp.ndarray:
    """Monotonic float32 -> int32 map: a <= b iff ord(a) <= ord(b)."""
    u = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(u < 0, u ^ jnp.int32(0x7FFFFFFF), u)


# ---------------------------------------------------------------------------
# Rank-space bisection (the sort replacements).
# ---------------------------------------------------------------------------


def kth_largest_ord(ordv: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """Ordinal of the k-th largest element per row ([rows, N] i32,
    k [rows, 1] i32 in [1, N]) by MSB-first radix construction: greedily
    set bits of the answer while at least k elements stay >= it. Exact —
    only integer compares and counts. The sign bit is resolved first
    (whether the k-th value is >= 0 decides the start point; OR-ing can
    only raise a two's-complement value within its sign class)."""
    nonneg = row_count(ordv >= 0) >= k
    r0 = jnp.where(nonneg, jnp.int32(0), jnp.int32(-(2**31)))

    def body(i, r):
        c = r | jnp.left_shift(jnp.int32(1), 30 - i)
        cnt = row_count(ordv >= c)
        return jnp.where(cnt >= k, c, r)

    return lax.fori_loop(0, 31, body, r0)


def top_p_cut(
    wbits: jnp.ndarray, w: jnp.ndarray, target: jnp.ndarray, sum_fn=None
) -> jnp.ndarray:
    """Largest int32 q (per row) whose strictly-greater weight mass
    S_gt(q) = sum(w where bits(w) > q) still reaches ``target``. The
    nucleus keep-set is then ``bits(w) > q``: the smallest set of largest
    weights whose mass >= target. ``wbits`` are the raw bits of the
    non-negative ``w`` (monotonic as integers); masses go through
    ``row_sum`` so the float rounding is tiling-independent."""
    sum_fn = sum_fn or row_sum

    def body(i, r):
        c = r | jnp.left_shift(jnp.int32(1), 30 - i)
        mass = sum_fn(jnp.where(wbits > c, w, 0.0))
        return jnp.where(mass >= target, c, r)

    return lax.fori_loop(0, 31, body, jnp.zeros_like(target, jnp.int32))


# ---------------------------------------------------------------------------
# Shared epilogue blocks ([rows, V2] padded; rows = R or row_block).
# ---------------------------------------------------------------------------


def penalize_block(
    logits: jnp.ndarray,  # [rows, N] f32
    counts: jnp.ndarray,  # [rows, N] i32 output-token counts
    prompt_seen: jnp.ndarray,  # [rows, N] bool
    rep: jnp.ndarray,  # [rows, 1] f32
    freq: jnp.ndarray,  # [rows, 1] f32
    pres: jnp.ndarray,  # [rows, 1] f32
) -> jnp.ndarray:
    """Repetition / frequency / presence penalties (HF/OpenAI semantics,
    reference ``vllm/v1/sample/ops/penalties.py``). Purely elementwise,
    so the kernel's tile-wise application is bit-identical to the
    reference's full-row application."""
    countsf = counts.astype(jnp.float32)
    seen_out = counts > 0
    seen_any = seen_out | prompt_seen
    logits = jnp.where(
        seen_any & (logits > 0),
        logits / rep,
        jnp.where(seen_any, logits * rep, logits),
    )
    logits = logits - freq * countsf
    logits = logits - pres * seen_out.astype(jnp.float32)
    return logits


def mask_top_k_block(
    scaled: jnp.ndarray, top_k: jnp.ndarray, vocab: int
) -> jnp.ndarray:
    """Keep the top-k logits per row ([rows, 1] i32 top_k; 0 disables).
    Ties with the k-th value are kept, matching the sorted reference."""
    k = jnp.where(
        top_k > 0, jnp.minimum(top_k, vocab), jnp.int32(vocab)
    ).astype(jnp.int32)
    ordv = float_ord(scaled)
    kth = kth_largest_ord(ordv, k)
    return jnp.where(ordv >= kth, scaled, jnp.float32(MASK_VALUE))


def mask_top_p_min_p_block(
    scaled: jnp.ndarray, top_p: jnp.ndarray, min_p: jnp.ndarray, sum_fn=None
) -> jnp.ndarray:
    """Nucleus + min-p in weight space (top_p/min_p [rows, 1] f32).
    ``w = exp(scaled - rowmax)`` gives ``max w == 1.0`` exactly, so
    min-p degenerates to ``w >= min_p``. Rows with ``top_p >= 1``
    (disabled) keep every token rather than shaving sub-ulp tail mass."""
    m = row_max(scaled)
    w = jnp.exp(scaled - m)
    target = top_p * (sum_fn or row_sum)(w)
    wbits = lax.bitcast_convert_type(w, jnp.int32)  # w >= 0: bits ordered
    q = top_p_cut(wbits, w, target, sum_fn)
    q = jnp.where(top_p >= 1.0, jnp.int32(-1), q)
    keep = (wbits > q) & (w >= min_p)
    return jnp.where(keep, scaled, jnp.float32(MASK_VALUE))


# ---------------------------------------------------------------------------
# Counter-based Gumbel stream (Threefry-2x32, 20 rounds).
# ---------------------------------------------------------------------------


def _rotl(x: jnp.ndarray, r: int) -> jnp.ndarray:
    return jnp.left_shift(x, r) | jnp.right_shift(x, 32 - r)


def threefry2x32(
    k0: jnp.ndarray, k1: jnp.ndarray, x0: jnp.ndarray, x1: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Standard 20-round Threefry-2x32 in plain uint32 jnp ops (add, xor,
    shift) — the same primitive jax.random builds on, but expressible
    inside a Pallas kernel body and keyed directly by our per-row seeds."""
    rots = ((13, 15, 26, 6), (17, 29, 16, 24))
    ks2 = k0 ^ k1 ^ jnp.uint32(0x1BD11BDA)
    ks = (k0, k1, ks2)
    x0 = x0 + k0
    x1 = x1 + k1
    for i in range(5):
        for r in rots[i % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x1 ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def _bits_to_gumbel(bits: jnp.ndarray) -> jnp.ndarray:
    # Top 23 bits -> uniform [0, 1) via exponent splice; clamp away the
    # single u == 0 pattern (would yield -inf noise).
    mant = jnp.right_shift(bits, jnp.uint32(9)) | jnp.uint32(0x3F800000)
    u = lax.bitcast_convert_type(mant, jnp.float32) - 1.0
    u = jnp.maximum(u, jnp.float32(5.9604645e-08))  # 2**-24
    return -jnp.log(-jnp.log(u))


def gumbel_tile(
    k0: jnp.ndarray,  # [rows, 1] uint32
    k1: jnp.ndarray,  # [rows, 1] uint32
    tile_idx,  # int or i32 scalar: which noise tile
    tile: int,  # lanes per tile (even)
) -> jnp.ndarray:
    """Noise lanes [rows, tile] for vocab positions
    [tile_idx * tile, (tile_idx + 1) * tile). Counter j enumerates the
    tile's Threefry blocks; out0 fills the tile's first half, out1 the
    second — a layout both the kernel (per tile) and the reference
    (concatenated tiles) produce identically."""
    half = tile // 2
    rows = k0.shape[0]
    j = lax.broadcasted_iota(jnp.int32, (rows, half), 1)
    j = (j + jnp.int32(tile_idx) * half).astype(jnp.uint32)
    o0, o1 = threefry2x32(k0, k1, j, jnp.zeros_like(j))
    return jnp.concatenate(
        [_bits_to_gumbel(o0), _bits_to_gumbel(o1)], axis=-1
    )


def counter_gumbel(prng_keys: jnp.ndarray, v2: int) -> jnp.ndarray:
    """Full noise block [rows, v2] for the reference path — the
    concatenation of ``gumbel_tile`` over the tile grid the kernel uses."""
    k0 = prng_keys[:, 0:1].astype(jnp.uint32)
    k1 = prng_keys[:, 1:2].astype(jnp.uint32)
    t = noise_tile(v2)
    return jnp.concatenate(
        [gumbel_tile(k0, k1, i, t) for i in range(v2 // t)], axis=-1
    )


def gumbel_argmax_block(
    scaled: jnp.ndarray,  # [rows, V2] masked/scaled logits
    k0: jnp.ndarray,  # [rows, 1] uint32
    k1: jnp.ndarray,  # [rows, 1] uint32
    tiles_ref=None,  # the kernel's [rows, V2] VMEM scratch, or None
) -> jnp.ndarray:
    """argmax(scaled + noise) streamed over noise tiles: per tile a
    first-occurrence argmax, folded with a strict ``>`` so earlier tiles
    win ties — exactly the first-occurrence argmax of the full row.

    The reference walks the tiles in a Python loop over slices of the
    value. The kernel hands in its scratch: the row block is parked there
    and the same fold runs as ONE loop body over a dynamic lane offset (a
    ref takes one, a value does not), so a row of 38 tiles is traced and
    lowered once, not 38 times over some 120 operations each (seconds of
    every start and every first use of a step program, PERF.md PR 30)."""
    rows, v2 = scaled.shape
    t = noise_tile(v2)

    def fold(best, i, seg):
        best_v, best_i = best
        seg = seg + gumbel_tile(k0, k1, i, t)
        m = row_max(seg)
        a = row_argmax(seg) + i * t
        upd = m > best_v
        return jnp.where(upd, m, best_v), jnp.where(upd, a, best_i)

    best = (
        jnp.full((rows, 1), -jnp.inf, jnp.float32),
        jnp.zeros((rows, 1), jnp.int32),
    )
    if tiles_ref is None:
        for i in range(v2 // t):
            best = fold(best, jnp.int32(i), scaled[:, i * t : (i + 1) * t])
        return best[1]
    tiles_ref[...] = scaled

    def body(i, best):
        lanes = pl.ds(pl.multiple_of(i * t, t), t)
        return fold(best, i, tiles_ref[:, lanes])

    return lax.fori_loop(0, v2 // t, body, best)[1]


def sample_block(
    x: jnp.ndarray,  # [rows, V2] penalized logits (pads -inf)
    temperature: jnp.ndarray,  # [rows, 1] f32; 0 => greedy
    top_k: jnp.ndarray,  # [rows, 1] i32
    top_p: jnp.ndarray,  # [rows, 1] f32
    min_p: jnp.ndarray,  # [rows, 1] f32
    k0: jnp.ndarray,  # [rows, 1] uint32
    k1: jnp.ndarray,  # [rows, 1] uint32
    *,
    vocab: int,
    needs_top_k: bool,
    needs_top_p_min_p: bool,
    return_prob: bool = False,
    sum_fn=None,
    tiles_ref=None,
):
    """The whole sampling epilogue after penalties, shared verbatim by
    the reference path ([R, V2]) and the kernel ([row_block, V2]) — the
    bit-exactness contract lives here. Returns [rows, 1] i32; with
    ``return_prob`` also [rows, 1] f32, the probability of the token drawn
    under the distribution it was drawn from: the softmax of the scaled
    and truncated logits, or for a greedy row the plain softmax of its
    logits (generation by diffusion ranks positions by it). ``tiles_ref``
    is the kernel's scratch, for ``gumbel_argmax_block``."""
    greedy = temperature == 0.0
    greedy_pick = row_argmax(x)
    scaled = x / jnp.where(greedy, 1.0, temperature)
    if needs_top_k:
        scaled = mask_top_k_block(scaled, top_k, vocab)
    if needs_top_p_min_p:
        scaled = mask_top_p_min_p_block(scaled, top_p, min_p, sum_fn)
    random_pick = gumbel_argmax_block(scaled, k0, k1, tiles_ref)
    pick = jnp.where(greedy, greedy_pick, random_pick)
    if not return_prob:
        return pick
    dist = jnp.where(greedy, x, scaled)
    w = jnp.exp(dist - row_max(dist))
    # The one weight at ``pick`` as a masked sum (exact: every other term
    # is 0.0); Mosaic has no gather along lanes.
    lane = lax.broadcasted_iota(jnp.int32, w.shape, w.ndim - 1)
    drawn = jnp.sum(jnp.where(lane == pick, w, 0.0), axis=-1, keepdims=True)
    return pick, drawn / (sum_fn or row_sum)(w)


# ---------------------------------------------------------------------------
# The Pallas kernel.
# ---------------------------------------------------------------------------


def _col_f(params: jnp.ndarray, c: int) -> jnp.ndarray:
    """Column c of a [rows, 128] params block as [rows, 1] (masked-sum
    extract — Mosaic rejects sub-128 lane slices)."""
    idx = lax.broadcasted_iota(jnp.int32, params.shape, 1)
    return jnp.sum(
        jnp.where(idx == c, params, jnp.zeros_like(params)),
        axis=-1,
        keepdims=True,
    )


def _sampler_kernel(
    # Scalar prefetch
    num_active_ref,  # [1] i32 (SMEM): rows before it are read
    # Inputs
    params_f_ref,  # [row_blk, 128] f32: temp, top_p, min_p, rep, freq, pres
    params_i_ref,  # [row_blk, 128] i32: top_k, seed0, seed1
    logits_hbm_ref,  # [R, V] f32, ANY
    counts_hbm_ref,  # [R, V] i32, ANY ([1, 128] dummy w/o penalties)
    pmask_hbm_ref,  # [R, V] i8, ANY ([1, 128] dummy w/o penalties)
    # Outputs
    out_ref,  # [row_blk, 128] i32 (sampled token broadcast across lanes)
    prob_ref,  # [row_blk, 128] f32 (its probability, likewise) or None
    # Scratch
    scaled_scratch,  # [row_blk, V2] f32
    logits_bufs,  # [2, row_blk, LT] f32
    counts_bufs,  # [2, row_blk, LT] i32 or None
    pmask_bufs,  # [2, row_blk, LT] i8 or None
    sems,  # DMA semaphores (3, 2)
    *,
    vocab: int,
    needs_penalties: bool,
    needs_top_k: bool,
    needs_top_p_min_p: bool,
):
    row_blk = out_ref.shape[0]
    v2 = scaled_scratch.shape[-1]
    lt = logits_bufs.shape[-1]
    r0 = pl.program_id(0) * row_blk
    num_tiles = pl.cdiv(vocab, lt)
    num_active = num_active_ref[0]

    def tile_copies(j, slot):
        """Async copies of vocab tile j into buffer ``slot``. Tile widths
        are static (python j), so the partial last tile is just a
        narrower copy."""
        w = min(lt, vocab - j * lt)
        rows = pl.ds(r0, row_blk)
        cols = pl.ds(j * lt, w)
        copies = [
            pltpu.make_async_copy(
                logits_hbm_ref.at[rows, cols],
                logits_bufs.at[slot, :, pl.ds(0, w)],
                sems.at[0, slot],
            )
        ]
        if needs_penalties:
            copies.append(
                pltpu.make_async_copy(
                    counts_hbm_ref.at[rows, cols],
                    counts_bufs.at[slot, :, pl.ds(0, w)],
                    sems.at[1, slot],
                )
            )
            copies.append(
                pltpu.make_async_copy(
                    pmask_hbm_ref.at[rows, cols],
                    pmask_bufs.at[slot, :, pl.ds(0, w)],
                    sems.at[2, slot],
                )
            )
        return copies

    @pl.when(r0 >= num_active)
    def _nobody_reads_these_rows():
        out_ref[...] = jnp.zeros_like(out_ref)
        if prob_ref is not None:
            prob_ref[...] = jnp.zeros_like(prob_ref)

    @pl.when(r0 < num_active)
    def _sample_the_block():
        # Phase 1: stream vocab tiles HBM -> VMEM (double-buffered), apply
        # penalties tile-wise, land the penalized logits in the row-resident
        # scratch. Static tile loop: widths and scratch offsets are literals.
        for c in tile_copies(0, 0):
            c.start()
        for j in range(num_tiles):
            if j + 1 < num_tiles:
                for c in tile_copies(j + 1, (j + 1) % 2):
                    c.start()
            for c in tile_copies(j, j % 2):
                c.wait()
            w = min(lt, vocab - j * lt)
            slot = j % 2
            tile = logits_bufs[slot, :, pl.ds(0, w)]
            if needs_penalties:
                tile = penalize_block(
                    tile,
                    counts_bufs[slot, :, pl.ds(0, w)],
                    pmask_bufs[slot, :, pl.ds(0, w)] != 0,
                    _col_f(params_f_ref[...], 3),
                    _col_f(params_f_ref[...], 4),
                    _col_f(params_f_ref[...], 5),
                )
            scaled_scratch[:, j * lt : j * lt + w] = tile
        if v2 > vocab:  # pad lanes: -inf -> zero weight, never wins argmax
            scaled_scratch[:, vocab:v2] = jnp.full(
                (row_blk, v2 - vocab), -jnp.inf, jnp.float32
            )

        # Phase 2: the shared epilogue, entirely in VMEM.
        pf = params_f_ref[...]
        pi = params_i_ref[...]
        seed0 = lax.bitcast_convert_type(
            _col_f(pi, 1).astype(jnp.int32), jnp.uint32
        )
        seed1 = lax.bitcast_convert_type(
            _col_f(pi, 2).astype(jnp.int32), jnp.uint32
        )
        sampled = sample_block(
            scaled_scratch[...],
            _col_f(pf, 0),
            _col_f(pi, 0).astype(jnp.int32),
            _col_f(pf, 1),
            _col_f(pf, 2),
            seed0,
            seed1,
            vocab=vocab,
            needs_top_k=needs_top_k,
            needs_top_p_min_p=needs_top_p_min_p,
            return_prob=prob_ref is not None,
            sum_fn=kernel_sum_fn(v2),
            tiles_ref=scaled_scratch,
        )
        # The block that straddles the count: its rows past it are zeros
        # too, as a skipped block's are.
        row = r0 + lax.broadcasted_iota(jnp.int32, (row_blk, 128), 0)
        live = row < num_active
        if prob_ref is not None:
            sampled, prob = sampled
            prob_ref[...] = jnp.where(live, prob, 0.0)
        out_ref[...] = jnp.where(live, sampled, 0)


@functools.partial(
    jax.jit,
    static_argnames=[
        "needs_penalties", "needs_top_k", "needs_top_p_min_p", "return_prob",
        "row_block", "logits_tile", "vmem_limit_bytes", "interpret",
    ],
)
def fused_sample(
    logits: jax.Array,  # [R, V] f32
    params_f: jax.Array,  # [R, 128] f32: temp, top_p, min_p, rep, freq, pres
    params_i: jax.Array,  # [R, 128] i32: top_k, seed0, seed1 (bitcast)
    counts: jax.Array,  # [R, V] i32, or [1, 128] dummy
    prompt_mask: jax.Array,  # [R, V] i8, or [1, 128] dummy
    num_active: jax.Array | None = None,  # i32 scalar; None: every row
    *,
    needs_penalties: bool = False,
    needs_top_k: bool = True,
    needs_top_p_min_p: bool = True,
    return_prob: bool = False,
    row_block: int = _ROW_BLOCK,
    logits_tile: int = _LOGITS_TILE,
    vmem_limit_bytes: int | None = None,
    interpret: bool = False,
):
    """Fused device sampling epilogue: [R] i32 sampled tokens from raw
    lm_head logits in a single HBM read; with ``return_prob`` also [R] f32,
    each drawn token's probability (``sample_block``). Rows at or past
    ``num_active`` are not sampled and come back as zeros; a row block
    wholly past it moves no byte. See the module docstring; use
    ``sample/sampler.py:dispatch_sample`` rather than calling this
    directly (eligibility and the params packing live there)."""
    num_rows, vocab = logits.shape
    if params_f.shape != (num_rows, 128) or params_i.shape != (num_rows, 128):
        raise ValueError(
            f"params must be [{num_rows}, 128], got "
            f"{params_f.shape=} {params_i.shape=}"
        )
    if needs_penalties and counts.shape != (num_rows, vocab):
        raise ValueError(f"{counts.shape=} != {(num_rows, vocab)}")
    v2 = compute_width(vocab)
    # row_block: a multiple of 8, or at least num_rows — Mosaic tiles the
    # [R, 128] parameter blocks (8, 128).
    row_blk = max(1, min(row_block, num_rows))
    lt = min(logits_tile, v2)
    if vmem_limit_bytes is None:
        vmem_limit_bytes = max(16 << 20, vmem_estimate(row_blk, v2))

    # Pad rows up to the block grid; pad rows sample into the void and
    # are sliced off below.
    r2 = pl.cdiv(num_rows, row_blk) * row_blk
    if r2 != num_rows:
        pad = r2 - num_rows
        logits = jnp.pad(logits, ((0, pad), (0, 0)))
        params_f = jnp.pad(params_f, ((0, pad), (0, 0)))
        params_i = jnp.pad(params_i, ((0, pad), (0, 0)))
        if needs_penalties:
            counts = jnp.pad(counts, ((0, pad), (0, 0)))
            prompt_mask = jnp.pad(prompt_mask, ((0, pad), (0, 0)))
    if num_active is None:
        num_active = num_rows
    num_active = jnp.asarray(num_active, jnp.int32).reshape(1)

    def params_block(i, num_active_ref):
        # A block nobody reads asks for the last live block's parameters
        # again, which the pipeline then does not fetch.
        last = jnp.maximum(pl.cdiv(num_active_ref[0], row_blk) - 1, 0)
        return jnp.minimum(i, last), 0

    params_spec = pl.BlockSpec((row_blk, 128), params_block)
    out_spec = pl.BlockSpec((row_blk, 128), lambda i, _: (i, 0))
    scratch_shapes = [
        pltpu.VMEM((row_blk, v2), jnp.float32),
        pltpu.VMEM((2, row_blk, lt), jnp.float32),
        pltpu.VMEM((2, row_blk, lt), jnp.int32) if needs_penalties else None,
        pltpu.VMEM((2, row_blk, lt), jnp.int8) if needs_penalties else None,
        pltpu.SemaphoreType.DMA((3, 2)),
    ]
    scratch_shapes = [s for s in scratch_shapes if s is not None]
    out_shape = [jax.ShapeDtypeStruct((r2, 128), jnp.int32)]
    if return_prob:
        out_shape.append(jax.ShapeDtypeStruct((r2, 128), jnp.float32))

    def kernel(*refs):
        na, pf, pi, lg, ct, pm, out = refs[:7]
        rest = list(refs[7:])
        prob = rest.pop(0) if return_prob else None
        if needs_penalties:
            scaled, lbufs, cbufs, pbufs, sem = rest
        else:
            scaled, lbufs, sem = rest
            cbufs = pbufs = None
        _sampler_kernel(
            na, pf, pi, lg, ct, pm, out, prob, scaled, lbufs, cbufs, pbufs,
            sem,
            vocab=vocab,
            needs_penalties=needs_penalties,
            needs_top_k=needs_top_k,
            needs_top_p_min_p=needs_top_p_min_p,
        )

    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                params_spec,
                params_spec,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[out_spec] * len(out_shape),
            grid=(r2 // row_blk,),
            scratch_shapes=scratch_shapes,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        out_shape=out_shape,
        name="fused_sampler_kernel",
        interpret=interpret,
    )(num_active, params_f, params_i, logits, counts, prompt_mask)
    if return_prob:
        return outs[0][:num_rows, 0], outs[1][:num_rows, 0]
    return outs[0][:num_rows, 0]
