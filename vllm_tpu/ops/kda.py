"""Delta-rule linear attention with a decay per key CHANNEL, over the
ragged token batch and per-request state slots.

Reference analog: the FLA ``chunk_kda`` / ``fused_recurrent_kda`` kernels
behind ``vllm/model_executor/layers/kda.py``. A head keeps a float32
matrix ``S [dk, dv]`` a request, zero at the request's start; a token
with log-decays ``a_t [dk] <= 0`` and ``beta_t`` in (0, 1) does

    S'  = diag(exp(a_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

(``ops/gdn.py`` is the same rule with one decay a head, as a scan a
token). Two forms here, both equal to that recurrence:

- ``kda_decode``: one token a row, for the rows named live. A Pallas
  kernel whose grid walks the rows; a row's ``[H, dk, dv]`` state (2 MiB
  at 32 heads of 128) is read from its slot, updated and written back in
  place; the steps past the live rows map to the block already held, so
  they move nothing. Off the TPU (and not under the interpreter) the same
  update as einsums over every row.
- ``kda_chunks``: rows with more than one token, in chunks of ``CHUNK``
  tokens inside a row's segment, ``n_chunks`` places at a time in rounds
  of a ``while_loop``, so that no mix of rows overflows a static bound;
  one round is the rule. On the TPU one Pallas kernel a round
  (``kda_chunk_kernel``): a step of its grid is one chunk of one row, and
  the places past the step's last chunk do nothing and move nothing, so a
  step pays for the chunks it carries. A chunk's tokens lie together in
  the ragged batch and are copied in, and its outputs out, by DMAs of a
  computed start; the row's ``[H, dk, dv]`` state is read from its slot
  at the row's first chunk, stays in VMEM to its last and is written back
  in place; the pairwise exponents, ``A_kk``, ``A_qk``, the solve and the
  state matmuls of a chunk never leave VMEM. Float32 throughout, a matmul
  as the three bfloat16 passes of ``Precision.HIGH``. Off the TPU (and as
  the tests' oracle) the XLA form: what does not depend on the incoming
  state for all of a round's places at once, then a scan over them with
  four small matmuls a chunk, states and tokens gathered and scattered.

The short convolution. Before the rule, q, k and v each pass a depthwise
causal convolution of K taps whose last K - 1 inputs are per-request state
too, a slot of ``[K - 1, G, dk]`` in the served dtype (G the heads of q,
k and v together). ``kda_short_conv``: on the TPU ONE Pallas kernel a
layer (``kda_conv_kernel``) for both kinds of step, which reads the tail of
a row that begins in the step from its slot, writes the tail of a row that
ends in it back in place, and touches no other slot; off the TPU (and as
the tests' oracle) ``read_slot_rows`` -> ``ragged_short_conv`` ->
``write_slot_rows``, one-hot matmuls over the whole layer of slots and a
gather a token and a tap.

Exponents. Every ``exp`` here is of a difference of cumulated decays
``b_i - b_j`` with ``i >= j``, or of ``b_i`` itself, so of a number
``<= 0``: a chunk is cut in blocks of ``SUB`` tokens, a pair inside one
block takes its exact pairwise exponent, and a pair across blocks is
split at the start of the row's block (both halves ``<= 0``). Nothing is
ever divided by a cumulated decay, so float32 holds it at any decay.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_tpu.ops.gdn import l2norm  # noqa: F401  (the mixer's q/k norm)

CHUNK = 64
SUB = 16
_HI = lax.Precision.HIGH  # three bf16 passes: float32 to some 2**-21


def ragged_short_conv(
    x: jnp.ndarray,  # [T, C] this step's inputs
    tail: jnp.ndarray,  # [R, K-1, C] the K-1 inputs before each row's chunk
    weight: jnp.ndarray,  # [K, C]; tap K-1 is the current token
    token_req_idx: jnp.ndarray,  # [T]
    query_start_loc: jnp.ndarray,  # [R+1]
    *,
    decode_only: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal convolution over each row's segment, continued
    from the row's cached tail. Returns ``(y [T, C] f32, new_tail)``,
    the tail in ``tail``'s dtype and layout (channels on the lanes).
    ``decode_only`` (static): token ``i`` is row ``i``'s one token, so
    nothing is gathered."""
    t = x.shape[0]
    r, km1, _ = tail.shape
    if decode_only:
        w = weight.astype(jnp.float32)
        y = x.astype(jnp.float32) * w[km1] + jnp.sum(
            tail.astype(x.dtype).astype(jnp.float32) * w[:km1], axis=1)
        return y, jnp.concatenate(
            [tail[:, 1:], x[:, None].astype(tail.dtype)], axis=1)
    ts = jnp.arange(t, dtype=jnp.int32)
    req = jnp.clip(token_req_idx, 0, r - 1)
    pos = ts - query_start_loc[req]
    w = weight.astype(jnp.float32)
    y = x.astype(jnp.float32) * w[km1]
    for s in range(1, km1 + 1):
        back = jnp.where(
            (pos >= s)[:, None],
            x[jnp.clip(ts - s, 0)],
            tail[req, jnp.clip(km1 - s + pos, 0, km1 - 1)].astype(x.dtype),
        )
        y = y + back.astype(jnp.float32) * w[km1 - s]
    qlen = query_start_loc[1:] - query_start_loc[:-1]  # [R]
    rows = []
    for j in range(km1, 0, -1):  # j inputs back from the row's next token
        from_x = x[jnp.clip(query_start_loc[1:] - j, 0, t - 1)]
        from_tail = tail[
            jnp.arange(r), jnp.clip(km1 - j + qlen, 0, km1 - 1)
        ].astype(x.dtype)
        rows.append(jnp.where((qlen >= j)[:, None], from_x, from_tail))
    return y, jnp.stack(rows, axis=1).astype(tail.dtype)


def read_slot_rows(
    stack: jnp.ndarray,  # [L, S, W]: a row of W numbers a slot a layer
    layer: jnp.ndarray,  # scalar i32
    slots: jnp.ndarray,  # [R] i32
) -> jnp.ndarray:
    """``stack[layer, slots]`` as a one-hot matmul: exact (one 1 a row,
    float32 sums), and one matmul where XLA's gather of R wide rows is a
    pass a row."""
    rows = lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)
    pick = slots[:, None] == jnp.arange(rows.shape[0], dtype=slots.dtype)
    return jnp.dot(
        pick.astype(rows.dtype), rows, precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(rows.dtype)


def write_slot_rows(
    stack: jnp.ndarray,  # [L, S, W]
    layer: jnp.ndarray,  # scalar i32
    slots: jnp.ndarray,  # [R] i32, distinct over the live rows
    rows: jnp.ndarray,  # [R, W]
    n_live: jnp.ndarray,  # scalar i32: rows [0, n_live) are written
) -> jnp.ndarray:
    """``stack.at[layer, slots[:n_live]].set(rows[:n_live])`` as a one-hot
    matmul and a select over the layer's slots: XLA's scatter is a loop
    of R iterations on the TPU, 1.4 ms a layer at 192 rows of 36,864.
    A number that is not finite is stored as 0, so that one request's
    overflow cannot reach another's row through a product with 0."""
    old = lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)
    live = jnp.arange(slots.shape[0]) < n_live
    pick = (
        slots[None, :] == jnp.arange(old.shape[0], dtype=slots.dtype)[:, None]
    ) & live[None, :]  # [S, R]
    rows = jnp.where(jnp.isfinite(rows), rows, 0).astype(old.dtype)
    put = jnp.dot(
        pick.astype(old.dtype), rows, precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(old.dtype)
    new = jnp.where(jnp.any(pick, axis=1)[:, None], put, old)
    return lax.dynamic_update_index_in_dim(stack, new, layer, 0)


def _pallas_form(state: jnp.ndarray | None = None) -> tuple[bool, bool]:
    """``(kernel, interpret)``: the forms run as Pallas kernels on a TPU
    (interpreted where ``VLLM_TPU_PALLAS_INTERPRET`` asks off it), and as
    XLA otherwise or under ``VLLM_TPU_DISABLE_PALLAS``. ``state``: the
    state stack of the two state kernels, which compute in float32 and
    take no other; the convolution's stack is in the served dtype and
    passes none."""
    from vllm_tpu import envs

    on_tpu = jax.default_backend() == "tpu"
    interpret = bool(envs.VLLM_TPU_PALLAS_INTERPRET) and not on_tpu
    kernel = (on_tpu or interpret) and not envs.VLLM_TPU_DISABLE_PALLAS
    if state is not None:
        kernel = kernel and state.dtype == jnp.float32
    return kernel, interpret


# ----------------------------------------------------------------------
# The short convolution over the live rows' slots
# ----------------------------------------------------------------------

CONV_TILE = 32  # tokens a step of the kernel's grid


def _conv_kernel(
    layer_ref, total_ref, qsl_ref, slots_ref, fresh_ref, row_ref,  # prefetch
    *refs,
    part: int,
    bias: bool = False,
):
    # The inputs [T, g, dk], their heads side by side making G; then:
    (*xs_hbm,
     w_ref,  # [K, G, dk] f32; with ``bias`` a row more, the bias
     stack_hbm,  # [Lk, S + 1, K - 1, G, dk]; a slot is read before it is written
     y_ref,  # [tile, G, dk] f32: this step's tokens
     stack_out_hbm,  # the same stack
     win,  # [2, K - 1 + tile + (K - 1) * tile, G, dk]: the K - 1 tokens
     # before the step's, the step's, then the tails of the rows that begin in it
     out,  # [2, (K - 1) * tile, G, dk]: the tails of the rows that end in it
     x_sem, in_sem, out_sem) = refs
    tile, g = y_ref.shape[:2]
    km1 = stack_hbm.shape[2]
    first_tail = km1 + tile
    i = pl.program_id(0)
    total = total_ref[0]
    n_tiles = (total + tile - 1) // tile
    layer = layer_ref[0]

    def rows_of(step):
        """The step's first token, and the rows of its first and last."""
        t0 = step * tile
        return t0, row_ref[t0], row_ref[jnp.minimum(t0 + tile, total) - 1]

    def for_rows(step, act, wants):
        """``act(row, its place among the step's rows)`` for the step's
        rows that ``wants(row, t0)``."""
        t0, lo, hi = rows_of(step)

        def one(k, carry):
            r = jnp.minimum(lo + k, hi)

            @pl.when((lo + k <= hi) & wants(r, t0))
            def _():
                act(r, k)

            return carry

        lax.fori_loop(0, tile, one, 0)

    # A row's tail is wanted where a token of its first K - 1 lies in the
    # step, and written where its last token does (a live row holds a
    # token: one without would keep its tail).
    begins = lambda r, t0: qsl_ref[r] + km1 > t0
    ends = lambda r, t0: (qsl_ref[r + 1] <= t0 + tile) & (qsl_ref[r + 1] > qsl_ref[r])

    def tail_in(r, k, buf):
        return pltpu.make_async_copy(
            stack_hbm.at[layer, slots_ref[r]],
            win.at[buf, pl.ds(first_tail + km1 * k, km1)], in_sem.at[buf])

    def tail_out(r, k, buf):
        return pltpu.make_async_copy(
            out.at[buf, pl.ds(km1 * k, km1)],
            stack_out_hbm.at[layer, slots_ref[r]], out_sem.at[buf])

    def tokens_in(step, buf):
        copies, g0 = [], 0
        for n, x_hbm in enumerate(xs_hbm):
            copies.append(pltpu.make_async_copy(
                x_hbm.at[pl.ds(step * tile, tile)],
                win.at[buf, pl.ds(km1, tile), pl.ds(g0, x_hbm.shape[1])],
                x_sem.at[buf, n]))
            g0 += x_hbm.shape[1]
        return copies

    def fetch(step, buf):
        for cp in tokens_in(step, buf):
            cp.start()

        def tail(r, k):
            @pl.when(fresh_ref[r] != 0)
            def _():  # the request starts: zero, without a read
                win[buf, pl.ds(first_tail + km1 * k, km1)] = jnp.zeros(
                    (km1,) + win.shape[2:], win.dtype)

            @pl.when(fresh_ref[r] == 0)
            def _():
                tail_in(r, k, buf).start()

        for_rows(step, tail, begins)

    @pl.when(i < n_tiles)
    def _():
        cur = i % 2

        @pl.when(i == 0)
        def _():
            fetch(0, 0)

        @pl.when(i + 1 < n_tiles)
        def _():
            fetch(i + 1, 1 - cur)

        for cp in tokens_in(i, cur):
            cp.wait()
        for_rows(i, lambda r, k: tail_in(r, k, cur).wait(),
                 lambda r, t0: begins(r, t0) & (fresh_ref[r] == 0))

        t0, lo, _ = rows_of(i)
        for g0 in range(0, g, part):
            gs = slice(g0, min(g0 + part, g))
            w = [w_ref[j, gs, :] for j in range(km1 + 1)]
            b = w_ref[km1 + 1, gs, :] if bias else None

            def token(ii, carry, gs=gs, w=w, b=b):
                t = t0 + ii

                @pl.when(t < total)
                def _():
                    r = row_ref[t]
                    pos = t - qsl_ref[r]
                    tail = first_tail + km1 * (r - lo)
                    here = win[cur, km1 + ii, gs, :]
                    y = here.astype(jnp.float32) * w[km1]
                    back = []  # the input s tokens back, s = 1 ... K - 1
                    for s in range(1, km1 + 1):
                        back.append(win[
                            cur,
                            jnp.where(pos >= s, km1 + ii - s, tail + km1 - s + pos),
                            gs, :])
                        y = y + back[-1].astype(jnp.float32) * w[km1 - s]
                    y_ref[ii, gs, :] = y if b is None else y + b

                    @pl.when(t + 1 == qsl_ref[r + 1])
                    def _():  # the row's last token: its K - 1 last inputs
                        place = km1 * (r - lo)
                        for j, v in enumerate(back[-2::-1] + [here]):
                            out[cur, place + j, gs, :] = v

                return carry

            lax.fori_loop(0, tile, token, 0)

        # The next step's tokens follow these.
        win[1 - cur, pl.ds(0, km1)] = win[cur, pl.ds(tile, km1)]

        @pl.when(i > 0)
        def _():
            for_rows(i - 1, lambda r, k: tail_out(r, k, 1 - cur).wait(), ends)

        for_rows(i, lambda r, k: tail_out(r, k, cur).start(), ends)

        @pl.when(i == n_tiles - 1)
        def _():
            for_rows(i, lambda r, k: tail_out(r, k, cur).wait(), ends)


def _live_tokens(query_start_loc, n_live):
    """``query_start_loc[n_live]``, the step's live tokens, without a gather."""
    at = jnp.arange(query_start_loc.shape[0]) == n_live
    return jnp.sum(jnp.where(at, query_start_loc, 0))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _conv_pallas(xs, stack, weights, layer, slots, fresh, query_start_loc,
                 n_live, bias=None, *, tile: int, interpret: bool = False):
    t = xs[0].shape[0]
    km1, g, dk = stack.shape[2:]
    r = slots.shape[0]
    i32 = lambda v: v.astype(jnp.int32)
    # A step of the grid is ``tile`` whole tokens, a token [heads, dk].
    tp = -(-t // tile) * tile
    xs = [jnp.pad(x, ((0, tp - t), (0, 0))).reshape(tp, -1, dk) for x in xs]
    w = jnp.concatenate(weights, axis=-1).astype(jnp.float32)
    if bias is not None:  # the taps' last row
        w = jnp.concatenate([w, bias.astype(jnp.float32)[None]], axis=0)
    qsl = i32(query_start_loc)
    total = _live_tokens(qsl, n_live)
    ts = jnp.arange(tp, dtype=jnp.int32)
    # The row of each token: the live rows that end at or before it.
    row = jnp.sum(
        (qsl[None, 1:] <= ts[:, None]) & (jnp.arange(r) < n_live)[None, :],
        axis=1, dtype=jnp.int32)
    # The heads in hand at once: [32, dk] f32 a tap; where 32 does not
    # divide the inputs' heads (one stream of 40), 16 at a time and what is
    # left: whole tiles of a 16-bit served dtype.
    part = 32 if all(x.shape[1] % 32 == 0 for x in xs) else min(16, g)

    def last_tile(i, layer, total, *_):
        return (jnp.clip((total[0] + tile - 1) // tile - 1, 0, i), 0, 0)

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    y, stack = pl.pallas_call(
        functools.partial(_conv_kernel, part=part, bias=bias is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            in_specs=[anywhere] * len(xs) + [
                pl.BlockSpec((w.shape[0], g, dk), lambda i, *_: (0, 0, 0)),
                anywhere,
            ],
            out_specs=[pl.BlockSpec((tile, g, dk), last_tile), anywhere],
            grid=(tp // tile,),
            scratch_shapes=[
                pltpu.VMEM((2, km1 + tile + km1 * tile, g, dk), stack.dtype),
                pltpu.VMEM((2, km1 * tile, g, dk), stack.dtype),
                pltpu.SemaphoreType.DMA((2, len(xs))),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((tp, g, dk), jnp.float32),
            jax.ShapeDtypeStruct(stack.shape, stack.dtype),
        ],
        # After the six prefetched and the inputs and taps: the stack.
        input_output_aliases={6 + len(xs) + 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 << 20,
        ),
        name="kda_conv_kernel",
        interpret=interpret,
    )(
        i32(layer).reshape(1), i32(total).reshape(1), qsl, i32(slots),
        i32(fresh), row, *xs, w.reshape(-1, g, dk), stack,
    )
    # The tokens past the live ones were never written.
    return jnp.where((ts < total)[:t, None, None], y[:t], 0.0), stack


def kda_short_conv(
    xs: tuple[jnp.ndarray, ...],  # each [T, C_i]: this step's inputs, side
    # by side the C = G * dk channels
    stack: jnp.ndarray,  # [Lk, S + 1, K - 1, G, dk] in their dtype; slot S is scratch
    weights: tuple[jnp.ndarray, ...],  # each [K, C_i]; tap K-1 is the current token
    layer: jnp.ndarray,  # scalar i32
    slots: jnp.ndarray,  # [R] i32, distinct over the live rows
    fresh: jnp.ndarray,  # [R] bool: the row starts its request
    token_req_idx: jnp.ndarray,  # [T]
    query_start_loc: jnp.ndarray,  # [R+1]
    num_seqs: jnp.ndarray,  # scalar i32: rows [0, num_seqs) are live
    *,
    decode_only: bool = False,
    bias: jnp.ndarray | None = None,  # [C], added to every live token's sum
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The depthwise causal convolution of a layer over each live row's
    segment, continued from the row's slot and stored back to it: ``(y [T,
    G, dk] f32, stack)``; ``y`` is 0 past the live tokens. One stream or
    several (the delta-rule mixer's q, k and v; the state-space mixer's one
    of x, B and C, whose convolution carries a ``bias``).

    On the TPU one Pallas kernel (``kda_conv_kernel``) over the live
    tokens, ``CONV_TILE`` a step of its grid. A token is ``[G, dk]``, whole
    tiles, so the K - 1 tokens before it are a step of the leading axis and
    nothing is shifted or gathered: the tokens come in by one DMA an input
    a step, the tail of a row that begins in the step by one DMA from its
    slot (zeroed without a read where the request starts), and the K - 1
    last inputs of a row that ends in it (tail ++ its tokens, so a row
    shorter than K - 1 keeps part of its old tail) go back by one DMA to
    the same slot, the stack aliased. Rows past ``num_seqs``, the scratch
    slot and the steps past the last live token move nothing. A
    decode-only step is the case of one token a row: the same kernel.
    Mosaic takes no single row of a tiled array, which is why a slot is
    ``[K - 1, G, dk]`` and not ``(K - 1) * C`` lanes of a row, and the
    inputs are taken apart because joining them is a pass over them.
    ``write_slot_rows`` stores a number that is not finite as 0 because
    its one-hot product would carry it to other rows; a row written by its
    own DMA has no such product, so here it is stored as it is: the same
    stored values for finite inputs.

    Off the TPU (and as the tests' oracle) ``read_slot_rows`` ->
    ``ragged_short_conv`` -> ``write_slot_rows`` over the stack seen as
    ``[Lk, S + 1, (K - 1) * C]`` (``decode_only``, static, picks the form of
    ``ragged_short_conv`` that gathers nothing; on a TPU, under
    ``VLLM_TPU_DISABLE_PALLAS``, that view is a copy of the stack each way:
    a form for finding faults, not for serving)."""
    t = xs[0].shape[0]
    lk, s1, km1, g, dk = stack.shape
    n_live = jnp.asarray(num_seqs, jnp.int32).reshape(())
    layer = jnp.asarray(layer, jnp.int32)
    kernel, interpret = _pallas_form()
    if kernel and all(x.dtype == stack.dtype for x in xs):
        return _conv_pallas(
            tuple(xs), stack, tuple(weights), layer, slots, fresh,
            query_start_loc, n_live, bias, tile=CONV_TILE,
            interpret=interpret)
    r = slots.shape[0]
    flat = stack.reshape(lk, s1, km1 * g * dk)
    tail = jnp.where(
        fresh[:, None, None], 0,
        read_slot_rows(flat, layer, slots).reshape(r, km1, g * dk))
    y, new_tail = ragged_short_conv(
        jnp.concatenate(xs, axis=-1), tail, jnp.concatenate(weights, axis=-1),
        token_req_idx, query_start_loc, decode_only=decode_only)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    flat = write_slot_rows(flat, layer, slots, new_tail.reshape(r, -1), n_live)
    live = jnp.arange(t) < _live_tokens(query_start_loc, n_live)
    return (jnp.where(live[:, None, None], y.reshape(t, g, dk), 0.0),
            flat.reshape(stack.shape))


# ----------------------------------------------------------------------
# One token a row
# ----------------------------------------------------------------------


def _decode_kernel(
    n_live_ref, slots_ref, fresh_ref, layer_ref,  # scalar prefetch
    qt_ref, kt_ref, dt_ref,  # [dk, H]: the key channels on the sublanes
    v_ref, beta_ref,  # [H, dv]
    s_ref,  # [H, dk, dv] this row's state
    o_ref,  # [H, dv]
    s_out_ref,  # [H, dk, dv], the same slot
):
    i = pl.program_id(0)

    @pl.when(i < n_live_ref[0])
    def _():
        keep = jnp.where(fresh_ref[i] != 0, 0.0, 1.0)
        for h in range(s_ref.shape[0]):
            kcol = kt_ref[:, h : h + 1]
            sd = s_ref[h] * (dt_ref[:, h : h + 1] * keep)
            mem = jnp.sum(sd * kcol, axis=0, keepdims=True)  # [1, dv]
            delta = beta_ref[h : h + 1, :] * (v_ref[h : h + 1, :] - mem)
            sn = sd + kcol * delta
            s_out_ref[h] = sn
            o_ref[h : h + 1, :] = jnp.sum(
                sn * qt_ref[:, h : h + 1], axis=0, keepdims=True
            )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_pallas(q, k, v, a, beta, state, layer, slots, fresh, n_live,
                   *, interpret: bool = False):
    r, h, dk = q.shape
    dv = v.shape[-1]
    to_cols = lambda x: jnp.swapaxes(x, 1, 2)  # [R, H, dk] -> [R, dk, H]

    def row_of(i, n, *_):
        return jnp.maximum(jnp.minimum(i, n[0] - 1), 0)

    cols = pl.BlockSpec((None, dk, h), lambda i, n, *_: (row_of(i, n), 0, 0))
    rows = pl.BlockSpec((None, h, dv), lambda i, n, *_: (row_of(i, n), 0, 0))
    slot = pl.BlockSpec(
        (None, None, h, dk, dv),
        lambda i, n, sl, fr, li: (li[0], sl[row_of(i, n)], 0, 0, 0),
    )
    o, new_state = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[cols, cols, cols, rows, rows, slot],
            out_specs=[rows, slot],
            grid=(r,),
        ),
        out_shape=[
            jax.ShapeDtypeStruct((r, h, dv), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # Operand 9 (after the four prefetched) is the state stack.
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 << 20,
        ),
        name="kda_decode_kernel",
        interpret=interpret,
    )(
        n_live.astype(jnp.int32).reshape(1), slots.astype(jnp.int32),
        fresh.astype(jnp.int32), layer.astype(jnp.int32).reshape(1),
        to_cols(q), to_cols(k), to_cols(jnp.exp(a)), v,
        jnp.broadcast_to(beta[..., None], v.shape), state,
    )
    return o, new_state


def _decode_xla(q, k, v, a, beta, state, layer, slots, fresh, n_live):
    s0 = jnp.where(fresh[:, None, None, None], 0.0, state[layer, slots])
    sd = s0 * jnp.exp(a)[..., None]
    mem = jnp.einsum("rhk,rhkv->rhv", k, sd, precision=_HI)
    delta = beta[..., None] * (v - mem)
    sn = sd + k[..., None] * delta[:, :, None, :]
    o = jnp.einsum("rhk,rhkv->rhv", q, sn, precision=_HI)
    live = jnp.arange(q.shape[0]) < n_live
    # Rows past the live ones park in the last slot, the padding scratch.
    where = jnp.where(live, slots, state.shape[1] - 1)
    return o, state.at[layer, where].set(sn.astype(state.dtype))


def kda_decode(
    q: jnp.ndarray,  # [R, H, dk] f32, normalised and scaled
    k: jnp.ndarray,  # [R, H, dk] f32, normalised
    v: jnp.ndarray,  # [R, H, dv] f32
    a: jnp.ndarray,  # [R, H, dk] f32 log-decay <= 0
    beta: jnp.ndarray,  # [R, H] f32
    state: jnp.ndarray,  # [L, S, H, dk, dv] f32; slot S-1 is scratch
    layer: jnp.ndarray,  # scalar i32
    slots: jnp.ndarray,  # [R] i32; rows >= n_live must name the scratch slot
    fresh: jnp.ndarray,  # [R] bool: the row starts its request
    n_live: jnp.ndarray,  # scalar i32: rows [0, n_live) are updated
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One token of every live row: ``(o [R, H, dv] f32, state)``. Rows
    past ``n_live`` are neither read nor written; their ``o`` is 0."""
    args = (q, k, v, a, beta, state, jnp.asarray(layer, jnp.int32),
            slots, fresh, jnp.asarray(n_live, jnp.int32).reshape(()))
    pallas, interpret = _pallas_form(state)
    if pallas:
        o, state = _decode_pallas(*args, interpret=interpret)
    else:
        o, state = _decode_xla(*args)
    live = jnp.arange(q.shape[0]) < n_live
    return jnp.where(live[:, None, None], o, 0.0), state


# ----------------------------------------------------------------------
# Chunks of a row's segment
# ----------------------------------------------------------------------


def _unit_lower_inverse(n: jnp.ndarray) -> jnp.ndarray:
    """``(I + N)^-1`` for strictly lower ``N [..., m, m]``, a row at a
    time (m is ``SUB``: 15 small fused steps, no loop on the device)."""
    m = n.shape[-1]
    eye = jnp.eye(m, dtype=n.dtype)
    rows = [jnp.broadcast_to(eye[0], n.shape[:-2] + (m,))]
    for i in range(1, m):
        prev = jnp.stack(rows, axis=-2)  # [..., i, m]
        rows.append(
            eye[i] - jnp.sum(n[..., i, :i, None] * prev, axis=-2)
        )
    return jnp.stack(rows, axis=-2)


def _chunk_operands(q, k, v, a, beta):
    """Everything of a chunk that does not depend on the state it meets.
    Inputs ``[N, C, H, d]`` (``beta [N, C, H]``), invalid tokens holding
    ``a = 0``, ``beta = 0``. Returns, head-major ``[N, H, ...]``:
    ``w [C, dv]``, ``wk [C, dk]`` (so that ``u = w - wk S0``), ``qp [C,
    dk]`` and ``aq [C, C]`` (``o = qp S0 + aq u``), ``kend [C, dk]`` and
    ``dend [dk]`` (``S1 = dend * S0 + kend^T u``)."""
    n, c, h, dk = k.shape
    nb = c // SUB
    b = jnp.cumsum(a, axis=1)  # inclusive, <= 0, falling
    blk = lambda x: x.reshape(n, nb, SUB, h, x.shape[-1])
    bb, kb, qb = blk(b), blk(k), blk(q)
    # The cumulated decay just before each block's first token.
    r_blk = jnp.concatenate(
        [jnp.zeros_like(b[:, :1]), b[:, SUB - 1 : c - 1 : SUB]], axis=1
    )  # [N, nb, H, dk]
    lower = jnp.tril(jnp.ones((SUB, SUB), bool))
    strict = jnp.tril(jnp.ones((SUB, SUB), bool), -1)

    # Inside a block: the exact pairwise exponent, masked before exp.
    diff = bb[:, :, :, None] - bb[:, :, None, :]  # [N, nb, i, j, H, dk]
    pair = jnp.exp(
        jnp.where(lower[None, None, :, :, None, None], diff, -jnp.inf)
    )
    kj = kb[:, :, None, :] * pair
    to_heads = lambda x: jnp.moveaxis(x, -1, 1)  # [N, nb, i, j, H] -> [N, H, ..]
    kk = to_heads(jnp.sum(kb[:, :, :, None] * kj, axis=-1))
    qk = to_heads(jnp.sum(qb[:, :, :, None] * kj, axis=-1))
    kk = jnp.where(strict, kk, 0.0)

    # Across blocks: split at the start of the row's block.
    k_in = kb * jnp.exp(bb - r_blk[:, :, None])
    q_in = qb * jnp.exp(bb - r_blk[:, :, None])
    a_kk = jnp.zeros((n, h, c, c), jnp.float32)
    a_qk = jnp.zeros((n, h, c, c), jnp.float32)
    for bi in range(nb):
        lo = bi * SUB
        rows = slice(lo, lo + SUB)
        a_kk = a_kk.at[:, :, rows, rows].set(kk[:, :, bi])
        a_qk = a_qk.at[:, :, rows, rows].set(qk[:, :, bi])
        if bi:
            k_out = k[:, :lo] * jnp.exp(r_blk[:, bi, None] - b[:, :lo])
            a_kk = a_kk.at[:, :, rows, :lo].set(jnp.einsum(
                "nihd,njhd->nhij", k_in[:, bi], k_out, precision=_HI))
            a_qk = a_qk.at[:, :, rows, :lo].set(jnp.einsum(
                "nihd,njhd->nhij", q_in[:, bi], k_out, precision=_HI))

    # (I + diag(beta) A) x = y, a block of rows at a time.
    bt = jnp.swapaxes(beta, 1, 2)  # [N, H, C]
    m = bt[..., None] * a_kk
    e_b = jnp.exp(b)
    y = jnp.concatenate(
        [v, k * e_b], axis=-1
    ) * beta[..., None]  # [N, C, H, dv + dk]
    y = jnp.swapaxes(y, 1, 2)  # [N, H, C, dv + dk]
    # The diagonal blocks' inverses all at once, then forward by blocks.
    inv = _unit_lower_inverse(jnp.stack(
        [m[:, :, bi * SUB:(bi + 1) * SUB, bi * SUB:(bi + 1) * SUB]
         for bi in range(nb)], axis=2))  # [N, H, nb, SUB, SUB]
    xs = []
    for bi in range(nb):
        lo = bi * SUB
        rows = slice(lo, lo + SUB)
        rhs = y[:, :, rows]
        if bi:
            rhs = rhs - jnp.einsum(
                "nhij,nhjx->nhix", m[:, :, rows, :lo],
                jnp.concatenate(xs, axis=2), precision=_HI)
        xs.append(jnp.einsum("nhij,nhjx->nhix", inv[:, :, bi], rhs,
                             precision=_HI))
    x = jnp.concatenate(xs, axis=2)
    dv = v.shape[-1]
    w, wk = x[..., :dv], x[..., dv:]
    hm = lambda z: jnp.swapaxes(z, 1, 2)  # [N, C, H, d] -> [N, H, C, d]
    return (w, wk, hm(q * e_b), a_qk,
            hm(k * jnp.exp(b[:, -1:] - b)), e_b[:, -1])


def _chunk_places(query_start_loc, num_seqs, r, c):
    """The step's chunks in order: ``(qlen [R], per_row [R], ends [R],
    total)``; place ``g`` is chunk ``g - (ends[row] - per_row[row])`` of
    the row ``searchsorted(ends, g, "right")``."""
    qlen = query_start_loc[1:] - query_start_loc[:-1]
    mine = (qlen > 1) & (jnp.arange(r) < num_seqs)
    per_row = jnp.where(mine, -(-qlen // c), 0)
    ends = jnp.cumsum(per_row)
    return qlen, per_row, ends, ends[-1]


def _chunks_xla(q, k, v, a, beta, state, layer, slots, fresh,
                query_start_loc, num_seqs, *, n_chunks):
    t, h, dk = q.shape
    dv = v.shape[-1]
    r = slots.shape[0]
    c, nc = CHUNK, n_chunks
    scratch = state.shape[1] - 1
    qlen, per_row, ends, total = _chunk_places(query_start_loc, num_seqs, r, c)

    def one_round(carry):
        rnd, s_in, state, out = carry
        g = rnd * nc + jnp.arange(nc, dtype=jnp.int32)
        row = jnp.clip(jnp.searchsorted(ends, g, side="right"), 0, r - 1)
        j = g - (ends[row] - per_row[row])
        live = g < total
        n_valid = jnp.where(live, jnp.clip(qlen[row] - j * c, 0, c), 0)
        first = live & (j == 0)
        last = live & (j == per_row[row] - 1)
        offs = jnp.arange(c, dtype=jnp.int32)
        ok = offs[None, :] < n_valid[:, None]  # [N, C]
        idx = jnp.clip(
            query_start_loc[row][:, None] + j[:, None] * c + offs, 0, t - 1
        )
        take = lambda x: jnp.where(
            ok.reshape(ok.shape + (1,) * (x.ndim - 1)), x[idx], 0.0
        )
        w, wk, qp, aq, kend, dend = _chunk_operands(
            take(q), take(k), take(v), take(a), take(beta)
        )
        init = jnp.where(
            fresh[row][:, None, None, None], 0.0,
            state[layer, slots[row]].astype(jnp.float32),
        )

        def step(s, xs):
            w_c, wk_c, qp_c, aq_c, kend_c, dend_c, init_c, first_c = xs
            s0 = jnp.where(first_c, init_c, s)
            u = w_c - jnp.einsum("hik,hkv->hiv", wk_c, s0, precision=_HI)
            o_c = jnp.einsum(
                "hik,hkv->hiv", qp_c, s0, precision=_HI
            ) + jnp.einsum("hij,hjv->hiv", aq_c, u, precision=_HI)
            s1 = dend_c[..., None] * s0 + jnp.einsum(
                "hjk,hjv->hkv", kend_c, u, precision=_HI)
            return s1, (o_c, s1)

        s_out, (o, s_all) = lax.scan(
            step, s_in, (w, wk, qp, aq, kend, dend, init, first)
        )
        o = jnp.swapaxes(o, 1, 2).reshape(nc * c, h, dv)  # [N*C, H, dv]
        out = out.at[jnp.where(ok, idx, t).reshape(-1)].set(o, mode="drop")
        state = state.at[
            layer, jnp.where(last, slots[row], scratch)
        ].set(s_all.astype(state.dtype))
        return rnd + 1, s_out, state, out

    _, _, state, out = lax.while_loop(
        lambda carry: carry[0] * nc < total,
        one_round,
        (jnp.int32(0), jnp.zeros((h, dk, dv), jnp.float32), state,
         jnp.zeros((t, h, dv), jnp.float32)),
    )
    return out, state


# The chunked form as one kernel a layer ---------------------------------

_NN = (((1,), (0,)), ((), ()))  # [m, k] [k, n]
_NT = (((1,), (1,)), ((), ()))  # [m, k] [n, k]
_TN = (((0,), (0,)), ((), ()))  # [k, m] [k, n]


def _bf16_parts(x, n: int, exact: bool):
    """Float32 ``x`` as a sum of ``n`` bfloat16 arrays, leading part
    first; ``exact`` (the interpreter, on the CPU, where a float32 product
    is one): ``x`` itself."""
    if exact:
        return [x]
    parts = []
    for _ in range(n):
        parts.append(x.astype(jnp.bfloat16))
        x = x - parts[-1].astype(jnp.float32)
    return parts


def _dot(x, y, dims):
    return lax.dot_general(x, y, dims, precision=lax.Precision.HIGHEST
                           if x.dtype == jnp.float32 else None,
                           preferred_element_type=jnp.float32)


def _dot_high(x, y, dims, exact: bool = False):
    """A float32 matmul at ``Precision.HIGH`` spelt out, since Mosaic
    takes only DEFAULT and HIGHEST: both operands cut in two bfloat16
    parts, the three products that hold a leading part, float32 sums."""
    xs, ys = _bf16_parts(x, 2, exact), _bf16_parts(y, 2, exact)
    return sum(_dot(p, r, dims) for n, p in enumerate(xs)
               for r in ys[: len(ys) - n])


def _dot_ones(x, y, dims, exact: bool = False, ones: int = 0):
    """A matmul whose side ``ones`` holds 0s and 1s only (exact in
    bfloat16), the other side cut in THREE bfloat16 parts: float32 sums of
    its own numbers."""
    sides = [x, y]
    whole = sides[ones]
    total = 0.0
    for part in _bf16_parts(sides[1 - ones], 3, exact):
        sides[ones], sides[1 - ones] = whole.astype(part.dtype), part
        total = total + _dot(*sides, dims)
    return total


def _chunk_kernel(
    n_live_ref, layer_ref, tok0_ref, nval_ref, slot_ref, load_ref,  # prefetch
    q_hbm, k_hbm, v_hbm, a_hbm, beta_hbm,  # [T, H, d]; beta [T, 1, lanes]
    out_in_hbm,  # aliased to out_hbm, never read
    s_ref,  # [H, dk, dv]: the slot of this place's row
    out_hbm,  # [T + C, H, dv]
    s_out_ref,  # [H, dk, dv], the same slot: held from a row's first
    # chunk of the call to its last
    qbuf, kbuf, vbuf, abuf, bbuf,  # [2, 2 C, H, d]: this place, the next
    obuf,  # [2, C, H, dv]
    b_s, k_s,  # [C, dk]: the cumulated decays and the keys of the head in hand
    in_sem, out_sem,
    *, sub: int, exact: bool,
):
    del layer_ref, slot_ref, out_in_hbm  # the index maps' and the alias's
    c, h, dk = obuf.shape[1:]
    t = q_hbm.shape[0]
    i = pl.program_id(0)
    n_live = n_live_ref[0]
    f32 = jnp.float32
    dot = functools.partial(_dot_high, exact=exact)

    def fetch(place, buf):
        # C whole tokens that hold the chunk's: from its first one, or the
        # last C of the step where those would pass its end.
        rows = pl.ds(jnp.minimum(tok0_ref[place], t - c), c)
        return [
            pltpu.make_async_copy(
                src.at[rows], dst.at[buf, pl.ds(0, c)], in_sem.at[buf, n])
            for n, (src, dst) in enumerate((
                (q_hbm, qbuf), (k_hbm, kbuf), (v_hbm, vbuf), (a_hbm, abuf),
                (beta_hbm, bbuf)))
        ]

    def put(place, buf):
        return pltpu.make_async_copy(
            obuf.at[buf], out_hbm.at[pl.ds(tok0_ref[place], c)],
            out_sem.at[buf])

    @pl.when(i < n_live)
    def _():
        cur = i % 2

        @pl.when(i == 0)
        def _():
            for cp in fetch(0, 0):
                cp.start()

        @pl.when(i + 1 < n_live)
        def _():
            for cp in fetch(i + 1, 1 - cur):
                cp.start()

        for cp in fetch(i, cur):
            cp.wait()

        n_valid = nval_ref[i]
        load = load_ref[i]
        # Where the chunk's tokens lie in the 2 C rows of a buffer (the
        # rows past the C fetched hold nothing and are masked).
        mine = pl.ds(tok0_ref[i] - jnp.minimum(tok0_ref[i], t - c), c)
        row_c = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
        valid = row_c < n_valid
        ri = lax.broadcasted_iota(jnp.int32, (c, c), 0)
        ci = lax.broadcasted_iota(jnp.int32, (c, c), 1)
        tril = (ci <= ri).astype(f32)
        lane_sc = lax.broadcasted_iota(jnp.int32, (sub, c), 1)
        row_s = lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
        lane_h = lax.broadcasted_iota(jnp.int32, (c, bbuf.shape[-1]), 1)

        def one_head(hd, carry):
            keep = lambda x: jnp.where(valid, x, 0.0)
            q = keep(qbuf[cur, mine, hd, :])
            k = keep(kbuf[cur, mine, hd, :])
            v = keep(vbuf[cur, mine, hd, :])
            a = keep(abuf[cur, mine, hd, :])
            beta = keep(jnp.sum(
                jnp.where(lane_h == hd, bbuf[cur, mine, 0, :], 0.0),
                axis=1, keepdims=True))  # [C, 1]
            s0 = jnp.where(
                load == 2, 0.0,
                jnp.where(load == 1, s_ref[hd], s_out_ref[hd]))
            # b[i] = the sum of a[j] over j <= i: cumulated, <= 0, falling.
            b = _dot_ones(tril, a, _NN, exact)
            kb = k * beta
            b_s[...], k_s[...] = b, k  # read back a row (a token) at a time
            m_rows, aqk_rows, inv_rows = [], [], []
            for lo in range(0, c, sub):
                bb, qb, kbb = b[lo:lo + sub], q[lo:lo + sub], kb[lo:lo + sub]
                row = lo + row_s
                # Inside the block: the exact pairwise exponent, a column
                # (one earlier token against the block's rows) at a time.
                # M = diag(beta) A_kk is strictly lower, and its column j
                # under the diagonal, n_j, comes out of the sum over the
                # lanes on every lane: (I + n_j e_j^T)^-1 = I - n_j e_j^T
                # is applied at once, and after the last column ``inv``
                # holds the block's (I + M)^-1 exactly (no matmul, so no
                # wait for one), at the block's own lanes.
                akk = aqk = jnp.zeros((sub, c), f32)
                inv = (lane_sc == row).astype(f32)
                for j in range(sub):
                    at = pl.ds(lo + j, 1)
                    kj = k_s[at, :] * jnp.exp(
                        jnp.minimum(bb - b_s[at, :], 0.0))
                    hit = lane_sc == lo + j
                    n_j = jnp.where(
                        row_s > j, jnp.sum(kbb * kj, axis=1, keepdims=True),
                        0.0)
                    akk = jnp.where(hit, n_j, akk)
                    aqk = jnp.where(
                        hit, jnp.sum(qb * kj, axis=1, keepdims=True), aqk)
                    if j < sub - 1:
                        inv = inv - n_j * inv[j:j + 1, :]
                if lo:
                    # Across blocks: split at the decay just before the
                    # block (both halves <= 0); the columns from the
                    # block on are 0 and the pairs inside it stay.
                    ref = b[lo - 1:lo]
                    inner = jnp.exp(bb - ref)
                    k_out = jnp.concatenate([
                        k[:lo] * jnp.exp(ref - b[:lo]),
                        jnp.zeros((c - lo, dk), f32)], axis=0)
                    cross = dot(
                        jnp.concatenate([qb * inner, kbb * inner], axis=0),
                        k_out, _NT)  # [2 SUB, C]
                    aqk, akk = aqk + cross[:sub], akk + cross[sub:]
                m_rows.append(akk)
                aqk_rows.append(jnp.where(lane_sc <= row, aqk, 0.0))
                inv_rows.append(inv)
            m, a_qk, inv = (jnp.concatenate(x, axis=0)
                            for x in (m_rows, aqk_rows, inv_rows))

            # u = (I + M)^-1 (beta v - beta k e^b S0), o = q e^b S0 + A_qk u.
            # With D the blocks on M's diagonal and E the rest, I + M =
            # (I + D)(I + F), F = (I + D)^-1 E, and F is strictly lower BY
            # BLOCKS: F^(C / SUB) = 0, so (I + F)^-1 = (I - F)(I + F^2)(I +
            # F^4)... exactly. A factor is applied to u while the next
            # power is squared: one matmul's wait a factor.
            e_b = jnp.exp(b)
            both = dot(jnp.concatenate([kb * e_b, q * e_b], axis=0),
                       s0, _NN)  # [2 C, dv]
            shift = sub.bit_length() - 1
            power = dot(inv, jnp.where((ri >> shift) != (ci >> shift), m, 0.0),
                        _NN)
            u = dot(inv, v * beta - both[:c], _NN)
            u = u - dot(power, u, _NN)
            for _ in range(shift + 1, c.bit_length() - 1):
                power = dot(power, power, _NN)
                u = u + dot(power, u, _NN)
            obuf[cur, :, hd, :] = both[c:] + dot(a_qk, u, _NN)
            # The decay over the whole chunk with dk on the sublanes, as S
            # is laid out.
            d_end = jnp.exp(_dot_ones(
                a, jnp.ones((c, v.shape[1]), f32), _TN, exact, ones=1))
            s_out_ref[hd] = d_end * s0 + dot(
                k * jnp.exp(b[c - 1:c, :] - b), u, _TN)
            return carry

        lax.fori_loop(0, h, one_head, 0)

        @pl.when(i > 0)
        def _():
            put(i - 1, 1 - cur).wait()

        put(i, cur).start()

        @pl.when(i == n_live - 1)
        def _():
            put(i, cur).wait()


@functools.partial(
    jax.jit, static_argnames=("n_chunks", "chunk", "sub", "interpret"))
def _chunks_pallas(q, k, v, a, beta, state, layer, slots, fresh,
                   query_start_loc, num_seqs, *, n_chunks: int, chunk: int,
                   sub: int, interpret: bool = False):
    t, h, dk = q.shape
    dv = v.shape[-1]
    r = slots.shape[0]
    c, nc = chunk, n_chunks
    assert c & (c - 1) == 0 and sub & (sub - 1) == 0, (c, sub)
    qlen, per_row, ends, total = _chunk_places(query_start_loc, num_seqs, r, c)
    # A chunk is read as C whole tokens: a step shorter than that is padded.
    short = max(c - t, 0)
    pad = lambda x: jnp.pad(x, ((0, short),) + ((0, 0),) * (x.ndim - 1))
    lanes = -(-h // 128) * 128  # a DMA moves whole lanes
    operands = (pad(q), pad(k), pad(v), pad(a),
                jnp.pad(beta, ((0, short), (0, lanes - h)))[:, None, :])
    i32 = lambda x: x.astype(jnp.int32)

    def slot_map(i, n, li, tok, nv, sl, ld):
        return (li[0], sl[i], 0, 0, 0)

    slot = pl.BlockSpec((None, None, h, dk, dv), slot_map)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    call = pl.pallas_call(
        functools.partial(_chunk_kernel, sub=sub, exact=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            in_specs=[anywhere] * 6 + [slot],
            out_specs=[anywhere, slot],
            grid=(nc,),
            scratch_shapes=[
                pltpu.VMEM((2, 2 * c, h, dk), jnp.float32),
                pltpu.VMEM((2, 2 * c, h, dk), jnp.float32),
                pltpu.VMEM((2, 2 * c, h, dv), jnp.float32),
                pltpu.VMEM((2, 2 * c, h, dk), jnp.float32),
                pltpu.VMEM((2, 2 * c, 1, lanes), jnp.float32),
                pltpu.VMEM((2, c, h, dv), jnp.float32),
                pltpu.VMEM((c, dk), jnp.float32),
                pltpu.VMEM((c, dk), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 5)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((t + c, h, dv), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # After the six prefetched: operand 11 is the output's buffer and
        # 12 the state stack.
        input_output_aliases={11: 0, 12: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20,
        ),
        name="kda_chunk_kernel",
        interpret=interpret,
    )

    def one_round(carry):
        rnd, state, out = carry
        # The places past the step's last chunk repeat it: same slot, so
        # nothing moves, and the kernel does nothing there.
        g = jnp.minimum(rnd * nc + jnp.arange(nc, dtype=jnp.int32), total - 1)
        row = jnp.clip(jnp.searchsorted(
            ends, g, side="right", method="compare_all"), 0, r - 1)
        j = g - (ends[row] - per_row[row])
        # A row's first chunk starts from its slot (from zero where the
        # request starts); its later chunks from what the place before
        # left in VMEM, but for a round's first place: the round before
        # left the row's state in its slot.
        load = jnp.where(
            j == 0, jnp.where(fresh[row], 2, 1),
            (jnp.arange(nc) == 0).astype(jnp.int32))
        out, state = call(
            i32(jnp.minimum(total - rnd * nc, nc)).reshape(1),
            layer.reshape(1),
            i32(query_start_loc[row] + j * c),
            i32(jnp.clip(qlen[row] - j * c, 0, c)), i32(slots[row]),
            i32(load), *operands, out, state)
        return rnd + 1, state, out

    _, state, out = lax.while_loop(
        lambda carry: carry[0] * nc < total,
        one_round,
        (jnp.int32(0), state, jnp.zeros((t + c, h, dv), jnp.float32)),
    )
    return out[:t], state


def kda_chunks(
    q: jnp.ndarray,  # [T, H, dk] f32, normalised and scaled
    k: jnp.ndarray,  # [T, H, dk] f32, normalised
    v: jnp.ndarray,  # [T, H, dv] f32
    a: jnp.ndarray,  # [T, H, dk] f32 log-decay <= 0
    beta: jnp.ndarray,  # [T, H] f32
    state: jnp.ndarray,  # [L, S, H, dk, dv]; slot S-1 is scratch
    layer: jnp.ndarray,  # scalar i32
    slots: jnp.ndarray,  # [R] i32
    fresh: jnp.ndarray,  # [R] bool: the row starts its request
    query_start_loc: jnp.ndarray,  # [R+1]
    num_seqs: jnp.ndarray,  # scalar i32
    *,
    n_chunks: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Every live row with MORE than one token, by chunks. Returns ``(o
    [T, H, dv] f32, state)``; ``o`` is 0 at the tokens of other rows."""
    nc = n_chunks or (-(-q.shape[0] // CHUNK) + 1)
    args = (q, k, v, a, beta, state, jnp.asarray(layer, jnp.int32), slots,
            fresh, query_start_loc,
            jnp.asarray(num_seqs, jnp.int32).reshape(()))
    pallas, interpret = _pallas_form(state)
    if pallas:
        return _chunks_pallas(*args, n_chunks=nc, chunk=CHUNK, sub=SUB,
                              interpret=interpret)
    return _chunks_xla(*args, n_chunks=nc)


def rows_of_one_token(query_start_loc, num_seqs, slots, fresh, t: int, scratch: int):
    """The live rows of ONE token gathered to the front of a mixed step, for
    the one-token form of a slot-state rule: ``(tok [R] their tokens, slots
    [R] with the scratch slot behind them, fresh [R] in their order, how
    many, where [R] to put their outputs: ``t`` drops)``."""
    r = slots.shape[0]
    qlen = query_start_loc[1:] - query_start_loc[:-1]
    one = (qlen == 1) & (jnp.arange(r) < num_seqs)
    order = jnp.argsort(~one, stable=True)  # rows of one token first
    n_one = jnp.sum(one.astype(jnp.int32))
    front = jnp.arange(r) < n_one
    tok = jnp.clip(query_start_loc[order], 0, t - 1)
    return (tok, jnp.where(front, slots[order], scratch), fresh[order], n_one,
            jnp.where(front, tok, t))


def kda_attention(
    q, k, v, a, beta, state, layer, slots, fresh, query_start_loc,
    num_seqs, *, decode_only: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The step's tokens through both forms. ``decode_only`` (static):
    token ``i`` is row ``i`` and every live row holds one token, so only
    the one-token form runs. Otherwise the rows of one token are
    gathered to the front for it and the others go by chunks."""
    num_seqs = jnp.asarray(num_seqs, jnp.int32).reshape(())
    if decode_only:
        return kda_decode(q, k, v, a, beta, state, layer, slots, fresh,
                          num_seqs)
    tok, slots_one, fresh_one, n_one, where = rows_of_one_token(
        query_start_loc, num_seqs, slots, fresh, q.shape[0], state.shape[1] - 1)
    o_one, state = kda_decode(
        q[tok], k[tok], v[tok], a[tok], beta[tok], state, layer, slots_one,
        fresh_one, n_one,
    )
    o, state = kda_chunks(
        q, k, v, a, beta, state, layer, slots, fresh, query_start_loc,
        num_seqs,
    )
    return o.at[where].set(o_one, mode="drop"), state
