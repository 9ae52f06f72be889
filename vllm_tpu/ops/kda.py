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
  tokens inside a row's segment. What does not depend on the incoming
  state is computed for all chunks at once (the pairwise decayed
  ``k k^T`` and ``q k^T``, the unit-triangular solve, the decayed
  operands); a scan over the chunks then carries the state with four
  small matmuls a chunk. A step's chunks are taken ``n_chunks`` at a
  time, in rounds of a ``while_loop``, so that no mix of rows overflows
  a static bound; one round is the rule.

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
    from vllm_tpu import envs

    on_tpu = jax.default_backend() == "tpu"
    interpret = bool(envs.VLLM_TPU_PALLAS_INTERPRET) and not on_tpu
    args = (q, k, v, a, beta, state, jnp.asarray(layer, jnp.int32),
            slots, fresh, jnp.asarray(n_live, jnp.int32).reshape(()))
    if state.dtype == jnp.float32 and (on_tpu or interpret) and not (
        envs.VLLM_TPU_DISABLE_PALLAS
    ):
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
    t, h, dk = q.shape
    dv = v.shape[-1]
    r = slots.shape[0]
    c = CHUNK
    nc = n_chunks or (-(-t // c) + 1)
    scratch = state.shape[1] - 1
    layer = jnp.asarray(layer, jnp.int32)

    qlen = query_start_loc[1:] - query_start_loc[:-1]
    mine = (qlen > 1) & (jnp.arange(r) < num_seqs)
    per_row = jnp.where(mine, -(-qlen // c), 0)
    ends = jnp.cumsum(per_row)
    total = ends[-1]

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
    t = q.shape[0]
    r = slots.shape[0]
    qlen = query_start_loc[1:] - query_start_loc[:-1]
    one = (qlen == 1) & (jnp.arange(r) < num_seqs)
    order = jnp.argsort(~one, stable=True)  # rows of one token first
    n_one = jnp.sum(one.astype(jnp.int32))
    front = jnp.arange(r) < n_one
    tok = jnp.clip(query_start_loc[order], 0, t - 1)
    o_one, state = kda_decode(
        q[tok], k[tok], v[tok], a[tok], beta[tok], state, layer,
        jnp.where(front, slots[order], state.shape[1] - 1), fresh[order],
        n_one,
    )
    o, state = kda_chunks(
        q, k, v, a, beta, state, layer, slots, fresh, query_start_loc,
        num_seqs,
    )
    o = o.at[jnp.where(front, tok, t)].set(o_one, mode="drop")
    return o, state
