"""Mamba2 (SSD) selective-state ops over the ragged token batch.

Reference analog: ``csrc/mamba/mamba_ssm/selective_scan_fwd.cu`` (Mamba1)
and the Mamba2 kernels the reference imports from ``mamba_ssm``; cache
contract ``MambaSpec`` (``vllm/v1/kv_cache_interface.py:531``) and the
per-request constant-size state of ``MambaManager``.

TPU-first formulation: ONE flat ragged [T] token batch (mixed chunked
prefills + decodes, same layout the attention path uses) processed by

- a gather-based causal depthwise conv whose left context comes from the
  per-request cached conv tail, and
- a segment-aware ``jax.lax.associative_scan`` over the flat axis for the
  SSD recurrence ``H_t = a_t H_{t-1} + dt_t B_t x_t^T`` — a_t is scalar
  per head in Mamba2, so the whole recurrence is a first-order linear
  scan; request boundaries reset the decay (a=0) and seed the cached
  state into the first element, which makes one scan exact across all
  requests in the batch.

The state cache is request-slot addressed (slot = the request's single
MambaSpec block id), not paged: SSM state is O(1) in sequence length —
that is the point of the architecture.

The O(T·H·P·N) materialization of ``dBx`` is the correctness-first
choice; the chunked SSD matmul formulation (intra-chunk attention-like
GEMMs + inter-chunk state scan) is the optimization seam for long
prefills, same role the fused ``mamba_chunk_scan`` kernels play on CUDA.

The rule over state SLOTS, in place (the end of this file; the functions
above gather a step's rows of state out of the stack and scatter them
back). ``ssd_attention`` sends the rows of one token to ``ssd_decode`` and
the others to ``ssd_chunks``, as ``ops/kda.py::kda_attention`` does for the
delta rule, over ``state [L, S + 1, H, P, N]`` float32 whose last slot is
the padding rows' scratch:

- ``ssd_decode``: one token a row, for the rows named live. On the TPU a
  Pallas kernel (``ssd_decode_kernel``) whose grid walks (row, block of
  heads); a block of a row's state (8 heads of ``[P, N]``, 1 MiB at 128 x
  256) is read from its slot, updated (``a H + dt x B^T``, then ``H C``)
  and written back through input-output aliasing; the steps past the live
  rows map to the block already held and move nothing. Off the TPU (and as
  the tests' oracle) the same update as einsums over every row.
- ``ssd_chunks``: rows of more than one token, in chunks of ``SSD_CHUNK``
  tokens inside a row's segment, ``n_chunks`` places a round of a
  ``while_loop``. XLA: what does not depend on the incoming state for all
  of a round's places at once (the chunk's pairwise decays and ``C B^T``),
  then a scan over the places with three small matmuls each; a place reads
  its row's slot (and only that) and writes the row's state after the
  chunk back to it, so a row's slot holds its state at its last chunk and
  no other row's slot is touched. Every ``exp`` is of a difference of
  cumulated log-decays ``b_i - b_j`` with ``i >= j``, so of a number
  ``<= 0``; nothing is divided by a decay.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_tpu.ops.kda import _chunk_places, _pallas_form, rows_of_one_token


def ragged_causal_conv(
    x: jnp.ndarray,  # [T, C] conv inputs (this step, pre-activation)
    conv_state: jnp.ndarray,  # [R, C, K-1] cached tail per request (seeded)
    weight: jnp.ndarray,  # [C, K] depthwise taps (tap K-1 = current token)
    bias: jnp.ndarray | None,  # [C]
    token_req_idx: jnp.ndarray,  # [T] owning request row
    query_start_loc: jnp.ndarray,  # [R+1] ragged offsets
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Causal depthwise conv with cached left context.

    Returns (y [T, C], new_conv_state [R, C, K-1]) where the new state is
    each request's last K-1 conv inputs (zero-padded history preserved).
    """
    t, c = x.shape
    k = weight.shape[1]
    ts = jnp.arange(t, dtype=jnp.int32)
    chunk_start = query_start_loc[token_req_idx]  # [T] flat chunk starts
    pos_in_chunk = ts - chunk_start

    def window_at(s: jnp.ndarray) -> jnp.ndarray:
        """Conv input s steps back from each token: from this chunk when
        available, else from the request's cached tail."""
        in_chunk = pos_in_chunk >= s
        from_flat = x[jnp.clip(ts - s, 0)]
        # Cached tail col K-2 is the newest pre-chunk input.
        col = jnp.clip(k - 1 - s + pos_in_chunk, 0, k - 2)
        from_state = conv_state[token_req_idx, :, col]
        return jnp.where(in_chunk[:, None], from_flat, from_state)

    # win[:, j] = input (k-1-j) steps back; j = k-1 is the current token.
    win = jnp.stack([window_at(k - 1 - j) for j in range(k)], axis=1)
    y = jnp.einsum("tjc,cj->tc", win.astype(jnp.float32),
                   weight.astype(jnp.float32))
    if bias is not None:
        y = y + bias.astype(jnp.float32)

    # New tail per request: the window (minus the oldest column) at each
    # request's last scheduled token.
    last = jnp.maximum(query_start_loc[1:] - 1, 0)  # [R]
    new_state = win[last][:, 1:, :].transpose(0, 2, 1)  # [R, C, K-1]
    return y.astype(x.dtype), new_state.astype(conv_state.dtype)


def ragged_mamba1_scan(
    x: jnp.ndarray,  # [T, I] conv-activated inputs
    dt: jnp.ndarray,  # [T, I] softplus-ed step sizes
    a_log: jnp.ndarray,  # [I, N] A_log parameter (A = -exp(A_log))
    b: jnp.ndarray,  # [T, N] input gate (shared across channels)
    c: jnp.ndarray,  # [T, N] output gate
    h0: jnp.ndarray,  # [R, I, N] cached state per request (seeded)
    token_req_idx: jnp.ndarray,  # [T]
    query_start_loc: jnp.ndarray,  # [R+1]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Mamba1 selective scan: identical first-order linear recurrence to
    the SSD scan, but the decay is PER-(channel, state) —
    ``dA[t, i, n] = exp(dt[t, i] * A[i, n])`` (Mamba2 collapses A to a
    scalar per head, which is what unlocks its matmul formulation).
    Reference analog: ``csrc/mamba/mamba_ssm/selective_scan_fwd.cu``.

    Returns (y [T, I], new_state [R, I, N])."""
    t = x.shape[0]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    af = -jnp.exp(a_log.astype(jnp.float32))  # [I, N], negative
    decay = jnp.exp(dtf[..., None] * af[None])  # [T, I, N]

    u = (
        (dtf * xf)[..., None] * b.astype(jnp.float32)[:, None, :]
    )  # [T, I, N] = dt*x (outer) B

    ts = jnp.arange(t, dtype=jnp.int32)
    is_first = ts == query_start_loc[token_req_idx]
    h0_tok = h0[token_req_idx]  # [T, I, N]
    u = u + jnp.where(is_first[:, None, None], decay * h0_tok, 0.0)
    decay = jnp.where(is_first[:, None, None], 0.0, decay)

    def combine(left, right):
        a1, u1 = left
        a2, u2 = right
        return a1 * a2, a2 * u1 + u2

    _, h_all = jax.lax.associative_scan(combine, (decay, u), axis=0)
    y = jnp.einsum("tin,tn->ti", h_all, c.astype(jnp.float32))

    last = jnp.maximum(query_start_loc[1:] - 1, 0)
    new_state = h_all[last]  # [R, I, N]
    return y.astype(x.dtype), new_state.astype(h0.dtype)


def ragged_ssd_scan_chunked(
    x: jnp.ndarray,  # [T, H, P]
    dt: jnp.ndarray,  # [T, H]
    a_log: jnp.ndarray,  # [H]
    b: jnp.ndarray,  # [T, H, N]
    c: jnp.ndarray,  # [T, H, N]
    h0: jnp.ndarray,  # [R, H, P, N]
    token_req_idx: jnp.ndarray,  # [T]
    query_start_loc: jnp.ndarray,  # [R+1]
    *,
    chunk: int = 64,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD: the matmul formulation of :func:`ragged_ssd_scan`.

    The flat scan materializes dBx at O(T*H*P*N); this computes the same
    recurrence as (reference role: the CUDA ``mamba_chunk_scan`` kernels
    next to ``selective_scan_fwd.cu``):

    1. INTRA-chunk: an attention-like masked GEMM per chunk —
       ``S[i, j] = (C_i . B_j) dt_j exp(cumA_i - cumA_j)`` for j <= i in
       the same request, then ``y_intra = S @ x``.
    2. INTER-chunk: per-chunk outflow states ``Z[c] = sum_j w_j B_j
       (dt_j x_j)^T`` (tokens whose request reaches the chunk end) chain
       through a tiny first-order scan over chunks; token i receives
       ``coef_i C_i^T H_init(c)`` when its request started before the
       chunk.
    3. SEEDS: the recurrence is linear in (h0, u), so cached states
       contribute independently: ``y_seed_i = g_i C_i^T h0[r_i]`` with
       ``g_i`` the segment-cumulative decay — scalar per head (Mamba2's
       A is scalar-per-head; this term is what breaks rank-1 chunking if
       folded into u, so it rides separately).

    All einsums pin ``Precision.HIGHEST``: TPU's default matmul
    precision is bf16, which silently diverges from the elementwise f32
    flat scan by ~1e-2 at these shapes.

    Request boundaries never need log-of-zero sentinels: within-segment
    decay products only involve REAL decays (exp(dt*A) > 0, and
    ln(decay) = dt*A exactly); cross-boundary flow is killed by explicit
    same-request masks and by chunk products that include a boundary
    token's masked factor.
    """
    t, h, p_dim = x.shape
    n = b.shape[-1]
    r = h0.shape[0]
    nc = -(-t // chunk)
    t_pad = nc * chunk
    if t_pad != t:
        pad = [(0, t_pad - t)]
        x = jnp.pad(x, pad + [(0, 0)] * 2)
        dt = jnp.pad(dt, pad + [(0, 0)])
        b = jnp.pad(b, pad + [(0, 0)] * 2)
        c = jnp.pad(c, pad + [(0, 0)] * 2)
        # Pad tokens: own segment id (never matches a live request).
        token_req_idx = jnp.pad(
            token_req_idx, pad, constant_values=r + 1
        )

    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    af = -jnp.exp(a_log.astype(jnp.float32))  # [H]
    ln_a = dtf * af[None]  # [Tp, H] = log(real decay), exact
    ts = jnp.arange(t_pad, dtype=jnp.int32)
    is_first = ts == query_start_loc[jnp.clip(token_req_idx, 0, r)]
    seg = token_req_idx  # segment id per token

    # ---- per-chunk views ----
    def ck(v):
        return v.reshape((nc, chunk) + v.shape[1:])

    seg_c = ck(seg)  # [NC, Q]
    ln_c = ck(ln_a)  # [NC, Q, H]
    dt_c = ck(dtf)
    x_c = ck(xf)  # [NC, Q, H, P]
    b_c = ck(b.astype(jnp.float32))
    c_c = ck(c.astype(jnp.float32))
    first_c = ck(is_first)

    # Within-segment products exp(cum_i - cum_j) for j < i never include
    # a segment-start decay, so zero it out of the cumsum; j == i adds
    # nothing (difference 0).
    ln_nf = jnp.where(first_c[..., None], 0.0, ln_c)
    cum = jnp.cumsum(ln_nf, axis=1)  # [NC, Q, H] inclusive

    # 1. Intra-chunk masked GEMM.
    g_bc = jnp.einsum("kqhn,kjhn->khqj", c_c, b_c, precision=jax.lax.Precision.HIGHEST)  # [NC, H, Q, Q]
    same = seg_c[:, :, None] == seg_c[:, None, :]  # [NC, Q, Q]
    causal = (
        jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]
    )
    decay_ij = jnp.exp(
        cum[:, :, None] - cum[:, None, :]
    )  # [NC, Q, Q, H] (i, j)
    w_ij = jnp.where(
        (same & causal)[..., None], decay_ij * dt_c[:, None, :, :], 0.0
    )  # [NC, Q, Q, H]
    y = jnp.einsum(
        "khqj,kqjh,kjhp->kqhp", g_bc, w_ij, x_c
    , precision=jax.lax.Precision.HIGHEST)  # [NC, Q, H, P]

    # 2. Inter-chunk state chain.
    # Outflow weight: decay from j (exclusive) to chunk end, masked to
    # tokens whose request reaches the chunk's last token.
    last_seg = seg_c[:, -1]  # [NC]
    reach = seg_c == last_seg[:, None]  # [NC, Q]
    w_out = jnp.where(
        reach[..., None],
        jnp.exp(cum[:, -1:, :] - cum) * dt_c,
        0.0,
    )  # [NC, Q, H]
    z = jnp.einsum(
        "kqhn,kqh,kqhp->khpn", b_c, w_out, x_c
    , precision=jax.lax.Precision.HIGHEST)  # [NC, H, P, N]
    # Chunk decay product INCLUDING boundary-masked factors: a chunk
    # containing a segment start forwards nothing.
    a_chunk = jnp.exp(jnp.sum(ln_c, axis=1)) * jnp.all(
        ~first_c, axis=1
    ).astype(jnp.float32)[:, None]  # [NC, H]

    def comb(left, right):
        a1, z1 = left
        a2, z2 = right
        return a1 * a2, a2[..., None, None] * z1 + z2

    a_sc, z_sc = jax.lax.associative_scan(comb, (a_chunk, z), axis=0)
    # H_init for chunk k = scanned state of chunk k-1 (exclusive).
    h_init = jnp.concatenate(
        [jnp.zeros_like(z_sc[:1]), z_sc[:-1]], axis=0
    )  # [NC, H, P, N]

    # Inflow: decay from chunk start through i inclusive (all real
    # factors), valid when i's request started BEFORE this chunk — i.e.
    # i shares the chunk's first token's request and that token is a
    # continuation, so no boundary sits in [chunk_start, i].
    coef = jnp.exp(jnp.cumsum(ln_c, axis=1))  # [NC, Q, H]
    cont = (seg_c == seg_c[:, :1]) & ~first_c[:, :1]  # [NC, Q]
    y_inter = jnp.einsum(
        "kqhn,khpn->kqhp", c_c * coef[..., None], h_init
    , precision=jax.lax.Precision.HIGHEST)
    y = y + y_inter * jnp.where(cont, 1.0, 0.0)[..., None, None]

    y = y.reshape(t_pad, h, p_dim)[:t]

    # 3. Seeds (linearity): g_i = segment-cumulative REAL decay.
    cs = jnp.cumsum(ln_a, axis=0)  # [Tp, H]
    start_idx = query_start_loc[jnp.clip(token_req_idx, 0, r)]
    base = cs[jnp.clip(start_idx, 0, t_pad - 1)] - ln_a[
        jnp.clip(start_idx, 0, t_pad - 1)
    ]
    g = jnp.exp(cs - base)[:t]  # [T, H]
    h0_tok = h0[jnp.clip(token_req_idx[:t], 0, r - 1)]  # [T, H, P, N]
    y_seed = jnp.einsum(
        "thn,thpn->thp", (c.astype(jnp.float32)[:t] * g[..., None]),
        h0_tok,
        precision=jax.lax.Precision.HIGHEST,
    )
    y = y + y_seed

    # Final per-request states at each request's last scheduled token.
    last = jnp.maximum(query_start_loc[1:] - 1, 0)  # [R]
    lc = last // chunk
    li = last % chunk
    rows = jnp.arange(r)
    # u-part: H_init(chunk) * coef + intra sum at the last token.
    coef_l = coef[lc, li] * jnp.where(
        (seg_c[lc, 0] == token_req_idx[last]) & ~first_c[lc, 0], 1.0, 0.0
    )[:, None]  # [R, H]
    state_u = h_init[lc] * coef_l[..., None, None]
    w_last = jnp.where(
        (
            (seg_c[lc] == token_req_idx[last][:, None])
            & (jnp.arange(chunk)[None] <= li[:, None])
        )[..., None],
        jnp.exp(cum[lc, li][:, None] - cum[lc]) * dt_c[lc],
        0.0,
    )  # [R, Q, H]
    state_u = state_u + jnp.einsum(
        "rqhn,rqh,rqhp->rhpn", b_c[lc], w_last, x_c[lc]
    , precision=jax.lax.Precision.HIGHEST)
    g_last = g[jnp.clip(last, 0, t - 1)]  # [R, H]
    new_state = state_u + g_last[..., None, None] * h0
    return y.astype(x.dtype), new_state.astype(h0.dtype)


def select_ssd_scan(t: int):
    """Chunked (matmul) formulation for long prefills, flat associative
    scan otherwise — ``t`` is a static trace-time shape, so the choice
    costs nothing at run time. The crossover reflects where the flat
    scan's O(T*H*P*N) dBx materialization starts to dominate."""
    return ragged_ssd_scan_chunked if t >= 256 else ragged_ssd_scan


def ragged_ssd_scan(
    x: jnp.ndarray,  # [T, H, P] conv-activated inputs
    dt: jnp.ndarray,  # [T, H] softplus-ed, clamped step sizes
    a_log: jnp.ndarray,  # [H] A_log parameter (A = -exp(A_log))
    b: jnp.ndarray,  # [T, H, N] input gates (group-expanded)
    c: jnp.ndarray,  # [T, H, N] output gates (group-expanded)
    h0: jnp.ndarray,  # [R, H, P, N] cached state per request (seeded)
    token_req_idx: jnp.ndarray,  # [T]
    query_start_loc: jnp.ndarray,  # [R+1]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Segment-aware first-order linear scan (the SSD recurrence).

    Returns (y [T, H, P], new_state [R, H, P, N] at each request's last
    scheduled token).
    """
    t = x.shape[0]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    af = -jnp.exp(a_log.astype(jnp.float32))  # [H], negative
    decay = jnp.exp(dtf * af[None, :])  # [T, H]

    # dBx contribution per token.
    u = (
        (dtf[..., None] * b.astype(jnp.float32))[:, :, None, :]
        * xf[..., None]
    )  # [T, H, P, N]

    # Request boundaries: zero the decay (no cross-request flow) and fold
    # the cached state into the first element of each segment.
    ts = jnp.arange(t, dtype=jnp.int32)
    is_first = ts == query_start_loc[token_req_idx]
    h0_tok = h0[token_req_idx]  # [T, H, P, N]
    u = u + jnp.where(
        is_first[:, None, None, None],
        decay[..., None, None] * h0_tok,
        0.0,
    )
    decay = jnp.where(is_first[:, None], 0.0, decay)

    def combine(left, right):
        a1, u1 = left
        a2, u2 = right
        return a1 * a2, a2[..., None, None] * u1 + u2

    _, h_all = jax.lax.associative_scan(combine, (decay, u), axis=0)
    y = jnp.einsum("thpn,thn->thp", h_all, c.astype(jnp.float32))

    last = jnp.maximum(query_start_loc[1:] - 1, 0)
    new_state = h_all[last]  # [R, H, P, N]
    return y.astype(x.dtype), new_state.astype(h0.dtype)


# ----------------------------------------------------------------------
# The rule over state slots, in place
# ----------------------------------------------------------------------

SSD_CHUNK = 128  # tokens a chunk (the family's ``mamba_chunk_size``)
SSD_HEADS = 8  # heads a step of the one-token kernel's grid
_HI = lax.Precision.HIGH  # three bf16 passes: float32 to some 2**-21


def _ssd_decode_kernel(
    n_live_ref, slots_ref, fresh_ref, layer_ref,  # scalar prefetch
    xt_ref,  # [P, hb]: dt * x, the head's channels on the sublanes
    at_ref,  # [P, hb]: the head's decay on every sublane
    b_ref, c_ref,  # [G, N]
    s_ref,  # [hb, P, N]: these heads of this row's state
    yt_ref,  # [P, hb]
    s_out_ref,  # [hb, P, N], the same place of the same slot
    *, heads_per_group: int,
):
    del slots_ref, layer_ref  # the index maps'
    i, j = pl.program_id(0), pl.program_id(1)
    hb = s_ref.shape[0]

    @pl.when(i < n_live_ref[0])
    def _():
        fresh = fresh_ref[i] != 0
        for h in range(hb):
            g = (j * hb + h) // heads_per_group
            # A request that starts reads nothing of what the slot held.
            s = jnp.where(fresh, 0.0, s_ref[h])  # [P, N]
            sn = s * at_ref[:, h : h + 1] + xt_ref[:, h : h + 1] * b_ref[pl.ds(g, 1), :]
            s_out_ref[h] = sn
            yt_ref[:, h : h + 1] = jnp.sum(
                sn * c_ref[pl.ds(g, 1), :], axis=1, keepdims=True)


def _heads_a_step(h: int) -> int:
    return max(d for d in range(1, min(h, SSD_HEADS) + 1) if h % d == 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_decode_pallas(x, dt, ln_a, b, c, state, layer, slots, fresh, n_live,
                       *, interpret: bool = False):
    r, h, p = x.shape
    g, n = b.shape[1:]
    hb = _heads_a_step(h)
    nb = h // hb
    # [R, H, P] -> [R, H / hb, P, hb]: a block's heads side by side on the
    # lanes, so that a head's channels are a column beside its [P, N] state.
    cols = lambda v: jnp.swapaxes(v.reshape(r, nb, hb, p), 2, 3)
    xt = cols(x * dt[..., None])
    at = cols(jnp.broadcast_to(jnp.exp(ln_a)[..., None], x.shape))

    def place(i, j, n_):
        """The step's (row, block of heads); past the live rows, the last
        live row's last block: the block already held."""
        dead = i >= n_[0]
        row = jnp.maximum(jnp.minimum(i, n_[0] - 1), 0)
        return row, jnp.where(dead, nb - 1, j)

    def col_map(i, j, n_, *_):
        return (*place(i, j, n_), 0, 0)

    def row_map(i, j, n_, *_):
        return (place(i, j, n_)[0], 0, 0)

    def slot_map(i, j, n_, sl, fr, li):
        row, blk = place(i, j, n_)
        return (li[0], sl[row], blk, 0, 0)

    col_spec = pl.BlockSpec((None, None, p, hb), col_map)
    grp_spec = pl.BlockSpec((None, g, n), row_map)
    slot_spec = pl.BlockSpec((None, None, hb, p, n), slot_map)
    yt, new_state = pl.pallas_call(
        functools.partial(_ssd_decode_kernel, heads_per_group=h // g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[col_spec, col_spec, grp_spec, grp_spec, slot_spec],
            out_specs=[col_spec, slot_spec],
            grid=(r, nb),
        ),
        out_shape=[
            jax.ShapeDtypeStruct((r, nb, p, hb), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # Operand 8 (after the four prefetched) is the state stack.
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 << 20,
        ),
        name="ssd_decode_kernel",
        interpret=interpret,
    )(
        n_live.astype(jnp.int32).reshape(1), slots.astype(jnp.int32),
        fresh.astype(jnp.int32), layer.astype(jnp.int32).reshape(1),
        xt, at, b, c, state,
    )
    return jnp.swapaxes(yt, 2, 3).reshape(r, h, p), new_state


def _ssd_decode_xla(x, dt, ln_a, b, c, state, layer, slots, fresh, n_live):
    r, h, p = x.shape
    g = b.shape[1]
    heads = lambda v: jnp.repeat(v, h // g, axis=1)  # [R, G, N] -> [R, H, N]
    s0 = jnp.where(fresh[:, None, None, None], 0.0, state[layer, slots])
    sn = s0 * jnp.exp(ln_a)[..., None, None] + (
        (x * dt[..., None])[..., None] * heads(b)[:, :, None, :])
    y = jnp.einsum("rhpn,rhn->rhp", sn, heads(c), precision=_HI)
    live = jnp.arange(r) < n_live
    # Rows past the live ones park in the last slot, the padding scratch.
    where = jnp.where(live, slots, state.shape[1] - 1)
    return y, state.at[layer, where].set(sn.astype(state.dtype))


def ssd_decode(
    x: jnp.ndarray,  # [R, H, P] f32: the convolved, activated inputs
    dt: jnp.ndarray,  # [R, H] f32: the step, after its softplus
    ln_a: jnp.ndarray,  # [R, H] f32: the log of the head's decay, <= 0
    b: jnp.ndarray,  # [R, G, N] f32: a group serves H / G heads
    c: jnp.ndarray,  # [R, G, N] f32
    state: jnp.ndarray,  # [L, S, H, P, N] f32; slot S-1 is scratch
    layer: jnp.ndarray,  # scalar i32
    slots: jnp.ndarray,  # [R] i32; rows >= n_live must name the scratch slot
    fresh: jnp.ndarray,  # [R] bool: the row starts its request
    n_live: jnp.ndarray,  # scalar i32: rows [0, n_live) are updated
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One token of every live row, ``H_t = a H + dt x B^T``, ``y = H_t C``:
    ``(y [R, H, P] f32, state)``. Rows past ``n_live`` are neither read nor
    written; their ``y`` is 0. The skip ``D x`` is the caller's."""
    args = (x, dt, ln_a, b, c, state, jnp.asarray(layer, jnp.int32), slots,
            fresh, jnp.asarray(n_live, jnp.int32).reshape(()))
    pallas, interpret = _pallas_form(state)
    if pallas:
        y, state = _ssd_decode_pallas(*args, interpret=interpret)
    else:
        y, state = _ssd_decode_xla(*args)
    live = jnp.arange(x.shape[0]) < n_live
    return jnp.where(live[:, None, None], y, 0.0), state


def ssd_chunks(
    x: jnp.ndarray,  # [T, H, P] f32
    dt: jnp.ndarray,  # [T, H] f32
    ln_a: jnp.ndarray,  # [T, H] f32 log-decay <= 0
    b: jnp.ndarray,  # [T, G, N] f32
    c: jnp.ndarray,  # [T, G, N] f32
    state: jnp.ndarray,  # [L, S, H, P, N]; slot S-1 is scratch
    layer: jnp.ndarray,  # scalar i32
    slots: jnp.ndarray,  # [R] i32
    fresh: jnp.ndarray,  # [R] bool: the row starts its request
    query_start_loc: jnp.ndarray,  # [R+1]
    num_seqs: jnp.ndarray,  # scalar i32
    *,
    n_chunks: int | None = None,
    chunk: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Every live row with MORE than one token, by chunks. Returns ``(y
    [T, H, P] f32, state)``; ``y`` is 0 at the tokens of other rows. Only
    the slots of the rows that have chunks are read or written (the places
    past the step's last chunk write the scratch slot)."""
    t, h, p = x.shape
    g, n = b.shape[1:]
    hg = h // g
    r = slots.shape[0]
    cs = chunk or SSD_CHUNK
    nc = n_chunks or (-(-t // cs) + 1)
    layer = jnp.asarray(layer, jnp.int32)
    num_seqs = jnp.asarray(num_seqs, jnp.int32).reshape(())
    scratch = state.shape[1] - 1
    qlen, per_row, ends, total = _chunk_places(query_start_loc, num_seqs, r, cs)
    tril = jnp.tril(jnp.ones((cs, cs), bool))
    zero = jnp.int32(0)

    def one_round(carry):
        rnd, s_in, state, out = carry
        place = rnd * nc + jnp.arange(nc, dtype=jnp.int32)
        row = jnp.clip(jnp.searchsorted(ends, place, side="right"), 0, r - 1)
        j = place - (ends[row] - per_row[row])
        live = place < total
        n_valid = jnp.where(live, jnp.clip(qlen[row] - j * cs, 0, cs), 0)
        offs = jnp.arange(cs, dtype=jnp.int32)
        ok = offs[None, :] < n_valid[:, None]  # [N, C]
        idx = jnp.clip(
            query_start_loc[row][:, None] + j[:, None] * cs + offs, 0, t - 1)
        take = lambda v: jnp.where(
            ok.reshape(ok.shape + (1,) * (v.ndim - 1)), v[idx], 0.0)
        xc, dtc, lac, bc, cc = take(x), take(dt), take(ln_a), take(b), take(c)
        # What a chunk does without the state it meets. ``cum`` is the
        # cumulated log-decay, falling; a token past the chunk's end holds
        # dt = 0 and a log-decay of 0, so it adds and forgets nothing.
        cum = jnp.cumsum(lac, axis=1)  # [N, C, H]
        pair = jnp.exp(jnp.where(
            tril[None, :, :, None], cum[:, :, None] - cum[:, None, :],
            -jnp.inf)) * dtc[:, None]  # [N, i, j, H]
        cb = jnp.einsum("kign,kjgn->kgij", cc, bc, precision=_HI)
        w = cb[:, :, None] * jnp.moveaxis(pair, 3, 1).reshape(nc, g, hg, cs, cs)
        y_in = jnp.einsum(
            "kghij,kjghp->kighp", w, xc.reshape(nc, cs, g, hg, p),
            precision=_HI)
        e_b = jnp.exp(cum).reshape(nc, cs, g, hg)
        u = xc.reshape(nc, cs, g, hg, p) * (
            jnp.exp(cum[:, -1:] - cum) * dtc).reshape(nc, cs, g, hg)[..., None]
        d_end = jnp.exp(cum[:, -1]).reshape(nc, g, hg)

        def step(carry, xs):
            s, state = carry
            y_k, cc_k, bc_k, eb_k, u_k, dend_k, first_k, fresh_k, slot_k, live_k = xs
            held = lax.dynamic_slice(
                state, (layer, slot_k, zero, zero, zero), (1, 1, h, p, n)
            )[0, 0].astype(jnp.float32)
            s0 = jnp.where(first_k, jnp.where(fresh_k, 0.0, held), s)
            s0 = s0.reshape(g, hg, p, n)
            y_k = y_k + eb_k[..., None] * jnp.einsum(
                "ign,ghpn->ighp", cc_k, s0, precision=_HI)
            s1 = (dend_k[..., None, None] * s0 + jnp.einsum(
                "jghp,jgn->ghpn", u_k, bc_k, precision=_HI)).reshape(h, p, n)
            # The row's state after this chunk, to the row's own slot: its
            # last chunk's is what stays.
            state = lax.dynamic_update_slice(
                state, s1[None, None].astype(state.dtype),
                (layer, jnp.where(live_k, slot_k, scratch), zero, zero, zero))
            return (s1, state), y_k.reshape(cs, h, p)

        (s_out, state), y = lax.scan(
            step, (s_in, state),
            (y_in, cc, bc, e_b, u, d_end, live & (j == 0), fresh[row],
             slots[row].astype(jnp.int32), live))
        out = out.at[jnp.where(ok, idx, t).reshape(-1)].set(
            y.reshape(nc * cs, h, p), mode="drop")
        return rnd + 1, s_out, state, out

    _, _, state, out = lax.while_loop(
        lambda carry: carry[0] * nc < total,
        one_round,
        (jnp.int32(0), jnp.zeros((h, p, n), jnp.float32), state,
         jnp.zeros((t, h, p), jnp.float32)),
    )
    return out, state


def ssd_attention(
    x, dt, ln_a, b, c, state, layer, slots, fresh, query_start_loc, num_seqs,
    *, decode_only: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The step's tokens through both forms. ``decode_only`` (static):
    token ``i`` is row ``i`` and every live row holds one token, so only the
    one-token form runs. Otherwise the rows of one token are gathered to
    the front for it and the others go by chunks."""
    num_seqs = jnp.asarray(num_seqs, jnp.int32).reshape(())
    if decode_only:
        return ssd_decode(x, dt, ln_a, b, c, state, layer, slots, fresh,
                          num_seqs)
    tok, slots_one, fresh_one, n_one, where = rows_of_one_token(
        query_start_loc, num_seqs, slots, fresh, x.shape[0], state.shape[1] - 1)
    y_one, state = ssd_decode(
        x[tok], dt[tok], ln_a[tok], b[tok], c[tok], state, layer, slots_one,
        fresh_one, n_one,
    )
    y, state = ssd_chunks(
        x, dt, ln_a, b, c, state, layer, slots, fresh, query_start_loc,
        num_seqs,
    )
    return y.at[where].set(y_one, mode="drop"), state
