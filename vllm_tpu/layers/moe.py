"""Fused Mixture-of-Experts layer.

Reference analog: ``vllm/model_executor/layers/fused_moe/`` — the CUDA stack
there is a modular-kernel framework (routing topk ``csrc/moe/
topk_softmax_kernels.cu``, token permute/align ``moe_align_sum_kernels.cu``,
grouped GEMM experts, all2all dispatch managers). The TPU design collapses
to two paths with one semantic:

- **grouped path** (TPU): sort tokens by expert, grouped matmul over the
  ragged groups (``ops/gmm_kernel.py``, the repo's fork of
  ``jax.experimental.pallas.ops.tpu.megablox.gmm``; megablox itself for the
  shapes ``_gmm`` leaves to it), unsort + weighted combine. This is the
  moe_align + grouped-GEMM pipeline as one Pallas kernel family.
- **dense path** (any backend, and the multi-device GSPMD path): one-hot
  dispatch einsum over the expert axis. With experts sharded over a mesh
  axis XLA turns the combine into the EP psum — the reference's all2all
  prepare/finalize managers (``all2all.py``) become sharding annotations.

Routing matches the reference semantics (softmax -> top-k -> optional
renormalize; ``fused_moe/layer.py select_experts``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from vllm_tpu.layers.activation import silu_and_mul


def select_experts(
    router_logits: jnp.ndarray,  # [T, E] (pre-softmax)
    top_k: int,
    renormalize: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (weights [T, k] f32, expert_ids [T, k] i32)."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    weights, ids = jax.lax.top_k(probs, top_k)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, ids.astype(jnp.int32)


def select_experts_grouped(
    logits: jnp.ndarray,  # [T, E] f32
    bias: jnp.ndarray | None,  # [E] selection bias (sigmoid routing)
    *,
    top_k: int,
    n_group: int = 1,
    topk_group: int = 1,
    sigmoid: bool = False,
    method: str = "greedy",
    renormalize: bool = False,
    scaling: float = 1.0,
    renorm_guard: float = 1e-20,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """DeepSeek routing (HF DeepseekV2MoEGate / DeepseekV3TopkRouter
    semantics). Returns (weights [T, k] f32, ids [T, k] i32).
    ``renorm_guard`` is added to the chosen scores' sum before they are
    divided by it (a family states its own: LFM2's is 1e-6)."""
    t, e = logits.shape
    g, k = n_group, top_k
    if sigmoid:
        scores = jax.nn.sigmoid(logits)
        choice = scores + bias[None, :] if bias is not None else scores
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        choice = scores
    if method in ("group_limited_greedy", "noaux_tc") and g > 1:
        grouped = choice.reshape(t, g, e // g)
        if method == "noaux_tc":
            top2, _ = jax.lax.top_k(grouped, 2)
            group_scores = top2.sum(axis=-1)  # [T, G]
        else:
            group_scores = grouped.max(axis=-1)
        _, group_idx = jax.lax.top_k(group_scores, topk_group)
        group_mask = (
            jax.nn.one_hot(group_idx, g, dtype=jnp.float32).sum(axis=1) > 0
        )  # [T, G]
        mask = jnp.repeat(group_mask, e // g, axis=-1)
        choice = jnp.where(mask, choice, 0.0)
    _, ids = jax.lax.top_k(choice, k)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if renormalize:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + renorm_guard)
    return weights * scaling, ids.astype(jnp.int32)


def _dense_moe(
    hidden: jnp.ndarray,  # [T, D]
    w_gate: jnp.ndarray,  # [E, D, F]
    w_up: jnp.ndarray,  # [E, D, F]
    w_down: jnp.ndarray,  # [E, F, D]
    weights: jnp.ndarray,  # [T, k]
    expert_ids: jnp.ndarray,  # [T, k]
    act_fn=None,
    biases=None,  # (b_gate [E,F], b_up [E,F], b_down [E,D]) or None
    first_expert: int = 0,
) -> jnp.ndarray:
    """One-hot dispatch: every expert sees every token, masked combine.
    FLOP-wasteful on one chip but exactly what GSPMD wants for EP: with
    ``w_*`` sharded on the expert axis each device computes only its
    experts and the combine lowers to a psum over the EP axis."""
    e = w_gate.shape[0]
    x = hidden.astype(w_gate.dtype)
    # [T, E] combine weights (0 for non-selected experts).
    # An id outside the held range [first_expert, first_expert + E) has
    # no column: its pair adds nothing.
    onehot = jax.nn.one_hot(
        expert_ids - first_expert, e, dtype=hidden.dtype
    )  # [T, k, E]
    combine = jnp.einsum("tk,tke->te", weights.astype(hidden.dtype), onehot)

    gate = jnp.einsum("td,edf->etf", x, w_gate)
    up = jnp.einsum("td,edf->etf", x, w_up)
    if biases is not None:
        gate = gate + biases[0][:, None, :]
        up = up + biases[1][:, None, :]
    act = act_fn(gate, up) if act_fn is not None else jax.nn.silu(gate) * up
    out = jnp.einsum("etf,efd->etd", act, w_down)  # [E, T, D]
    if biases is not None:
        out = out + biases[2][:, None, :]
    return jnp.einsum("etd,te->td", out, combine.astype(out.dtype))


def _grouped_moe(
    hidden: jnp.ndarray,
    w_gate: jnp.ndarray,
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    weights: jnp.ndarray,
    expert_ids: jnp.ndarray,
    *,
    interpret: bool = False,
    act_fn=None,
    biases=None,  # (b_gate [E,F], b_up [E,F], b_down [E,D]) or None
    held: tuple[int, int] | None = None,
    live: jnp.ndarray | None = None,  # [T] bool, tokens that are not padding
) -> jnp.ndarray:
    """Sort-by-expert + grouped matmul, ``_gmm`` (single-device fast path).

    One rule for the pairs that are NOT run: such a pair gets the id ``e``,
    sorts behind every group and is counted in no ``group_sizes``, so the
    kernel never visits its row, and the combine takes 0 for it. Two things
    make a pair one of these:

    - ``live``: its token is padding. A step's pad slots are routed like
      any token (the router is a dense matmul over ``T``), and what they
      hold is whatever the attention kernel left in its buffer (it writes
      no row past the live ones): run, they spread over most experts and
      have the kernel fetch the matrices of experts that no live token hit.
      They are counted nowhere and computed nowhere, and a pad token's
      output is exactly 0. ``None`` (a caller that knows of no padding)
      runs every token.
    - ``held = (first, count)``: of the groups ``w_*`` stack, only ``count``
      from ``first`` on can be routed to here (an expert-parallel share,
      ``expert_ids`` already moved into the stack's numbering), and its id
      lies outside."""
    t, d = hidden.shape
    e = w_gate.shape[0]
    k = expert_ids.shape[1]
    run = None  # [T, k] bool, None = every pair
    if held is not None:
        first, count = held
        run = (expert_ids >= first) & (expert_ids < first + count)
    if live is not None:
        run = live[:, None] if run is None else run & live[:, None]
    if run is not None:
        run = jnp.broadcast_to(run, expert_ids.shape)
        expert_ids = jnp.where(run, expert_ids, e)
    flat_experts = expert_ids.reshape(-1)  # [T*k]
    order = jnp.argsort(flat_experts)  # stable
    token_idx = order // k  # source token of each sorted slot
    x_sorted = hidden[token_idx]  # [T*k, D]
    group_sizes = jnp.bincount(flat_experts, length=e + 1)[:e].astype(
        jnp.int32
    )

    # gmm tiles rows by 128: pad the row dim. The pad rows lie past every
    # group, like the pairs that are not run, and the unsort below never
    # reads them.
    m = t * k
    m_pad = -(-m // 128) * 128
    if m_pad != m:
        x_sorted = jnp.pad(x_sorted, ((0, m_pad - m), (0, 0)))

    def mm(lhs, rhs, sizes):
        return _gmm(lhs, rhs, sizes, interpret)

    gate = mm(x_sorted, w_gate, group_sizes)
    up = mm(x_sorted, w_up, group_sizes)
    if biases is not None:
        # Per-row expert ids of the SORTED layout (rows past the groups
        # take the last expert's: their biased garbage is never read).
        sorted_e = jnp.minimum(flat_experts[order], e - 1)
        if m_pad != m:
            sorted_e = jnp.concatenate(
                [sorted_e, jnp.full(m_pad - m, e - 1, sorted_e.dtype)]
            )
        gate = gate + biases[0][sorted_e].astype(gate.dtype)
        up = up + biases[1][sorted_e].astype(up.dtype)
    act = (
        act_fn(gate, up) if act_fn is not None else jax.nn.silu(gate) * up
    ).astype(hidden.dtype)
    out_sorted = mm(act, w_down, group_sizes).astype(jnp.float32)  # [M, D]
    if biases is not None:
        out_sorted = out_sorted + biases[2][sorted_e].astype(jnp.float32)

    # Unsort and combine with routing weights. Rows the kernel did not run
    # hold whatever the buffer held, and so may a pad token's weights (its
    # input is no request's): the one place that keeps both out.
    inv = jnp.argsort(order)
    out = out_sorted[inv].reshape(t, k, d)
    weights = weights.astype(jnp.float32)
    if run is not None:
        out = jnp.where(run[:, :, None], out, 0.0)
        weights = jnp.where(run, weights, 0.0)
    return jnp.einsum("tkd,tk->td", out, weights).astype(hidden.dtype)


# The deepest matrix whose blocks hold the depth whole (the repo's kernel).
_GMM_WHOLE_DEPTH = 4096


def _gmm_tiling(k: int, n: int) -> tuple[int, int, int]:
    """Tiles ``(rows, depth, columns)`` of the grouped matmul over an
    expert's ``[k, n]`` matrix.

    megablox's default, (128, 128, 128), fetches a matrix in 32 KB pieces:
    at a few rows an expert the kernel is then bound by its own grid (16 x
    6 steps a visit of a 2048 x 768 expert; 9% of the byte roofline on the
    v5e, PERF.md PR 29). The depth goes in tiles of at most 2048. Up to
    ``_GMM_WHOLE_DEPTH`` a BLOCK holds them all (``ops/gmm_kernel.py``, for
    which the depth tile is only the order of the float32 sums) and as much
    width as 8 MiB of bf16 hold, in pieces that divide ``n``: every row
    tile re-reads its rows a column tile, so the fewer the better (PERF.md
    PR 54 has the sweep). A deeper matrix goes a depth tile a block of 4
    MiB (megablox, whose two buffers a block must fit the default scoped
    VMEM)."""
    def tile(dim: int, cap: int) -> int:
        if dim <= cap:
            return dim
        return next((t for t in range(cap // 128 * 128, 0, -128)
                     if dim % t == 0), 128)

    if k % 128 or n % 128:
        return (128, 128, 128)
    tk = tile(k, 2048)
    depth, block = (k, 8 << 20) if k <= _GMM_WHOLE_DEPTH else (tk, 4 << 20)
    return (128, tk, tile(n, max(128, block // (2 * depth))))


def _takes_gmm_kernel(k: int, n: int) -> bool:
    """Whether ``_gmm`` sends an expert's ``[k, n]`` matrix to the repo's
    kernel: whole tiles (the tests' tiny presets are not; of such a matrix
    ``_gmm_tiling``'s tiles are whole too) of a depth that a block holds
    whole."""
    return k % 128 == 0 and n % 128 == 0 and k <= _GMM_WHOLE_DEPTH


def _gmm(
    lhs: jnp.ndarray,  # [M, k] rows sorted by group, M a multiple of 128
    rhs: jnp.ndarray,  # [G, k, n]
    group_sizes: jnp.ndarray,  # [G] i32, sum may be < M
    interpret: bool = False,
) -> jnp.ndarray:
    """The grouped matmul at ``_gmm_tiling``'s tiles, float32 out: the
    repo's kernel (``ops/gmm_kernel.py``, which keeps the next expert's
    matrix in flight while a row tile revisits the one it holds) where a
    block holds the depth whole; megablox where it does not (a depth past
    4096, or the tests' tiny presets that are no whole tiles). The sums
    are the same bit for bit on either."""
    k, n = rhs.shape[1:]
    tiling = _gmm_tiling(k, n)
    if _takes_gmm_kernel(k, n):
        from vllm_tpu.ops import gmm_kernel

        return gmm_kernel.gmm(
            lhs, rhs, group_sizes, tk=tiling[1], tn=tiling[2],
            interpret=interpret)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    return gmm(
        lhs, rhs, group_sizes, preferred_element_type=jnp.float32,
        interpret=interpret, tiling=tiling,
    )


def gmm_column_tiles(hidden: int, intermediate: int) -> int:
    """Column tiles of a sparse layer's three grouped matmuls (gate, up,
    down) together, those that run the repo's kernel: what one (group, row
    tile) visit of ``gmm_kernel.count_visits`` is repeated over."""
    def tiles(k: int, n: int) -> int:
        return n // _gmm_tiling(k, n)[2] if _takes_gmm_kernel(k, n) else 0

    return 2 * tiles(hidden, intermediate) + tiles(intermediate, hidden)


def _excl_cumsum(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.concatenate([jnp.zeros((1,), x.dtype), jnp.cumsum(x)[:-1]])


def _local_grouped_experts(
    xs: jnp.ndarray,  # [M, D] rows sorted by local expert
    w_gate: jnp.ndarray,  # [El, D, F]
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,  # [El, F, D]
    group_sizes: jnp.ndarray,  # [El] i32, sum may be < M
    interpret: bool,
) -> jnp.ndarray:
    """Grouped-GEMM expert compute over expert-sorted rows.

    megablox ``gmm``'s grid is ``(tiles_n, num_active_tiles, tiles_k)`` with
    the tile count derived from ``group_sizes`` via scalar prefetch, so rows
    past ``sum(group_sizes)`` cost nothing (their output is uninitialized —
    callers must never read them)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, d = xs.shape
    f = w_gate.shape[2]
    # Row tile must divide m (callers round the buffer up to 128 rows);
    # k/n remainders are handled in-kernel.
    kw = dict(
        preferred_element_type=jnp.float32,
        interpret=interpret,
        tiling=(min(128, m), min(128, d), min(128, f)),
    )
    gate = gmm(xs, w_gate, group_sizes, **kw)
    up = gmm(xs, w_up, group_sizes, **kw)
    act = (jax.nn.silu(gate) * up).astype(xs.dtype)
    kw["tiling"] = (min(128, m), min(128, f), min(128, d))
    return gmm(act, w_down, group_sizes, **kw)


def ep_moe(
    hidden: jnp.ndarray,  # [T, D] (replicated over the ep axis)
    w_gate: jnp.ndarray,  # [E, D, F] (sharded over ep on dim 0)
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,  # [E, F, D]
    weights: jnp.ndarray,  # [T, k] f32 combine weights
    expert_ids: jnp.ndarray,  # [T, k] i32
    *,
    mesh,
    axis: str,
    interpret: bool = False,
    use_ragged_a2a: bool | None = None,
) -> jnp.ndarray:
    """Expert-parallel MoE: ragged all_to_all dispatch + grouped GEMM.

    The real EP formulation the reference builds in
    ``vllm/model_executor/layers/fused_moe/modular_kernel.py:181`` (prepare:
    route + permute + dispatch; experts: grouped GEMM; finalize: combine) and
    ``csrc/moe/moe_align_sum_kernels.cu`` (token alignment), done the TPU way:
    a ``shard_map`` manual region over the ``axis`` mesh axis where

    1. each device sorts its ``T/ep`` tokens' (token, k) pairs by global
       expert id (expert ownership is contiguous, so this is also sorted by
       destination device),
    2. per-destination counts are exchanged (``all_gather`` of an [ep] int
       vector) giving the full [src, dst] count matrix from which every
       ragged offset is derived,
    3. payload rows ride ``jax.lax.ragged_all_to_all`` (XLA's native ragged
       dispatch collective) to the expert owners — the CPU backend has no
       lowering for it, so tests swap in an exact all_gather emulation with
       identical offset math,
    4. received rows are sorted by local expert and hit the megablox grouped
       GEMM (dynamic ``num_active_tiles``: FLOPs track the *actual* token
       count, the worst-case static buffer costs memory only),
    5. results ride the reverse ragged all_to_all home and are combined with
       routing weights.

    Dropless: the receive buffer is worst-case sized (``T*k`` rows), so no
    capacity-factor token dropping — required for inference correctness.
    """
    ep = mesh.shape[axis]
    t, d = hidden.shape
    e = w_gate.shape[0]
    k = expert_ids.shape[1]
    if e % ep:
        raise ValueError(f"num_experts {e} not divisible by ep size {ep}")
    el = e // ep
    if use_ragged_a2a is None:
        use_ragged_a2a = jax.default_backend() == "tpu"

    # Pad tokens to a multiple of ep (pad rows route to expert 0, weight 0).
    t_pad = -(-t // ep) * ep
    if t_pad != t:
        hidden = jnp.pad(hidden, ((0, t_pad - t), (0, 0)))
        weights = jnp.pad(weights, ((0, t_pad - t), (0, 0)))
        expert_ids = jnp.pad(expert_ids, ((0, t_pad - t), (0, 0)))
    # Worst case: every pair routes to one device. Rounded up to the gmm
    # row tile; extra slots look like unreceived pads (sentinel expert id).
    cap = t_pad * k
    if cap > 128:
        cap = -(-cap // 128) * 128

    def local_fn(x, wg, wu, wd, w, ids):
        my = jax.lax.axis_index(axis)
        tl = x.shape[0]
        flat = ids.reshape(-1)  # [tl*k] global expert ids
        order = jnp.argsort(flat, stable=True)
        x_send = x[order // k]
        # One [E]-int all_gather carries ALL dispatch metadata: every chunk
        # is expert-sorted, so receivers reconstruct per-row expert ids and
        # group sizes from counts alone — no id payload collective.
        expert_counts = jnp.bincount(flat, length=e).astype(jnp.int32)
        g_ec = jax.lax.all_gather(expert_counts, axis)  # [src, E]
        cm = g_ec.reshape(ep, ep, el).sum(-1)  # [src, dst] pair counts
        send_counts = cm[my]
        recv_counts = cm[:, my]
        # row_excl[s, d]: offset of the chunk for d in s's send buffer;
        # col_excl[s, d]: offset of s's chunk in d's receive buffer. The
        # four ragged-a2a offset vectors are rows/columns of these.
        row_excl = jnp.concatenate(
            [jnp.zeros((ep, 1), jnp.int32), jnp.cumsum(cm, 1)[:, :-1]], 1
        )
        col_excl = jnp.concatenate(
            [jnp.zeros((1, ep), jnp.int32), jnp.cumsum(cm, 0)[:-1]], 0
        )

        # Per-source counts for MY experts; their row-cumsum recovers each
        # received row's local expert id below.
        my_counts = jax.lax.dynamic_slice(g_ec, (0, my * el), (ep, el))
        my_cumsum = jnp.cumsum(my_counts, axis=1)  # [src, el]
        total_recv = jnp.sum(recv_counts)
        j = jnp.arange(cap)
        src = jnp.clip(
            jnp.searchsorted(jnp.cumsum(recv_counts), j, side="right"),
            0, ep - 1,
        )
        p = j - col_excl[src, my]  # position within src's chunk
        valid = j < total_recv
        local_eid = jnp.where(
            valid, jnp.sum(p[:, None] >= my_cumsum[src], axis=1), el
        )
        group_sizes = jnp.sum(my_counts, axis=0)  # [el]

        if use_ragged_a2a:
            xr = jax.lax.ragged_all_to_all(
                x_send, jnp.zeros((cap, d), x.dtype),
                row_excl[my], send_counts, col_excl[my], recv_counts,
                axis_name=axis,
            )
        else:
            # Exact emulation for backends without the primitive: gather
            # everything, assemble my receive buffer with the same layout.
            g_x = jax.lax.all_gather(x_send, axis)  # [ep, tl*k, d]
            pos = jnp.clip(row_excl[src, my] + p, 0, tl * k - 1)
            xr = jnp.where(valid[:, None], g_x[src, pos], 0)

        # Local alignment: sort received rows by local expert (pads sort
        # last via the sentinel id ``el``), grouped GEMM, unsort.
        lorder = jnp.argsort(local_eid, stable=True)
        xs = xr[lorder]
        ys = _local_grouped_experts(
            xs, wg, wu, wd, group_sizes, interpret
        ).astype(x.dtype)
        y_unsorted = jnp.zeros_like(ys).at[lorder].set(ys)

        if use_ragged_a2a:
            y_back = jax.lax.ragged_all_to_all(
                y_unsorted, jnp.zeros((tl * k, d), x.dtype),
                col_excl[:, my], recv_counts, row_excl[:, my], send_counts,
                axis_name=axis,
            )
        else:
            g_y = jax.lax.all_gather(y_unsorted, axis)  # [ep, cap, d]
            jj = jnp.arange(tl * k)
            dst = jnp.clip(
                jnp.searchsorted(jnp.cumsum(send_counts), jj, side="right"),
                0, ep - 1,
            )
            y_back = g_y[dst, col_excl[my, dst] + (jj - row_excl[my, dst])]

        y_flat = jnp.zeros_like(y_back).at[order].set(y_back)
        y_pairs = y_flat.reshape(tl, k, d).astype(jnp.float32)
        return jnp.einsum("tkd,tk->td", y_pairs, w).astype(x.dtype)

    from jax.sharding import PartitionSpec as P

    from vllm_tpu.parallel.mesh import shard_map

    out = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(axis, None), P(axis, None, None), P(axis, None, None),
            P(axis, None, None), P(axis, None), P(axis, None),
        ),
        out_specs=P(axis, None),
        axis_names=frozenset({axis}),
        # pallas_call (gmm) does not annotate varying-mesh-axes metadata;
        # skip the vma check rather than thread vma through the kernel.
        check_vma=False,
    )(hidden, w_gate, w_up, w_down, weights, expert_ids)
    return out[:t]


def fused_experts(
    hidden: jnp.ndarray,  # [T, D]
    w_gate: jnp.ndarray,  # [E, D, F]
    w_up: jnp.ndarray,  # [E, D, F]
    w_down: jnp.ndarray,  # [E, F, D]
    weights: jnp.ndarray,  # [T, k] f32 combine weights
    expert_ids: jnp.ndarray,  # [T, k] i32
    use_grouped: bool | None = None,
    *,
    ep_mesh=None,
    ep_axis: str | None = None,
    act_fn=None,
    biases=None,
    first_expert: int | None = None,
    live: jnp.ndarray | None = None,  # [T] bool, tokens that are not padding
) -> jnp.ndarray:
    """Experts + combine for pre-computed routing (custom gating schemes —
    DeepSeek group-limited / sigmoid-bias routing — share the expert
    compute). ``use_grouped=None`` auto-selects the megablox path on
    single-device TPU, dense one-hot otherwise. With ``ep_mesh``/``ep_axis``
    set (and axis size > 1) the ragged all_to_all expert-parallel path is
    taken instead. ``act_fn(gate, up)`` overrides the silu GLU and
    ``biases`` adds per-expert (gate, up, down) biases (GPT-OSS).

    ``first_expert``: this layer HOLDS the experts ``[first_expert,
    first_expert + E)`` of a router that is wider than ``E`` (one chip's
    share of an expert-parallel deployment, without its exchange). The
    ids stay the router's; pairs routed to experts held elsewhere are
    dropped before the expert compute and add nothing to the result,
    which is this share's part of the layer's sum.

    ``live``: the step's tokens that are not padding. The grouped path
    runs no pair of the others and gives them exactly 0; the paths that
    compute every row whatever is live (dense, expert-parallel) ignore
    it. ``None`` = every token is live."""
    if first_expert is not None and ep_mesh is not None:
        raise NotImplementedError(
            "an expert share is one device's; a mesh shards the experts "
            "itself (ep_moe)"
        )
    if ep_mesh is not None and ep_axis and ep_mesh.shape[ep_axis] > 1:
        if act_fn is not None or biases is not None:
            raise NotImplementedError(
                "expert-parallel path does not support custom activations "
                "or per-expert biases yet (GPT-OSS runs ep=1)"
            )
        from vllm_tpu import envs

        return ep_moe(
            hidden, w_gate, w_up, w_down, weights, expert_ids,
            mesh=ep_mesh, axis=ep_axis,
            interpret=(
                envs.VLLM_TPU_PALLAS_INTERPRET
                or jax.default_backend() != "tpu"
            ),
        )
    if use_grouped is None:
        use_grouped = _grouped_is_the_path()
    if use_grouped:
        share = first_expert is not None
        return _grouped_moe(
            hidden, w_gate, w_up, w_down, weights,
            expert_ids - first_expert if share else expert_ids,
            act_fn=act_fn, biases=biases,
            held=(0, w_gate.shape[0]) if share else None, live=live,
        )
    return _dense_moe(
        hidden, w_gate, w_up, w_down, weights, expert_ids,
        act_fn=act_fn, biases=biases, first_expert=first_expert or 0,
    )


def _grouped_is_the_path() -> bool:
    """Grouped megablox is the single-device fast path; under a multi-
    device mesh the dense one-hot path is the GSPMD/EP formulation."""
    return jax.default_backend() == "tpu" and jax.device_count() == 1


def fused_experts_of_layer(
    hidden: jnp.ndarray,  # [T, D]
    w_gate: jnp.ndarray,  # [L, E, D, F]: the whole stack of the layer scan
    w_up: jnp.ndarray,  # [L, E, D, F]
    w_down: jnp.ndarray,  # [L, E, F, D]
    layer: jnp.ndarray,  # scalar i32
    weights: jnp.ndarray,  # [T, k] f32 combine weights
    expert_ids: jnp.ndarray,  # [T, k] i32, experts of this layer
    *,
    ep_mesh=None,
    ep_axis: str | None = None,
    first_expert: int | None = None,
    live: jnp.ndarray | None = None,  # [T] bool, tokens that are not padding
) -> jnp.ndarray:
    """``fused_experts`` for a layer scan that holds its expert weights as
    ``[L, E, ...]`` stacks. On the grouped (megablox) path the stacks go to
    the kernel WHOLE, as ``L * E`` groups of which only this layer's are
    non-empty: a Pallas call cannot read a slice of its operand in place,
    so handing it ``stack[layer]`` makes XLA copy the layer's experts into a
    temporary every layer of every step (1.2 GB at 128 experts of 2048 x
    768, read and written, before the kernel reads them again). A group
    that holds no rows is never visited, so nothing else of the stack is
    read. The other paths index the stack and fuse the slice as before."""
    expert_parallel = (
        ep_mesh is not None and ep_axis and ep_mesh.shape[ep_axis] > 1
    )
    if expert_parallel or not _grouped_is_the_path():
        return fused_experts(
            hidden, w_gate[layer], w_up[layer], w_down[layer], weights,
            expert_ids, ep_mesh=ep_mesh, ep_axis=ep_axis,
            first_expert=first_expert, live=live,
        )
    n_layers, e = w_gate.shape[:2]
    share = first_expert is not None
    return _grouped_moe(
        hidden,
        w_gate.reshape(n_layers * e, *w_gate.shape[2:]),
        w_up.reshape(n_layers * e, *w_up.shape[2:]),
        w_down.reshape(n_layers * e, *w_down.shape[2:]),
        weights, expert_ids - (first_expert or 0) + layer * e,
        # Ids below the share's first fall into the layer before: the
        # held range keeps them out.
        held=(layer * e, e) if share else None, live=live,
    )


def fused_moe(
    hidden: jnp.ndarray,  # [T, D]
    router_weight: jnp.ndarray,  # [D, E]
    w_gate: jnp.ndarray,  # [E, D, F]
    w_up: jnp.ndarray,  # [E, D, F]
    w_down: jnp.ndarray,  # [E, F, D]
    top_k: int,
    renormalize: bool = True,
    use_grouped: bool | None = None,
) -> jnp.ndarray:
    """Router + experts + combine (softmax top-k routing)."""
    router_logits = hidden.astype(jnp.float32) @ router_weight.astype(jnp.float32)
    weights, expert_ids = select_experts(router_logits, top_k, renormalize)
    return fused_experts(
        hidden, w_gate, w_up, w_down, weights, expert_ids, use_grouped
    )


def share_of_sparse_layer(
    h2: jnp.ndarray,  # [T, D] normed layer input
    lp: dict,  # router [D, E_all], router_bias [E_all] | absent, ws_* shared
    experts: dict,  # we_gate, we_up, we_down: [E, ...] or a [L, E, ...] stack
    row,  # the layer's row in a stack, None where ``experts`` is one layer's
    live: jnp.ndarray,  # [T] bool, tokens that are not padding
    *,
    select: dict,  # select_experts_grouped's keywords
    num_experts: int,  # experts HELD here
    first_expert: int | None,  # the first of them in the router, None = all
    shared: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """A sparse layer as the models that serve one chip's share of an
    expert-parallel deployment run it (``ep_size`` / ``ep_rank`` in
    ``config.json``): the router over ALL its outputs, the held experts'
    part of the routed sum, the shared expert where there is one, and the
    live tokens routed to each held expert ``[E]``."""
    with jax.named_scope("moe_router"):
        logits = h2.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
        weights, ids = select_experts_grouped(
            logits, lp.get("router_bias"), **select)
    local = ids - (first_expert or 0)
    counts = jnp.zeros(num_experts, jnp.int32).at[
        local.reshape(-1)
    ].add(
        jnp.broadcast_to(live[:, None], ids.shape).reshape(-1)
        .astype(jnp.int32) * (local.reshape(-1) >= 0),
        mode="drop",
    )
    with jax.named_scope("moe_experts"):
        if row is None:
            out = fused_experts(
                h2, experts["we_gate"], experts["we_up"],
                experts["we_down"], weights, ids,
                first_expert=first_expert, live=live,
            )
        else:
            out = fused_experts_of_layer(
                h2, experts["we_gate"], experts["we_up"],
                experts["we_down"], row, weights, ids,
                first_expert=first_expert, live=live,
            )
    if shared:
        with jax.named_scope("moe_shared"):
            gate_up = jnp.concatenate(
                [h2 @ lp["ws_gate"], h2 @ lp["ws_up"]], -1
            )
            out = out + silu_and_mul(gate_up) @ lp["ws_down"]
    return out, counts
