"""Gated delta net (GDN) recurrence over the ragged token batch.

Reference analog: ``vllm/v1/attention/backends/gdn_attn.py`` + the FLA
``chunk_gated_delta_rule`` kernels (HF slow path:
``modeling_qwen3_next.torch_recurrent_gated_delta_rule``). The state is
a per-(v-head) MATRIX ``S [dk, dv]`` updated by a gated delta rule:

    S_t   = exp(g_t) * S_{t-1}
    mem_t = k_t . S_t                       (readout of k's memory)
    S_t  += k_t (x) beta_t (v_t - mem_t)    (delta correction)
    y_t   = q_t . S_t

Unlike Mamba's diagonal decays this update is rank-1-plus-scale on a
matrix, so the one-shot associative-scan trick does not apply; the
correctness-first formulation here is a sequential ``lax.scan`` over
the flat ragged batch with per-request state seeding at segment starts
(the chunked WY formulation is the optimization seam, same role the
FLA chunk kernels play on CUDA).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(
        jnp.sum(xf * xf, axis=-1, keepdims=True) + eps
    )


def ragged_gated_delta_rule(
    q: jnp.ndarray,  # [T, Hv, Dk] (already repeated to v-heads)
    k: jnp.ndarray,  # [T, Hv, Dk]
    v: jnp.ndarray,  # [T, Hv, Dv]
    g: jnp.ndarray,  # [T, Hv] log-decay (<= 0), or [T, Hv, Dk] a key channel
    beta: jnp.ndarray,  # [T, Hv] in (0, 1)
    h0: jnp.ndarray,  # [R, Hv, Dk, Dv] cached state per request
    token_req_idx: jnp.ndarray,  # [T]
    query_start_loc: jnp.ndarray,  # [R+1]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns ``(y [T, Hv, Dv], new_state [R, Hv, Dk, Dv])``.

    q/k are l2-normalized and q is scaled by ``Dk**-0.5`` inside (the
    HF ``use_qk_l2norm_in_kernel=True`` semantics)."""
    t, hv, dk = q.shape
    dv = v.shape[-1]
    r = h0.shape[0]

    qf = l2norm(q) * (dk ** -0.5)
    kf = l2norm(k)
    vf = v.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    bf = beta.astype(jnp.float32)

    ts = jnp.arange(t, dtype=jnp.int32)
    is_first = ts == query_start_loc[jnp.clip(token_req_idx, 0, r)]
    is_last = ts == query_start_loc[
        jnp.clip(token_req_idx, 0, r) + 1
    ] - 1
    h0f = h0.astype(jnp.float32)

    def step(carry, inp):
        s, states = carry  # s [Hv, Dk, Dv], states [R, Hv, Dk, Dv]
        q_t, k_t, v_t, g_t, b_t, first, last, rid = inp
        s = jnp.where(first, h0f[rid], s)
        s = s * jnp.exp(g_t).reshape(hv, -1, 1)
        mem = jnp.einsum("hk,hkv->hv", k_t, s)
        delta = (v_t - mem) * b_t[:, None]
        s = s + k_t[:, :, None] * delta[:, None, :]
        y_t = jnp.einsum("hk,hkv->hv", q_t, s)
        states = jax.lax.cond(
            last,
            lambda st: st.at[rid].set(s),
            lambda st: st,
            states,
        )
        return (s, states), y_t

    (_, states), y = jax.lax.scan(
        step,
        (jnp.zeros((hv, dk, dv), jnp.float32), h0f),
        (qf, kf, vf, gf, bf, is_first, is_last,
         jnp.clip(token_req_idx, 0, r - 1)),
    )
    return y.astype(v.dtype), states.astype(h0.dtype)
