"""Fused sort-free sampling kernel: exact equivalence vs the XLA
reference path, sorted-formulation oracles, and dispatcher routing.

Three layers of evidence (SURVEY.md §4 tiering):

1. Bit-exactness: the Pallas kernel (interpret mode on CPU) and the XLA
   reference (``sample/sampler.py:sample``) share the same primitive
   functions, so their sampled tokens must be IDENTICAL — across every
   static flag combination and kernel block shape, on an odd
   (non-128-aligned) vocab.
2. Semantic oracles vs the classical sorted formulations — these catch
   bugs the cross-path exactness tests can't (both paths share the
   primitives, so a shared bug cancels out).
3. Dispatcher routing: eligibility rules, escape hatches, and the
   all-greedy design decision (XLA argmax, not a kernel launch).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import vllm_tpu.envs as envs
from vllm_tpu.ops import sampler_kernel as _sk
from vllm_tpu.sample.sampler import (
    SamplingMetadata,
    _mask_top_k,
    _mask_top_p_min_p,
    dispatch_sample,
    sample,
    sampler_kernel_eligible,
)

V_ODD = 333  # exercises the -inf pad up to the pow2 width (512)
# Past the widest power of two: 33 noise tiles (135168 lanes), the sums in
# the kernel's tile order, 2763 lanes of -inf pad.
V_WIDE = _sk.MAX_POW2_WIDTH + 333


def _kernel_sum(vocab):
    """The order of summation the kernel uses at this vocabulary's width,
    for the reference to share (the halving tree where it is the default)."""
    return _sk.kernel_sum_fn(_sk.compute_width(vocab))


@pytest.fixture(autouse=True)
def _fresh_env_cache():
    """envs caches on first read; tests that mutate os.environ need a
    clean slate on both sides."""
    envs.refresh()
    yield
    envs.refresh()


def _make_md(
    rows: int,
    vocab: int,
    *,
    temperature=None,
    top_k=None,
    top_p=None,
    min_p=None,
    repetition_penalty=None,
    frequency_penalty=None,
    presence_penalty=None,
    seeds=None,
    counts=None,
    prompt_mask=None,
) -> SamplingMetadata:
    def arr(x, default, dtype=jnp.float32):
        if x is None:
            return jnp.full((rows,), default, dtype)
        return jnp.asarray(x, dtype)

    if seeds is None:
        seeds = np.stack(
            [np.arange(1, rows + 1), np.arange(101, rows + 101)], axis=1
        )
    if counts is None:
        counts = jnp.zeros((rows, vocab), jnp.int32)
    if prompt_mask is None:
        prompt_mask = jnp.zeros((rows, vocab), jnp.bool_)
    return SamplingMetadata(
        temperature=arr(temperature, 1.0),
        top_k=arr(top_k, 0, jnp.int32),
        top_p=arr(top_p, 1.0),
        min_p=arr(min_p, 0.0),
        presence_penalty=arr(presence_penalty, 0.0),
        frequency_penalty=arr(frequency_penalty, 0.0),
        repetition_penalty=arr(repetition_penalty, 1.0),
        prng_keys=jnp.asarray(seeds, jnp.uint32),
        output_token_counts=counts,
        prompt_token_mask=prompt_mask,
    )


def _mixed_batch(rng, vocab, with_penalties):
    """Six rows covering greedy, plain temperature, top-k, top-p, min-p,
    and everything-at-once."""
    rows = 6
    logits = jnp.asarray(
        rng.standard_normal((rows, vocab)) * 3.0, jnp.float32
    )
    kw = dict(
        temperature=[0.0, 1.0, 0.7, 1.3, 0.9, 0.8],
        top_k=[0, 0, 3, 0, 0, 7],
        top_p=[1.0, 1.0, 1.0, 0.8, 1.0, 0.9],
        min_p=[0.0, 0.0, 0.0, 0.0, 0.05, 0.02],
    )
    if with_penalties:
        counts = (rng.integers(0, 3, size=(rows, vocab)) *
                  (rng.random((rows, vocab)) < 0.05)).astype(np.int32)
        pmask = rng.random((rows, vocab)) < 0.05
        kw.update(
            repetition_penalty=[1.0, 1.2, 1.0, 1.5, 1.0, 1.1],
            frequency_penalty=[0.0, 0.3, 0.0, 0.0, 0.2, 0.1],
            presence_penalty=[0.0, 0.0, 0.4, 0.0, 0.0, 0.2],
            counts=jnp.asarray(counts),
            prompt_mask=jnp.asarray(pmask),
        )
    return logits, _make_md(rows, vocab, **kw)


# ---------------------------------------------------------------------------
# 1. Kernel vs reference bit-exactness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("vocab", [V_ODD, V_WIDE])
@pytest.mark.parametrize("return_prob", [False, True])
@pytest.mark.parametrize("needs_penalties", [False, True])
@pytest.mark.parametrize("needs_top_k", [False, True])
@pytest.mark.parametrize("needs_top_p_min_p", [False, True])
def test_kernel_matches_reference(
    needs_penalties, needs_top_k, needs_top_p_min_p, return_prob, vocab
):
    rng = np.random.default_rng(
        7 + needs_penalties * 4 + needs_top_k * 2 + needs_top_p_min_p
    )
    logits, md = _mixed_batch(rng, vocab, needs_penalties)
    flags = dict(
        needs_penalties=needs_penalties,
        needs_top_k=needs_top_k,
        needs_top_p_min_p=needs_top_p_min_p,
        needs_gumbel=True,
        return_prob=return_prob,
    )
    want = sample(logits, md, sum_fn=_kernel_sum(vocab), **flags)
    use_kernel, interpret = sampler_kernel_eligible(
        vocab, needs_gumbel=True, allow_interpret=True
    )
    assert use_kernel and interpret, "conftest arms interpret mode"
    got = dispatch_sample(logits, md, allow_interpret=True, **flags)
    # Tokens, the raw logprobs (pre-masking in both paths) and, asked for,
    # the drawn tokens' probabilities: bit for bit.
    assert len(got) == len(want) == 2 + return_prob
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if return_prob:
        assert np.all(np.asarray(got[2]) > 0)


def _pack_params(md: SamplingMetadata):
    params_f = jnp.pad(
        jnp.stack(
            [md.temperature, md.top_p, md.min_p, md.repetition_penalty,
             md.frequency_penalty, md.presence_penalty],
            axis=1,
        ),
        ((0, 0), (0, 122)),
    )
    keys_i = jax.lax.bitcast_convert_type(
        md.prng_keys.astype(jnp.uint32), jnp.int32
    )
    params_i = jnp.pad(
        jnp.stack(
            [md.top_k.astype(jnp.int32), keys_i[:, 0], keys_i[:, 1]],
            axis=1,
        ),
        ((0, 0), (0, 125)),
    )
    return params_f, params_i


def _fused(logits, md, **kw):
    params_f, params_i = _pack_params(md)
    return _sk.fused_sample(
        logits, params_f, params_i,
        md.output_token_counts.astype(jnp.int32),
        md.prompt_token_mask.astype(jnp.int8),
        needs_penalties=True, needs_top_k=True, needs_top_p_min_p=True,
        interpret=True, **kw,
    )


@pytest.mark.parametrize("row_block,logits_tile,vocab", [
    (2, 256, V_ODD), (8, 128, V_ODD), (3, 384, V_ODD),
    # 17 and 5 tiles a row, the last one partial.
    (2, 8192, V_WIDE), (8, 32768, V_WIDE),
])
def test_kernel_block_shape_invariance(row_block, logits_tile, vocab):
    """Tiling must not change a single sampled token (or its probability)
    — the DMA tile loop and row padding are pure layout."""
    rng = np.random.default_rng(17)
    logits, md = _mixed_batch(rng, vocab, True)
    want, _, want_p = sample(
        logits, md, needs_penalties=True, return_prob=True,
        sum_fn=_kernel_sum(vocab),
    )
    got = _fused(logits, md, row_block=row_block, logits_tile=logits_tile)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got, got_p = _fused(logits, md, row_block=row_block,
                        logits_tile=logits_tile, return_prob=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))


@pytest.mark.parametrize("num_active", [0, 1, 2, 3, 5, 6])
def test_rows_past_the_active_count_are_zeros(num_active):
    """Nobody reads the rows at or past the count: a row block wholly past
    it is skipped, the one that straddles it is cut, and the rows before it
    are what the full call gives."""
    rng = np.random.default_rng(19)
    logits, md = _mixed_batch(rng, V_ODD, True)
    full, full_p = _fused(logits, md, row_block=2, logits_tile=256,
                          return_prob=True)
    assert np.all(np.asarray(full_p) > 0)
    got, got_p = _fused(logits, md, row_block=2, logits_tile=256,
                        return_prob=True,
                        num_active=jnp.asarray(num_active, jnp.int32))
    live = np.arange(6) < num_active
    np.testing.assert_array_equal(
        np.asarray(got), np.where(live, np.asarray(full), 0))
    np.testing.assert_array_equal(
        np.asarray(got_p), np.where(live, np.asarray(full_p), 0))
    only = _fused(logits, md, row_block=2, logits_tile=256,
                  num_active=jnp.asarray(num_active, jnp.int32))
    np.testing.assert_array_equal(np.asarray(only), np.asarray(got))


def test_dispatch_hands_the_active_count_to_the_kernel():
    rng = np.random.default_rng(21)
    logits, md = _mixed_batch(rng, V_ODD, False)
    full = dispatch_sample(logits, md, allow_interpret=True, return_prob=True)
    got = dispatch_sample(logits, md, allow_interpret=True, return_prob=True,
                          num_active=jnp.asarray(3, jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(got[0]), np.where(np.arange(6) < 3, np.asarray(full[0]), 0))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(full[1]))
    np.testing.assert_array_equal(
        np.asarray(got[2]), np.where(np.arange(6) < 3, np.asarray(full[2]), 0))
    # The reference path samples every row all the same.
    ref = dispatch_sample(logits, md, enable_kernel=False, return_prob=True,
                          num_active=jnp.asarray(3, jnp.int32))
    for g, w in zip(ref, full):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_wide_sums_one_fixed_order_close_to_the_fused_pass():
    """``tiled_row_sum`` is the wide widths' fixed order: the noise tiles
    added lane for lane, then the halving tree. The XLA epilogue's
    ``plain_sum`` there differs from it in the last bits at most."""
    rng = np.random.default_rng(61)
    x = jnp.asarray(rng.random((3, 5 * 4096)), jnp.float32)
    tiles = np.asarray(x).reshape(3, 5, 4096)
    acc = tiles[:, 0]
    for i in range(1, 5):
        acc = acc + tiles[:, i]
    np.testing.assert_array_equal(
        np.asarray(_sk.tiled_row_sum(x)), np.asarray(_sk.row_sum(acc)))
    np.testing.assert_allclose(
        np.asarray(_sk.tiled_row_sum(x)), np.asarray(_sk.plain_sum(x)),
        rtol=1e-6)
    assert _sk.kernel_sum_fn(131072) is _sk.row_sum
    assert _sk.kernel_sum_fn(135168) is _sk.tiled_row_sum


def test_seeded_determinism_and_row_position_invariance():
    """A (seed, logits) pair samples the same token regardless of where
    its row sits in the batch, and across repeated calls — the per-row
    counter-based stream has no batch state."""
    rng = np.random.default_rng(23)
    logits, md = _mixed_batch(rng, V_ODD, False)
    a, _ = dispatch_sample(logits, md, allow_interpret=True)
    b, _ = dispatch_sample(logits, md, allow_interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    perm = np.asarray([3, 0, 5, 1, 4, 2])
    import dataclasses

    md_p = SamplingMetadata(
        **{
            f.name: getattr(md, f.name)[perm]
            for f in dataclasses.fields(SamplingMetadata)
        }
    )
    c, _ = dispatch_sample(logits[perm], md_p, allow_interpret=True)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(a)[perm])


def test_greedy_rows_match_argmax():
    rng = np.random.default_rng(29)
    logits = jnp.asarray(rng.standard_normal((4, V_ODD)), jnp.float32)
    md = _make_md(4, V_ODD, temperature=[0.0, 0.0, 1.0, 0.0])
    got, _ = dispatch_sample(logits, md, allow_interpret=True)
    want = np.argmax(np.asarray(logits), axis=-1)
    got = np.asarray(got)
    for r in (0, 1, 3):
        assert got[r] == want[r]


def test_sampled_tokens_respect_truncation():
    """Every sampled token must come from its row's allowed set."""
    rng = np.random.default_rng(31)
    logits = jnp.asarray(rng.standard_normal((8, V_ODD)) * 2, jnp.float32)
    md = _make_md(
        8, V_ODD,
        temperature=[0.9] * 8,
        top_k=[3] * 4 + [0] * 4,
        top_p=[1.0] * 4 + [0.5] * 4,
        seeds=np.stack([np.arange(8) + 5, np.arange(8) + 55], axis=1),
    )
    got = np.asarray(dispatch_sample(logits, md, allow_interpret=True)[0])
    scaled = np.asarray(logits) / 0.9
    for r in range(4):  # top-k rows
        top3 = np.argsort(scaled[r])[::-1][:3]
        assert got[r] in top3
    probs = np.exp(scaled - scaled.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    for r in range(4, 8):  # top-p rows: token inside the nucleus
        order = np.argsort(probs[r])[::-1]
        csum = np.cumsum(probs[r][order])
        nucleus = set(order[: int(np.searchsorted(csum, 0.5) + 1)].tolist())
        assert got[r] in nucleus


# ---------------------------------------------------------------------------
# 2. Sorted-formulation oracles (independent of the shared primitives)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 2, 7, 100, V_ODD, 0])
def test_top_k_matches_sorted_oracle(k):
    rng = np.random.default_rng(37 + k)
    logits = jnp.asarray(rng.standard_normal((5, V_ODD)) * 4, jnp.float32)
    got = np.asarray(_mask_top_k(logits, jnp.full((5,), k, jnp.int32)))
    x = np.asarray(logits)
    for r in range(5):
        if k == 0 or k >= V_ODD:
            np.testing.assert_array_equal(got[r], x[r])
            continue
        kth = np.sort(x[r])[::-1][k - 1]
        keep = x[r] >= kth  # ties with the k-th value are kept
        np.testing.assert_array_equal(got[r][keep], x[r][keep])
        assert np.all(got[r][~keep] <= _sk.MASK_VALUE)


@pytest.mark.parametrize("top_p", [0.1, 0.5, 0.9, 1.0])
def test_top_p_matches_sorted_oracle(top_p):
    """Kept set is upward-closed in probability, reaches the target mass,
    and is minimal (dropping its lightest weight class goes below)."""
    rng = np.random.default_rng(41)
    logits = jnp.asarray(rng.standard_normal((6, V_ODD)) * 3, jnp.float32)
    got = np.asarray(
        _mask_top_p_min_p(logits, jnp.full((6,), top_p, jnp.float32),
                          jnp.zeros((6,), jnp.float32))
    )
    x = np.asarray(logits, np.float64)
    p = np.exp(x - x.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    for r in range(6):
        keep = got[r] > _sk.MASK_VALUE
        if top_p >= 1.0:
            assert keep.all()
            continue
        assert keep.any()
        # upward-closed: every kept prob >= every dropped prob
        assert p[r][keep].min() >= p[r][~keep].max() - 1e-12
        mass = p[r][keep].sum()
        assert mass >= top_p * (1 - 1e-5)
        # minimal: removing the lightest kept weight class undershoots
        wmin = p[r][keep].min()
        assert mass - p[r][np.isclose(p[r], wmin) & keep].sum() < top_p

    # Degenerate nucleus: top_p -> 0 keeps exactly the argmax.
    tiny = np.asarray(
        _mask_top_p_min_p(logits, jnp.full((6,), 1e-6, jnp.float32),
                          jnp.zeros((6,), jnp.float32))
    )
    for r in range(6):
        keep = tiny[r] > _sk.MASK_VALUE
        assert keep.sum() == 1 and np.argmax(x[r]) == np.argmax(keep)


def test_min_p_matches_reference_rule():
    """min-p keeps token t iff p(t) >= min_p * max_p — exact in weight
    space because the row max weight is exactly 1.0."""
    rng = np.random.default_rng(43)
    logits = jnp.asarray(rng.standard_normal((5, V_ODD)) * 3, jnp.float32)
    min_p = 0.04
    got = np.asarray(
        _mask_top_p_min_p(logits, jnp.ones((5,), jnp.float32),
                          jnp.full((5,), min_p, jnp.float32))
    )
    x = np.asarray(logits)
    w = np.exp((x - x.max(-1, keepdims=True)).astype(np.float32))
    for r in range(5):
        keep = got[r] > _sk.MASK_VALUE
        # Ignore tokens within float rounding of the threshold.
        margin = np.abs(w[r] - min_p) > 1e-6
        np.testing.assert_array_equal(
            keep[margin], (w[r] >= min_p)[margin]
        )


def test_penalties_match_hf_semantics():
    rng = np.random.default_rng(47)
    rows, v = 3, 50
    logits = jnp.asarray(rng.standard_normal((rows, v)), jnp.float32)
    counts = rng.integers(0, 3, size=(rows, v)).astype(np.int32)
    pmask = rng.random((rows, v)) < 0.2
    rep, freq, pres = 1.3, 0.25, 0.5
    got = np.asarray(
        _sk.penalize_block(
            logits, jnp.asarray(counts), jnp.asarray(pmask),
            jnp.full((rows, 1), rep), jnp.full((rows, 1), freq),
            jnp.full((rows, 1), pres),
        )
    )
    x = np.asarray(logits)
    seen = (counts > 0) | pmask
    want = np.where(seen & (x > 0), x / rep, np.where(seen, x * rep, x))
    want = want - freq * counts
    want = want - pres * (counts > 0)
    np.testing.assert_allclose(got, want.astype(np.float32), rtol=1e-6)


def test_sampling_distribution_matches_softmax():
    """Empirical sampling frequencies over many independent seeds track
    the softmax distribution (the Gumbel-argmax correctness check)."""
    rng = np.random.default_rng(53)
    v, n = 16, 4096
    row = rng.standard_normal(v).astype(np.float32)
    logits = jnp.asarray(np.broadcast_to(row, (n, v)).copy())
    seeds = np.stack(
        [np.arange(1, n + 1), np.full(n, 777)], axis=1
    )
    md = _make_md(n, v, seeds=seeds)
    got = np.asarray(sample(logits, md)[0])
    emp = np.bincount(got, minlength=v) / n
    p = np.exp(row - row.max())
    p /= p.sum()
    assert np.abs(emp - p).max() < 0.03


# ---------------------------------------------------------------------------
# 3. Dispatcher routing and escape hatches
# ---------------------------------------------------------------------------


def test_eligible_interpret_on_cpu():
    use, interp = sampler_kernel_eligible(
        V_ODD, needs_gumbel=True, allow_interpret=True
    )
    assert use and interp


def test_not_eligible_without_interpret_on_cpu():
    assert sampler_kernel_eligible(4096, needs_gumbel=True) == (False, False)


def test_all_greedy_is_not_kernel_work():
    assert sampler_kernel_eligible(
        4096, needs_gumbel=False, allow_interpret=True
    ) == (False, False)


def test_knob_disables_kernel():
    assert sampler_kernel_eligible(
        4096, needs_gumbel=True, enable_kernel=False, allow_interpret=True
    ) == (False, False)


def test_env_escape_hatch(monkeypatch):
    monkeypatch.setenv("VLLM_TPU_DISABLE_SAMPLER_KERNEL", "1")
    envs.refresh()
    assert sampler_kernel_eligible(
        4096, needs_gumbel=True, allow_interpret=True
    ) == (False, False)


def test_global_pallas_escape_hatch(monkeypatch):
    monkeypatch.setenv("VLLM_TPU_DISABLE_PALLAS", "1")
    envs.refresh()
    assert sampler_kernel_eligible(
        4096, needs_gumbel=True, allow_interpret=True
    ) == (False, False)


def test_mosaic_vocab_rules(monkeypatch):
    """On-TPU (Mosaic) eligibility: 128-lane alignment, a size floor, and
    a VMEM-driven ceiling."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ok = lambda v: sampler_kernel_eligible(v, needs_gumbel=True)
    assert ok(32000) == (True, False)
    assert ok(2048) == (True, False)
    assert ok(131072) == (True, False)
    assert ok(333) == (False, False)  # not 128-aligned
    assert ok(1024) == (False, False)  # below the floor
    # Past the widest power of two: whole noise tiles, up to the ceiling.
    assert ok(131200) == (True, False)
    assert ok(151936) == (True, False)  # the Qwen family's
    assert ok(_sk.MAX_KERNEL_WIDTH) == (True, False)
    assert ok(_sk.MAX_KERNEL_WIDTH + 128) == (False, False)  # past it


def test_the_ceiling_is_the_vmem_estimate_on_the_least_vmem():
    assert _sk.compute_width(151936) == 155648 == 38 * 4096
    assert _sk.compute_width(128256) == 131072  # as before the wide widths
    assert _sk.compute_width(V_ODD) == 512
    widest = _sk.MAX_KERNEL_WIDTH
    assert widest % 4096 == 0 and widest >= 155648
    assert _sk.vmem_estimate(8, widest) <= _sk._LEAST_VMEM_BYTES
    assert _sk.vmem_estimate(8, widest + 4096) > _sk._LEAST_VMEM_BYTES


def test_dispatch_fallback_matches_reference():
    """With the kernel ineligible, dispatch_sample IS the reference."""
    rng = np.random.default_rng(59)
    logits, md = _mixed_batch(rng, V_ODD, False)
    want, _ = sample(logits, md)
    got, _ = dispatch_sample(logits, md, enable_kernel=False,
                             allow_interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
