"""The served error as a FACTOR over what rounding to the served type
costs the reference itself, on the same seed and the same tokens
(``harness/checker.py::compare(..., factors=True)``, asked for by a cell
whose ``correct_limits`` name a ``*_factor``): the arithmetic on a family
made up here, and the second pass of the Kimi-Linear reference on the CPU
at a tiny size. The cell's limits were set from the chip's readings
(PERF.md section 4)."""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.checker import compare
from benchmark.harness.manifest import BENCH_DIR, Cell, load_manifest, load_module

from .tiny_kimi_linear import TINY_KIMI, share

REF = load_module(BENCH_DIR / "reference" / "kimi_linear.py")
V, TOP = 64, 5


def family(level: float):
    """A reference whose pass in the served type lies ``level`` x noise
    from its float32 pass: a seed whose weights make much or little of
    rounding."""
    def logits(seq, compute):
        rng = np.random.default_rng(len(seq))
        exact = rng.normal(size=(len(seq), V)).astype(np.float32)
        rounding = np.random.default_rng(len(seq) + 1).normal(size=exact.shape)
        return exact + (0.0 if jnp.dtype(compute) == jnp.float32 else level) * rounding

    return types.SimpleNamespace(
        seeded_weights=lambda config, seed, dtype: jnp.zeros(()),
        forward_many=lambda config, weights, seqs, compute=jnp.float32: [
            logits(s, compute) for s in seqs])


def served(fam, level: float, times: float, lengths=(300, 400, 500)) -> list[dict]:
    """Answers whose logprobs lie ``times`` x ``level`` x (other) noise from
    the family's float32 pass: a served path that adds ``times`` of what
    rounding costs."""
    out = []
    for n in lengths:
        prompt, tokens = list(range(7)), list(range(n))
        exact = fam.forward_many(None, None, [prompt + tokens[:-1]])[0][len(prompt) - 1:]
        noisy = exact + times * level * np.random.default_rng(n + 2).normal(size=exact.shape)
        logp = np.asarray(jax.nn.log_softmax(noisy, axis=-1))
        top = np.argsort(-logp, axis=-1)[:, :TOP]
        out.append({"prompt": prompt, "tokens": [int(t[0]) for t in top],
                    "top_logprobs": [{str(int(t)): float(logp[j, t]) for t in row}
                                     for j, row in enumerate(top)]})
    return out


@pytest.mark.parametrize("level", [0.01, 0.03])
def test_a_factor_divides_the_seeds_level_out_and_keeps_what_a_lower_precision_adds(level):
    fam = family(level)
    sound = compare({}, fam, 1, served(fam, level, 1.0), "bfloat16", factors=True)
    lower = compare({}, fam, 1, served(fam, level, 1.25), "bfloat16", factors=True)
    # The absolute errors follow the level, threefold from seed to seed ...
    assert sound["p50_abs"] == pytest.approx(level * 0.68, rel=0.15)
    # ... the factors do not, and a quarter more error reads as a quarter more.
    for key in ("p50_factor", "p90_factor", "mean_factor", "worst_sequence_factor"):
        assert sound[key] == pytest.approx(1.0, abs=0.08), key
        assert lower[key] == pytest.approx(1.25, abs=0.1), key
        assert lower[key] > 1.12 * sound[key], key
    assert {"lower_p50_abs", "lower_p90_abs", "lower_mean_abs"} <= set(sound)
    # The third cell's own limits pass the one and fail the other.
    limits = Cell(load_manifest(), "kimilinear48b.long-answers").numbers["correct_limits"]
    for key in ("p50_factor", "worst_sequence_factor"):
        assert sound[key] < limits[key] < lower[key], key
    assert all("lower_mean_abs" in p for p in sound["per_sample"])


def test_without_factors_the_reference_runs_once_and_the_numbers_are_the_old_ones():
    calls = []
    fam = family(0.02)
    once = types.SimpleNamespace(
        seeded_weights=fam.seeded_weights,
        forward_many=lambda *a, **kw: calls.append(kw) or fam.forward_many(*a, **kw))
    samples = served(fam, 0.02, 1.0)
    plain = compare({}, once, 1, samples, "bfloat16")
    assert calls == [{}] and not any("factor" in k or "lower" in k for k in plain)
    both = compare({}, once, 1, samples, "bfloat16", factors=True)
    assert calls[1:] == [{}, {"compute": jnp.dtype("bfloat16")}]
    assert {k: both[k] for k in plain if k != "per_sample"} == {
        k: v for k, v in plain.items() if k != "per_sample"}
    # The flat run of (position, token) pairs is the old loop's, number for number.
    d = [abs(float(lp) - float(np.asarray(jax.nn.log_softmax(
            fam.forward_many(None, None, [s["prompt"] + s["tokens"][:-1]])[0][len(s["prompt"]) - 1:],
            axis=-1))[j, int(t)]))
         for s in samples for j, top in enumerate(s["top_logprobs"]) for t, lp in top.items()]
    assert plain["compared"] == len(d) and plain["mean_abs"] == pytest.approx(np.mean(d), rel=1e-6)


@pytest.mark.parametrize("cell,asks", [("mistral7b.chat", False), ("sdar30b.chat", False),
                                       ("kimilinear48b.long-answers", True)])
def test_a_cell_asks_for_the_second_pass_by_naming_a_factor_among_its_limits(cell, asks):
    """``run_cell.py`` puts ``factors`` into the checker's job by this rule;
    a cell that names none has the checker it had."""
    limits = Cell(load_manifest(), cell).numbers["correct_limits"]
    assert any(k.endswith("_factor") for k in limits) == asks


@pytest.mark.parametrize("config", [TINY_KIMI, share(2)], ids=["whole", "share"])
def test_the_kimi_reference_in_the_served_type_is_the_same_model_rounded(config):
    """The second pass: the same weights, the same formulae, activations in
    bfloat16. It stays a bfloat16's distance from the float32 pass (not
    another model, not the same numbers), nothing looks ahead in it either,
    and the leaves the family keeps in float32 stay so."""
    weights = REF.seeded_weights(config, 6, jnp.bfloat16)
    seq = np.random.default_rng(6).integers(0, 250, 60).tolist()
    exact, low = (np.asarray(REF.forward_many(config, weights, [seq], **kw)[0])
                  for kw in ({}, {"compute": jnp.bfloat16}))
    assert exact.dtype == low.dtype == np.float32 and np.isfinite(low).all()
    gap = np.abs(np.asarray(jax.nn.log_softmax(low, -1)) - np.asarray(jax.nn.log_softmax(exact, -1)))
    assert 1e-3 < np.median(gap) < 0.3, np.median(gap)
    assert (exact.argmax(-1) == low.argmax(-1)).mean() > 0.6
    other_end = np.asarray(REF.forward_many(
        config, weights, [seq[:40] + [7] * 20], compute=jnp.bfloat16)[0])
    np.testing.assert_array_equal(other_end[:40], low[:40])
    lw = REF.layer_weights(config, weights, 1, jnp.bfloat16)
    assert lw["mixer"]["a_log"].dtype == lw["mixer"]["dt_bias"].dtype == jnp.float32
    assert lw["ffn"]["router_bias"].dtype == jnp.float32
    assert lw["mixer"]["wq"].dtype == lw["experts"]["we_up"].dtype == jnp.bfloat16
