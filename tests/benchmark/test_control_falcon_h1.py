"""The controls of ``correct`` for ``falconh1-34b.long-answers``: the
comparison that passes the program as configured must fail it (a) with the
recurrent state STORED in bfloat16, (b) with the state-space branch's output
multiplied by zero, (c) with ``key_multiplier`` left out; and leaving out
any ONE of the config's multipliers fails it too. None is a flag of the
program; on the chip the three are edits of the chip's copy (PERF.md section
4), here patches. At a tiny size on the CPU, through the harness's own
``compare``; the cell's limits were set from the chip's readings. Served in
float32, so that a sound run differs from the reference in the order of
sums only and what a control adds stands alone."""

from __future__ import annotations

import jax.numpy as jnp
import pytest

from benchmark.harness.checker import compare
from benchmark.harness.manifest import BENCH_DIR, load_module

from .test_reference_falcon_h1 import served_samples
from .tiny_falcon_h1 import MULTIPLIERS, TINY_FALCON_H1

REF = load_module(BENCH_DIR / "reference" / "falcon_h1.py")
SEEDS = (3, 4)
SOUND: dict[int, dict] = {}


def sound(tmp_path, seed):
    if seed not in SOUND:
        SOUND[seed] = compare(TINY_FALCON_H1, REF, seed,
                              served_samples(tmp_path, seed, lengths=(24, 70)), "float32")
    return SOUND[seed]


def state_in_bfloat16(monkeypatch):
    from vllm_tpu.models.falcon_h1 import FalconH1ForCausalLM as M

    alloc = M.alloc_kv_cache

    def lower(self, *args):
        cache = alloc(self, *args)
        return {**cache, "ssm": cache["ssm"].astype(jnp.bfloat16)}

    monkeypatch.setattr(M, "alloc_kv_cache", lower)


def without(name):
    """The program with one multiplier of the config at 1 (or, for
    ``ssm_out_multiplier=0``, the branch gone), the weights as drawn for the
    config as published: the model is built from an edited copy of the
    config AFTER the draw's widths are read off the real one."""
    key, _, index = name.partition(".")

    def patch(monkeypatch, value=1.0):
        from vllm_tpu.models.falcon_h1 import FalconH1ForCausalLM as M

        init = M.__init__

        def edited(self, hf_config, *a, **kw):
            init(self, hf_config, *a, **kw)
            attr = {"mlp_multipliers.0": "gate_multiplier", "mlp_multipliers.1":
                    "down_multiplier"}.get(name, key)
            if key == "ssm_multipliers":
                zones = list(self.ssm_multipliers)
                zones[int(index)] = value
                self.ssm_multipliers = tuple(zones)
            else:
                assert hasattr(self, attr), attr
                setattr(self, attr, value)

        monkeypatch.setattr(M, "__init__", edited)

    return patch


CONTROLS = {
    "the state stored in bfloat16": state_in_bfloat16,
    "the state-space branch times zero": lambda mp: without("ssm_out_multiplier")(mp, 0.0),
    "key_multiplier left out": without("key_multiplier"),
}


@pytest.mark.parametrize("control", list(CONTROLS))
def test_a_control_fails_the_comparison_that_passes_the_program(tmp_path, monkeypatch, control):
    """Each on two seeds, over the form of the committed limits (the median,
    the worst sequence's mean): a sound run reads under 5e-4 everywhere, a
    control at least ten times the sound runs' largest in both."""
    ok = [sound(tmp_path, seed) for seed in SEEDS]
    assert max(r["max_abs"] for r in ok) < 5e-4, ok
    CONTROLS[control](monkeypatch)
    lower = [compare(TINY_FALCON_H1, REF, seed,
                     served_samples(tmp_path, seed, lengths=(24, 70)), "float32")
             for seed in SEEDS]
    assert min(r["p50_abs"] for r in lower) > 10 * max(r["p50_abs"] for r in ok), (ok, lower)
    assert min(r["worst_sequence_mean_abs"] for r in lower) > 10 * max(
        r["worst_sequence_mean_abs"] for r in ok), (ok, lower)


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_leaving_out_any_one_multiplier_fails(tmp_path, monkeypatch, name):
    """Every multiplier is shown to matter: the program with that one at 1
    (none of the tiny config's is 1) misses the sound run's largest error by
    more than tenfold in the median."""
    ok = sound(tmp_path, SEEDS[0])
    without(name)(monkeypatch)
    got = compare(TINY_FALCON_H1, REF, SEEDS[0],
                  served_samples(tmp_path, SEEDS[0], lengths=(24, 70)), "float32")
    assert got["p50_abs"] > 10 * max(ok["p50_abs"], 2e-5), (name, ok["p50_abs"], got["p50_abs"])
