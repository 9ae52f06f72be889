"""The controls of ``correct`` for ``kimilinear48b.long-answers``: the
comparison that passes the program as configured must fail it in the
nearest lower precision of each of its two caches: the recurrent state
kept in bfloat16, and the latent rows rounded to float8 as they are
written. Neither is a flag of the program; on the chip they are two edits
of a scratch copy (PERF.md section 4), here two patches. At a tiny size
on the CPU; the cell's limits were set from the chip's readings. On the
chip, served in bfloat16, it is the two FACTORS over the reference's own
bfloat16 pass that fail both on every seed (``test_checker_factors.py``,
``test_manifest_kimi_linear.py``); at this size bfloat16 serving drowns
either (factors 1.02-1.15 sound, 1.08-1.33 under a control, 1,200 numbers
a seed on the CPU), so the controls are kept here served in float32, where
there is no second pass to divide by."""

from __future__ import annotations

import jax.numpy as jnp
import pytest

from benchmark.harness.checker import compare
from benchmark.harness.manifest import BENCH_DIR, load_module

from .test_reference_kimi_linear import served_samples
from .tiny_kimi_linear import TINY_KIMI

SEEDS = (3, 4, 5)


def state_in_bfloat16(monkeypatch):
    from vllm_tpu.models.kimi_linear import KimiLinearForCausalLM as M

    alloc = M.alloc_kv_cache

    def lower(self, *args):
        cache = alloc(self, *args)
        return {**cache, "kda": cache["kda"].astype(jnp.bfloat16)}

    monkeypatch.setattr(M, "alloc_kv_cache", lower)


def latent_rows_through_float8(monkeypatch):
    from vllm_tpu.models import deepseek

    write = deepseek.write_latent
    monkeypatch.setattr(
        deepseek, "write_latent",
        lambda kv, li, latent, slots: write(
            kv, li, latent.astype(jnp.float8_e4m3fn).astype(latent.dtype), slots))


@pytest.mark.parametrize("control", [state_in_bfloat16, latent_rows_through_float8])
def test_a_cache_in_lower_precision_is_told_from_the_configured_one(
        tmp_path, monkeypatch, control):
    """Served in float32, so that the sound runs differ from the
    reference in the order of sums only and what a control adds stands
    alone: at this size (a hidden state of 64, sixteen experts) bfloat16
    activations flip routes, and that noise would drown both."""
    ref = load_module(BENCH_DIR / "reference" / "kimi_linear.py")

    def readings():
        return [compare(TINY_KIMI, ref, seed,
                        served_samples(tmp_path, seed, lengths=(24, 70)), "float32")
                for seed in SEEDS]

    sound = readings()
    control(monkeypatch)
    lower = readings()
    assert max(r["max_abs"] for r in sound) < 5e-4, sound
    assert min(r["p50_abs"] for r in lower) > 10 * max(r["p50_abs"] for r in sound), (sound, lower)
    assert min(r["worst_sequence_mean_abs"] for r in lower) > 1e-3, lower
