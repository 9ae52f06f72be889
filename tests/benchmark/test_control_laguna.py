"""The controls of ``correct`` for ``lagunaxs2.code-mixed``: the comparison
that passes the program as configured must fail it (a) in the nearest lower
precision, its own float8 KV cache, (b) with the MECHANISM taken out: the
window layers served as full attention, their window scalar 0 (the pages
kept, since nothing may be read that was given back), and (c) with a FAULT
IN THE EXPERT LAYER: a share whose held experts are taken for the router's
next ones (``first_expert`` off by one), so that every routed pair meets
another expert's weights. (b) and (c) are no flags of the program: on the
chip they are edits of a scratch copy (PERF.md section 4), here patches. At a tiny size on the CPU, served in float32 so
that the sound runs differ from the reference in the order of sums only
and what a control adds stands alone; the cell's limits were set from the
chip's readings."""

from __future__ import annotations

import pytest

from benchmark.harness.checker import compare
from benchmark.harness.manifest import BENCH_DIR, load_module

from .test_reference_laguna import served_samples
from .tiny_laguna import TINY_LAGUNA, share

SEEDS = (3, 4, 5)
REF = load_module(BENCH_DIR / "reference" / "laguna.py")


def readings(tmp_path, config=TINY_LAGUNA, **engine):
    return [compare(config, REF, seed,
                    served_samples(tmp_path, seed, lengths=(16, 70, 130), config=config,
                                   **engine),
                    "float32")
            for seed in SEEDS]


def window_layers_as_full(monkeypatch):
    """The window scalar 0 in every window layer's attention call, and no
    page given back (a full layer reads them all)."""
    from vllm_tpu.core.single_type_managers import SlidingWindowManager
    from vllm_tpu.models import laguna

    attend = laguna.paged_attention
    monkeypatch.setattr(
        laguna, "paged_attention",
        lambda *a, sliding_window=None, **kw: attend(*a, sliding_window=None, **kw))
    monkeypatch.setattr(SlidingWindowManager, "remove_skipped_blocks", lambda self, request: 0)


def test_the_sound_program_passes(tmp_path):
    sound = readings(tmp_path)
    assert max(r["max_abs"] for r in sound) < 5e-4, sound


def test_a_float8_cache_is_told_from_the_configured_one(tmp_path):
    sound = readings(tmp_path)
    lower = readings(tmp_path, kv_cache_dtype="fp8")
    assert min(r["p50_abs"] for r in lower) > 10 * max(r["p50_abs"] for r in sound), (sound, lower)
    assert min(r["worst_sequence_mean_abs"] for r in lower) > 1e-3, lower


def test_window_layers_served_as_full_are_told_from_the_window(tmp_path, monkeypatch):
    """Only the prompts that pass the window can tell: 70 and 130 tokens
    against a window of 32 (the one of 16 stays under it with its 12
    generated tokens and reads the same either way)."""
    sound = readings(tmp_path)
    window_layers_as_full(monkeypatch)
    as_full = readings(tmp_path, freed_at_least=0)
    assert min(r["p50_abs"] for r in as_full) > 10 * max(r["p50_abs"] for r in sound), (
        sound, as_full)
    assert min(r["worst_sequence_mean_abs"] for r in as_full) > 1e-3, as_full
    # The short prompt is the same either way.
    for r in as_full:
        assert r["per_sample"][0]["max_abs"] < 5e-4, r["per_sample"][0]


def test_a_share_that_takes_its_experts_for_the_next_ones_is_told(tmp_path, monkeypatch):
    """One share of four, held experts 4..7 of the router's 16: served with
    ``first_expert`` 5 a pair routed to expert 5 meets expert 4's weights
    and the pairs of expert 4 are dropped. The comparison sees the expert
    layer: every sequence fails, the short one too."""
    from vllm_tpu.models import laguna

    config = share(1)
    sound = readings(tmp_path, config)
    assert max(r["max_abs"] for r in sound) < 5e-4, sound
    init = laguna.LagunaForCausalLM.__init__

    def off_by_one(self, *args, **kw):
        init(self, *args, **kw)
        self.first_expert += 1

    monkeypatch.setattr(laguna.LagunaForCausalLM, "__init__", off_by_one)
    faulty = readings(tmp_path, config)
    assert min(r["p50_abs"] for r in faulty) > 10 * max(r["p50_abs"] for r in sound), (
        sound, faulty)
    for r in faulty:
        assert min(p["mean_abs"] for p in r["per_sample"]) > 1e-3, r["per_sample"]
