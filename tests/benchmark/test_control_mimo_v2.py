"""The controls of ``correct`` for ``mimov25.long-answers``: the comparison
that passes the program as configured must fail it (a) in the nearest lower
precision, its own float8 KV cache, (b) with the SINK left out of the
window layers' softmax, and (c) with the window layers served as full
attention (their window scalar None, the pages kept, since nothing may be
read that was given back). (b) and (c) are no flags of the program: on the
chip they are edits of a scratch copy (PERF.md section 4), here patches. At
a tiny size on the CPU, served in float32 so that the sound runs differ
from the reference in the order of sums only and what a control adds stands
alone; the cell's limits were set from the chip's readings."""

from __future__ import annotations

from benchmark.harness.checker import compare
from benchmark.harness.manifest import BENCH_DIR, load_module

from .test_reference_mimo_v2 import served_samples
from .tiny_mimo_v2 import TINY_MIMO_V2

SEEDS = (3, 4, 5)
REF = load_module(BENCH_DIR / "reference" / "mimo_v2.py")


def readings(tmp_path, **engine):
    return [compare(TINY_MIMO_V2, REF, seed,
                    served_samples(tmp_path, seed, lengths=(12, 70, 130), max_tokens=16,
                                   **engine),
                    "float32")
            for seed in SEEDS]


def without_the_sink(monkeypatch):
    from vllm_tpu.models import mimo_v2

    attend = mimo_v2.paged_attention
    monkeypatch.setattr(
        mimo_v2, "paged_attention",
        lambda *a, sinks=None, **kw: attend(*a, sinks=None, **kw))


def window_layers_as_full(monkeypatch):
    """The window scalar None in every window layer's attention call (the
    sink stays), and no page given back (a full layer reads them all)."""
    from vllm_tpu.core.single_type_managers import SlidingWindowManager
    from vllm_tpu.models import mimo_v2

    attend = mimo_v2.paged_attention
    monkeypatch.setattr(
        mimo_v2, "paged_attention",
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


def test_window_layers_without_their_sink_are_told(tmp_path, monkeypatch):
    """Every sequence can tell, the short one too: the sink takes its
    share of every window row from the first position on."""
    sound = readings(tmp_path)
    without_the_sink(monkeypatch)
    bare = readings(tmp_path)
    assert min(r["p50_abs"] for r in bare) > 10 * max(r["p50_abs"] for r in sound), (sound, bare)
    for r in bare:
        assert min(p["mean_abs"] for p in r["per_sample"]) > 1e-3, r["per_sample"]


def test_window_layers_served_as_full_are_told_from_the_window(tmp_path, monkeypatch):
    """Only the prompts that pass the window can tell: 70 and 130 tokens
    against a window of 24 (the one of 12 ends at 28 tokens: its last four
    generated positions are past the window by a key or two, the others
    read the same either way)."""
    sound = readings(tmp_path)
    window_layers_as_full(monkeypatch)
    as_full = readings(tmp_path, freed_at_least=0)
    assert min(r["p50_abs"] for r in as_full) > 10 * max(r["p50_abs"] for r in sound), (
        sound, as_full)
    assert min(r["worst_sequence_mean_abs"] for r in as_full) > 1e-3, as_full
    for r in as_full:
        assert r["per_sample"][0]["mean_abs"] < min(
            p["mean_abs"] for p in r["per_sample"][1:]), r["per_sample"]
