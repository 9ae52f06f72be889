"""The control of ``correct`` for ``sdar30b.chat``: the comparison that
passes the program as configured must fail it in the nearest lower
precision, its own fp8 KV cache. Here at a tiny size on the CPU; on the
chip at the cell's own sizes the readings are in PERF.md, and the limits in
``benchmark/cells/sdar30b.chat.json`` were set from those."""

from __future__ import annotations

from benchmark.harness.checker import compare
from benchmark.harness.manifest import BENCH_DIR, load_module

from .test_reference_sdar_moe import served_samples
from .tiny_sdar_moe import TINY_SDAR

SEEDS = (3, 4, 5)


def test_fp8_kv_is_told_from_the_configured_precision(tmp_path):
    ref = load_module(BENCH_DIR / "reference" / "sdar_moe.py")
    sound, lower = [], []
    for seed in SEEDS:
        for engine, into in (({}, sound), ({"kv_cache_dtype": "fp8"}, lower)):
            samples = served_samples(tmp_path, seed, "bfloat16", lengths=(24, 90), **engine)
            into.append(compare(TINY_SDAR, ref, seed, samples, "bfloat16"))
    # At this size (two layers, a head of 16) the tail is a few numbers and
    # a flipped expert moves it in both; the median tells the two apart.
    assert min(r["p50_abs"] for r in lower) >= 2 * max(r["p50_abs"] for r in sound), (sound, lower)
    assert min(r["mean_abs"] for r in lower) > max(r["mean_abs"] for r in sound), (sound, lower)
