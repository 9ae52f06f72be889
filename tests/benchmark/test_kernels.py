"""The counting functions of ``benchmark/kernels/`` against sizes worked
out by hand, and the table of peaks."""

from __future__ import annotations

import pytest

from benchmark.harness.manifest import BENCH_DIR, kernel_counts, load_json, peaks_for

MISTRAL = load_json(BENCH_DIR / "configs" / "mistral-7b-v0.3.json")
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_decode_attention_bytes():
    k = kernel_counts("decode_attn")
    # 8 KV heads x 128 x (K and V) x 2 bytes = 4096 bytes a position a layer;
    # 16 layers: 65,536 bytes a position of context.
    assert k.kv_bytes_per_context_token(MISTRAL) == 65536
    # 16 KV heads x 128 x 2 x 2 = 8192 a layer; 8 layers: the same.
    mha = {"num_attention_heads": 16, "hidden_size": 2048, "num_hidden_layers": 8}
    assert k.kv_bytes_per_context_token(mha) == 65536
    # 64 decode tokens of 900 positions each: 3.77 GB, 4.6 ms at 819 GB/s.
    assert k.kv_bytes(MISTRAL, 64 * 900) == 64 * 900 * 65536
    assert k.least_seconds(MISTRAL, 64 * 900, V5E) == pytest.approx(64 * 900 * 65536 / 819e9)
    assert k.least_seconds(MISTRAL, 64 * 900, V5E) == pytest.approx(4.609e-3, rel=1e-3)


def test_peaks_are_keyed_by_device_kind_and_a_missing_kind_is_an_error():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "cloud.google.com" in load_json(BENCH_DIR / "peaks.json")["source"]
    with pytest.raises(KeyError, match="TPU v9"):
        peaks_for("TPU v9")


def _ctx(config, trace, deltas, steps, decode_ctx=(0, 0)):
    from benchmark.harness.manifest import load_module

    return {
        "trace": trace, "config": {k: v for k, v in config.items() if k != "bench"},
        "peaks": V5E, "kernel_counts": kernel_counts,
        "delta": lambda *names: next((deltas[n] for n in names if n in deltas), None),
        "steps": lambda: steps, "decode_context_tokens": lambda: decode_ctx,
    }, load_module


def test_roofline_readers_on_a_trace_written_by_hand():
    # Decode attention: 1000 decode tokens of 900 positions arrived in the
    # slices; 30 of their 40 steps were decode-only (16 calls a step, one a
    # layer), and the decode kernel took 0.15 s.
    trace = {"kernel_seconds": {"rpa_decode_kernel": 0.15, "rpa_kernel": 0.05},
             "kernel_calls": {"rpa_decode_kernel": 30 * 16, "rpa_kernel": 10 * 16}}
    ctx, load = _ctx(MISTRAL, trace, {}, steps=40, decode_ctx=(1000, 900_000))
    reader = load(BENCH_DIR / "layer_metrics" / "decode_attn_roofline.py")
    assert reader.read(ctx) == pytest.approx(100 * (0.75 * 900_000 * 65536 / 819e9) / 0.15)
    assert reader.read({**ctx, "trace": None}) is None
    # A slice without a decode-only step has nothing to read.
    none = {"kernel_seconds": {"rpa_kernel": 0.05}, "kernel_calls": {"rpa_kernel": 160}}
    assert reader.read({**ctx, "trace": none}) is None
    idle = load(BENCH_DIR / "layer_metrics" / "device_idle_share.py")
    assert idle.read({"trace": {"busy_s": 0.75, "window_s": 1.0}}) == pytest.approx(25.0)
    assert idle.read({"trace": None}) is None
