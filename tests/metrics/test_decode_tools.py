"""CPU smoke coverage for the decode-performance tooling:

- ``tools/probe_decode_attn.py --smoke``: the decode kernel's block-size
  sweep in Pallas interpret mode against the XLA reference;
- ``tools/probe_sampler.py --smoke``: the fused sampling kernel's
  block-shape sweep, bit-exact against the XLA sampling epilogue;
- ``tools/probe_ragged.py``: the ragged kernel at its tiny CPU case;
- ``metrics/goodput.py``'s summary schema.
"""

from __future__ import annotations

import os
import subprocess
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def _run_tool(name, *args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, "tools", name), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
    )


def test_probe_decode_attn_smoke():
    proc = _run_tool("probe_decode_attn.py", "--smoke")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "smoke sweep ok" in proc.stdout
    # Every sweep point reported and matched the reference.
    assert proc.stdout.count("MISMATCH") == 0
    assert proc.stdout.count("decode sb=") == 9


def test_probe_sampler_smoke():
    """Fused sampling kernel bit-exact vs the XLA reference across the
    interpret-mode block-shape sweep."""
    proc = _run_tool("probe_sampler.py", "--smoke")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "smoke sweep ok" in proc.stdout
    assert proc.stdout.count("MISMATCH") == 0
    assert proc.stdout.count("kernel rb=") == 4


def test_probe_ragged_smoke():
    """The ragged attention kernel's probe at its tiny CPU case: the
    kernel, the parent-style explicit blocks and the host's count run, and
    the outputs agree with the XLA reference."""
    import json

    proc = _run_tool("probe_ragged.py", "--variant", "4x8")
    assert proc.returncode == 0, proc.stderr[-2000:]
    points = [json.loads(x) for x in proc.stdout.splitlines() if x.startswith("{")]
    assert [p["kernel"] for p in points] == ["change", "4x8"]
    assert all(p["max_abs_err"] < 3e-2 and 0 < p["live_share"] <= 100 for p in points)


def test_goodput_summary_schema():
    """bench.py's ``goodput`` block: accepted tokens/s under the ITL
    SLO, scored by the pure helper (vllm_tpu/metrics/goodput.py)."""
    from vllm_tpu.metrics.goodput import goodput_summary

    # Spec on: 10ms steps bursting 2 tokens -> 5ms per-token gaps.
    g = goodput_summary(
        [(0.010, 2)] * 50, elapsed_s=2.0,
        accepted_tokens=80, emitted_tokens=100, slo_itl_ms=8.0)
    for key in ("accepted_tok_s", "slo_attainment", "slo_met",
                "p99_itl_ms", "slo_itl_ms", "itl_samples",
                "token_source"):
        assert key in g, key
    assert g["accepted_tok_s"] == 40.0
    assert g["token_source"] == "spec_accepted"
    assert g["slo_attainment"] == 1.0 and g["slo_met"] is True
    assert g["p99_itl_ms"] == 5.0
    assert g["itl_samples"] == 100

    # Spec off: falls back to emitted tokens/s; a tail sample past the
    # SLO flips slo_met and dents attainment.
    g = goodput_summary(
        [(0.010, 1)] * 95 + [(0.200, 1)] * 5, elapsed_s=1.0,
        emitted_tokens=100, slo_itl_ms=50.0)
    assert g["token_source"] == "emitted"
    assert g["accepted_tok_s"] == 100.0
    assert g["slo_attainment"] == 0.95 and g["slo_met"] is False
    assert g["p99_itl_ms"] == 200.0


def test_goodput_summary_empty_window():
    from vllm_tpu.metrics.goodput import goodput_summary

    g = goodput_summary([], elapsed_s=0.0, slo_itl_ms=50.0)
    assert g["accepted_tok_s"] is None
    assert g["slo_attainment"] is None and g["p99_itl_ms"] is None
