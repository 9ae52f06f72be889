"""Tier-1 wrapper for the registry lint in ``tools/check_metrics.py``:
every metric attribute renders on /metrics, names match the vllm:
namespace grammar, docs are non-empty."""

from __future__ import annotations

import os
import subprocess
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def _lint():
    sys.path.insert(0, os.path.join(_REPO_ROOT, "tools"))
    try:
        import check_metrics
    finally:
        sys.path.pop(0)
    return check_metrics


def test_registry_lint_clean():
    check_metrics = _lint()
    assert check_metrics.check() == []


def test_lint_cli_exit_code():
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO_ROOT, "tools",
                                      "check_metrics.py")],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "ok:" in proc.stdout


def test_lint_holds_the_exposition_to_the_step_phases_and_kinds(monkeypatch):
    """A phase that stops reaching /metrics is a lint error, not a per-layer
    metric that silently reads None."""
    check_metrics = _lint()
    assert check_metrics.check_step_exposition() == []
    assert {"prepare", "fetch", "update", "output"} <= set(
        check_metrics.REQUIRED_STEP_PHASES)
    monkeypatch.setattr(check_metrics, "REQUIRED_STEP_PHASES",
                        check_metrics.REQUIRED_STEP_PHASES + ("sample",))
    [err] = check_metrics.check_step_exposition()
    assert "sample" in err


def test_lint_names_a_metric_dropped_from_the_render_list(monkeypatch):
    """What the old count of metrics stood guard over, held by name: a
    metric that a refactor leaves on the registry but drops from the render
    list, and a required name that is gone, are each an error that says
    which."""
    check_metrics = _lint()
    from vllm_tpu.metrics import prometheus

    real_init = prometheus.PrometheusRegistry.__init__

    def init_without_fallback_rows(self, *a, **k):
        real_init(self, *a, **k)
        self._metrics = [m for m in self._metrics
                         if m.name != "vllm:sampler_fallback_rows_total"]

    monkeypatch.setattr(prometheus.PrometheusRegistry, "__init__",
                        init_without_fallback_rows)
    errors = check_metrics.check()
    assert any("vllm:sampler_fallback_rows_total" in e
               and "never render" in e for e in errors), errors

    monkeypatch.setattr(prometheus.PrometheusRegistry, "__init__", real_init)
    monkeypatch.setattr(
        check_metrics, "REQUIRED_SAMPLER_METRICS",
        check_metrics.REQUIRED_SAMPLER_METRICS | {"vllm:no_such_series"})
    assert [e for e in check_metrics.check()
            if "vllm:no_such_series" in e and "missing" in e]
    assert not hasattr(check_metrics, "MIN_METRICS")
