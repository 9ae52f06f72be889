"""Tier-1 wrapper for the registry lint in ``tools/check_metrics.py``:
every metric attribute renders on /metrics, names match the vllm:
namespace grammar, docs are non-empty."""

from __future__ import annotations

import os
import subprocess
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def test_registry_lint_clean():
    sys.path.insert(0, os.path.join(_REPO_ROOT, "tools"))
    try:
        import check_metrics
    finally:
        sys.path.pop(0)
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
    sys.path.insert(0, os.path.join(_REPO_ROOT, "tools"))
    try:
        import check_metrics
    finally:
        sys.path.pop(0)
    assert check_metrics.check_step_exposition() == []
    assert {"prepare", "fetch", "update", "output"} <= set(
        check_metrics.REQUIRED_STEP_PHASES)
    monkeypatch.setattr(check_metrics, "REQUIRED_STEP_PHASES",
                        check_metrics.REQUIRED_STEP_PHASES + ("sample",))
    [err] = check_metrics.check_step_exposition()
    assert "sample" in err
