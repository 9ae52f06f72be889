"""Fetch-vs-recompute cost model: the decision flips at the analytic
crossover, degraded links bias toward recompute, and the env pin
(forced-cheap / forced-expensive link) locks the bandwidth."""

from __future__ import annotations

import pytest

from vllm_tpu.kv_fabric.cost_model import (
    DEFAULT_FLOPS_PER_TOKEN,
    DEFAULT_PEAK_FLOPS,
    ENV_LINK_GBPS,
    FetchCostModel,
)


@pytest.fixture(autouse=True)
def _no_env_pin(monkeypatch):
    monkeypatch.delenv(ENV_LINK_GBPS, raising=False)


def _bare_model(link_bw=1.0e9):
    """Zero fixed costs, unit efficiency: pure bytes-vs-FLOPs tradeoff,
    so the crossover is exactly analytic."""
    return FetchCostModel(
        link_bw=link_bw,
        link_latency_s=0.0,
        prefill_overhead_s=0.0,
        prefill_eff=1.0,
    )


def test_decision_flips_at_analytic_crossover():
    bw = 1.0e9
    m = _bare_model(link_bw=bw)
    n_tokens = 1024
    recompute_s = n_tokens * DEFAULT_FLOPS_PER_TOKEN / DEFAULT_PEAK_FLOPS
    crossover_bytes = recompute_s * bw
    cheap = m.decide(n_tokens, int(crossover_bytes * 0.9))
    assert cheap.fetch, (cheap.fetch_s, cheap.recompute_s)
    dear = m.decide(n_tokens, int(crossover_bytes * 1.1))
    assert not dear.fetch, (dear.fetch_s, dear.recompute_s)
    assert dear.recompute_s == pytest.approx(recompute_s)


def test_roofline_overrides_defaults():
    class FakeRoofline:
        has_peaks = True
        peak_flops = 10.0e12

        def flops_per_token(self):
            return 1.0e9

    m = _bare_model()
    m.set_roofline(FakeRoofline())
    assert m.recompute_time_s(1000) == pytest.approx(1000 * 1e9 / 10e12)
    assert m.stats()["has_roofline"]


def test_degraded_link_biases_toward_recompute():
    m = FetchCostModel(
        link_latency_s=0.0, prefill_overhead_s=0.0, prefill_eff=1.0)
    assert not m.pinned
    n_tokens, nbytes = 1024, 4 << 20
    assert m.decide(n_tokens, nbytes).fetch, "healthy link must fetch"
    # The link degrades: observed transfers crawl at ~100 KB/s. The EWMA
    # drags the modeled bandwidth down until fetch loses.
    for _ in range(40):
        m.observe_transfer(100_000, 1.0)
    assert m.link_bw < 1.0e6
    assert m.stats()["transfers_observed"] == 40
    assert not m.decide(n_tokens, nbytes).fetch


def test_env_pin_forces_link_bandwidth(monkeypatch):
    monkeypatch.setenv(ENV_LINK_GBPS, "100")
    m = FetchCostModel()
    assert m.pinned
    assert m.link_bw == pytest.approx(100e9)
    # Pinned models ignore measurements (the test hook must stay put).
    m.observe_transfer(1000, 10.0)
    assert m.link_bw == pytest.approx(100e9)
    assert m.stats()["transfers_observed"] == 0


def test_env_pin_forced_expensive_flips_to_recompute(monkeypatch):
    """The ISSUE's forced-expensive-link knob: a microscopic pinned
    bandwidth makes every nonzero transfer lose to recompute."""
    monkeypatch.setenv(ENV_LINK_GBPS, "0.000001")  # 1 KB/s
    slow = FetchCostModel()
    assert not slow.decide(64, 1 << 20).fetch
    monkeypatch.setenv(ENV_LINK_GBPS, "1000")
    fast = FetchCostModel()
    assert fast.decide(64, 1 << 20).fetch


def test_prefill_overhead_favors_fetch_for_small_blocks():
    """Defaults include the fixed per-prefill cost (an extra scheduling
    round + dispatch), so tiny transfers win even when the FLOPs alone
    would not justify a fetch."""
    m = FetchCostModel()
    d = m.decide(n_tokens=16, nbytes=4096)
    assert d.fetch
    assert d.fetch_s < m.prefill_overhead_s


def test_last_decision_exported_in_stats():
    m = _bare_model()
    m.decide(128, 1024)
    s = m.stats()
    assert s["last_decision"]["n_tokens"] == 128
    assert s["last_decision"]["nbytes"] == 1024
    assert s["link_bw_pinned"] is True
