#!/usr/bin/env python3
"""Lint the Prometheus registry for exposition hygiene.

Checks, against a freshly constructed ``PrometheusRegistry``:

- every metric attribute on the registry is listed in ``_metrics`` (a
  metric recorded but missing from the list silently never renders on
  ``/metrics``), and vice versa (no orphans in the render list);
- metric names match ``vllm:[a-z0-9_]+`` and are unique;
- every metric has non-empty HELP documentation;
- the overload/lifecycle metric names the README documents are present
  (a rename here silently breaks dashboards and runbooks).

Run standalone (``python tools/check_metrics.py``, exit 1 on failure)
or via the tier-1 wrapper ``tests/metrics/test_check_metrics.py``.
"""

from __future__ import annotations

import os
import re
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

NAME_RE = re.compile(r"^vllm:[a-z0-9_]+$")

# Documented in the README ("Overload & lifecycle" / "Resilience");
# keep in sync with PrometheusRegistry.
REQUIRED_LIFECYCLE_METRICS = {
    "vllm:requests_shed_total",
    "vllm:request_timeouts_total",
    "vllm:stream_outputs_dropped_total",
    "vllm:requests_aborted_slow_client_total",
    "vllm:lifecycle_draining",
    "vllm:inflight_prompt_tokens",
    "vllm:requests_lost_on_restart_total",
}

# Documented in the README ("Fault injection & chaos testing");
# dashboards for coordinator failover alert on these names.
REQUIRED_CHAOS_METRICS = {
    "vllm:coordinator_up",
    "vllm:coordinator_restarts_total",
    "vllm:dp_snapshot_age_seconds",
    "vllm:dp_routing_degraded",
    "vllm:failpoints_fired_total",
}

# Documented in the README ("Execution guards & quarantine");
# the quarantine chaos scenario asserts on these names.
REQUIRED_CONTAINMENT_METRICS = {
    "vllm:numeric_guard_trips_total",
    "vllm:step_watchdog_trips_total",
    "vllm:requests_quarantined_total",
}

# Documented in the README ("Frontend scale-out & KV-aware routing");
# the session-affinity acceptance test asserts on these names.
REQUIRED_ROUTER_METRICS = {
    "vllm:dp_routing_decisions_total",
    "vllm:dp_prefix_hit_blocks",
    "vllm:api_server_index",
    "vllm:api_server_count",
}

# Documented in the README ("Decode performance"); bench dashboards
# track decode-batch purity and multi-step amortization by these names.
REQUIRED_DECODE_METRICS = {
    "vllm:decode_batch_ratio",
    "vllm:sampled_tokens_per_launch",
    "vllm:prep_fallback_rows_total",
}

# Documented in the README ("Metrics"); the benchmark's per-layer readers
# (benchmark/layer_metrics/) read these names, and every phase below as a
# label of vllm:engine_step_duration_seconds.
REQUIRED_STEP_METRICS = {
    "vllm:engine_step_duration_seconds",
    "vllm:engine_steps_total",
    "vllm:engine_step_tokens_total",
    "vllm:attn_context_tokens_total",
    "vllm:attn_ragged_pairs_total",
    "vllm:attn_decode_tiles_total",
    "vllm:step_bucket_compiles",
    "vllm:step_first_use_seconds_total",
    # The start and compiling (README "The start and compiling"): the
    # benchmark's five setup_* readers read these.
    "vllm:step_first_use_stage_seconds_total",
    "vllm:compile_seconds_total",
    "vllm:compile_cache_total",
    "vllm:startup_seconds",
}
# Documented in the README ("Generation by diffusion over blocks"); the
# benchmark's readers diffusion_tokens_per_pass, moe_experts_hit_share,
# moe_gmm_roofline and block_attn_roofline read these names (the fifth is
# the kind "block" of vllm:attn_context_tokens_total).
REQUIRED_DIFFUSION_MOE_METRICS = {
    "vllm:diffusion_passes_total",
    "vllm:diffusion_committed_tokens_total",
    "vllm:diffusion_stores_fused_total",
    "vllm:diffusion_head_positions_total",
    "vllm:diffusion_head_steps_total",
    "vllm:moe_assignments_total",
    "vllm:moe_experts_hit_total",
    "vllm:moe_pad_pairs_skipped_total",
    "vllm:moe_gmm_visits_total",
}
DIFFUSION_PASS_KINDS = ("denoise", "store")
REQUIRED_STEP_PHASES = ("schedule", "dispatch", "prepare", "finalize",
                        "fetch", "update", "output")
STEP_KINDS = ("decode", "mixed")

# Documented in the README ("Sampling performance"); the benchmark's
# ``sampler_fallback_rows_per_step`` reads the second.
REQUIRED_SAMPLER_METRICS = {
    "vllm:sampler_kernel_launches_total",
    "vllm:sampler_fallback_rows_total",
}

# Documented in the README ("Multi-host fault tolerance"); the mesh
# shrink/rejoin acceptance tests assert on these names.
REQUIRED_MESH_METRICS = {
    "vllm:mesh_rank_losses_total",
    "vllm:mesh_recoveries_total",
    "vllm:mesh_size",
    "vllm:mesh_recovery_duration_seconds",
}

# Documented in the README ("Adaptive speculation").
REQUIRED_ADAPTIVE_SPEC_METRICS = {
    "vllm:spec_decode_acceptance_rate",
    "vllm:spec_decode_draft_len",
    "vllm:spec_decode_suspended",
    "vllm:spec_decode_suspensions_total",
}

# Documented in the README ("Tiered KV fabric"); the cross-engine
# prefix-hit acceptance test and chaos scenarios assert on these names.
REQUIRED_KV_FABRIC_METRICS = {
    "vllm:kv_fabric_tier_blocks",
    "vllm:kv_fabric_tier_bytes",
    "vllm:kv_fabric_fetch_total",
    "vllm:kv_fabric_demotions_total",
    "vllm:kv_fabric_fetch_bytes_total",
}

# Documented in the README ("Disaggregated serving"); the chaos
# --disagg scenario and the parity acceptance test assert on these.
REQUIRED_DISAGG_METRICS = {
    "vllm:disagg_handoffs_total",
    "vllm:disagg_push_bytes_total",
    "vllm:disagg_handoff_duration_seconds",
}

# Documented in the README ("SLO scoreboard"); the replay bench and
# per-class dashboards read these names.
REQUIRED_SLO_METRICS = {
    "vllm:request_ttft_seconds",
    "vllm:request_itl_seconds",
    "vllm:slo_attainment",
    "vllm:request_trace_records_total",
}

# Documented in the README ("Elastic capacity"); the traffic-ramp chaos
# scenario and capacity dashboards assert on these names.
REQUIRED_AUTOSCALE_METRICS = {
    "vllm:pool_size_desired",
    "vllm:pool_size_actual",
    "vllm:scale_events_total",
    "vllm:engine_drain_duration_seconds",
    "vllm:weight_reseed_total",
    "vllm:kv_fabric_tier_occupancy",
}

# Documented in the README ("QoS & brownout"); the overload-storm chaos
# scenario and the bench FIFO-vs-QoS A/B assert on these names.
REQUIRED_QOS_METRICS = {
    "vllm:brownout_rung",
    "vllm:brownout_transitions_total",
    "vllm:brownout_time_at_rung_seconds",
    "vllm:pressure_preemptions_total",
    "vllm:tenant_inflight_tokens",
    "vllm:tenant_debt",
}

# Documented in the README ("Zero-downtime operations"); the rolling-
# upgrade chaos scenario and mixed-pool dashboards assert on these.
REQUIRED_UPGRADE_METRICS = {
    "vllm:upgrade_events_total",
    "vllm:upgrade_in_progress",
    "vllm:engine_version_info",
    "vllm:config_reloads_total",
    "vllm:schema_mismatch_total",
}

def check() -> list[str]:
    """Return a list of lint errors (empty = clean)."""
    from vllm_tpu.metrics.prometheus import (
        BiLabeledCounter,
        BiLabeledGauge,
        Counter,
        Gauge,
        Histogram,
        InfoGauge,
        LabeledCounter,
        LabeledGauge,
        LabeledHistogram,
        PrometheusRegistry,
    )

    metric_types = (BiLabeledCounter, BiLabeledGauge, Counter, Gauge, Histogram,
                    InfoGauge, LabeledCounter, LabeledGauge,
                    LabeledHistogram)
    reg = PrometheusRegistry()
    errors: list[str] = []

    attr_metrics = [
        (attr, m) for attr, m in vars(reg).items()
        if isinstance(m, metric_types)
    ]
    listed_ids = {id(m) for m in reg._metrics}
    attr_ids = {id(m) for _, m in attr_metrics}

    for attr, m in attr_metrics:
        if id(m) not in listed_ids:
            errors.append(
                f"registry.{attr} ({m.name}) is not in _metrics — "
                f"it will never render on /metrics")
    for m in reg._metrics:
        if id(m) not in attr_ids:
            errors.append(
                f"_metrics entry {m.name} is not a registry attribute")

    seen: dict[str, str] = {}
    for attr, m in attr_metrics:
        if not NAME_RE.match(m.name):
            errors.append(
                f"registry.{attr}: name {m.name!r} does not match "
                f"vllm:[a-z0-9_]+")
        if not (getattr(m, "doc", "") or "").strip():
            errors.append(f"registry.{attr} ({m.name}): empty HELP doc")
        if m.name in seen:
            errors.append(
                f"duplicate metric name {m.name} "
                f"(registry.{seen[m.name]} and registry.{attr})")
        else:
            seen[m.name] = attr

    for name in sorted(REQUIRED_LIFECYCLE_METRICS - set(seen)):
        errors.append(
            f"required lifecycle metric {name} is missing from the "
            f"registry (documented in README)")
    for name in sorted(REQUIRED_CHAOS_METRICS - set(seen)):
        errors.append(
            f"required coordinator/chaos metric {name} is missing from "
            f"the registry (documented in README)")
    for name in sorted(REQUIRED_CONTAINMENT_METRICS - set(seen)):
        errors.append(
            f"required containment metric {name} is missing from "
            f"the registry (documented in README)")
    for name in sorted(REQUIRED_ROUTER_METRICS - set(seen)):
        errors.append(
            f"required router metric {name} is missing from "
            f"the registry (documented in README)")
    for name in sorted(REQUIRED_MESH_METRICS - set(seen)):
        errors.append(
            f"required mesh metric {name} is missing from "
            f"the registry (documented in README)")
    for name in sorted(REQUIRED_DECODE_METRICS - set(seen)):
        errors.append(
            f"required decode metric {name} is missing from "
            f"the registry (documented in README)")
    for name in sorted(REQUIRED_STEP_METRICS - set(seen)):
        errors.append(
            f"required step metric {name} is missing from "
            f"the registry (documented in README)")
    errors += check_step_exposition()
    for name in sorted(REQUIRED_DIFFUSION_MOE_METRICS - set(seen)):
        errors.append(
            f"required diffusion/MoE metric {name} is missing from "
            f"the registry (documented in README)")
    errors += check_diffusion_exposition()
    for name in sorted(REQUIRED_SAMPLER_METRICS - set(seen)):
        errors.append(
            f"required sampler metric {name} is missing from "
            f"the registry (documented in README)")
    for name in sorted(REQUIRED_ADAPTIVE_SPEC_METRICS - set(seen)):
        errors.append(
            f"required adaptive-spec metric {name} is missing from "
            f"the registry (documented in README)")
    for name in sorted(REQUIRED_KV_FABRIC_METRICS - set(seen)):
        errors.append(
            f"required kv-fabric metric {name} is missing from "
            f"the registry (documented in README)")
    for name in sorted(REQUIRED_DISAGG_METRICS - set(seen)):
        errors.append(
            f"required disagg metric {name} is missing from "
            f"the registry (documented in README)")
    for name in sorted(REQUIRED_SLO_METRICS - set(seen)):
        errors.append(
            f"required SLO-scoreboard metric {name} is missing from "
            f"the registry (documented in README)")
    for name in sorted(REQUIRED_AUTOSCALE_METRICS - set(seen)):
        errors.append(
            f"required elastic-capacity metric {name} is missing from "
            f"the registry (documented in README)")
    for name in sorted(REQUIRED_QOS_METRICS - set(seen)):
        errors.append(
            f"required QoS/brownout metric {name} is missing from "
            f"the registry (documented in README)")
    for name in sorted(REQUIRED_UPGRADE_METRICS - set(seen)):
        errors.append(
            f"required zero-downtime metric {name} is missing from "
            f"the registry (documented in README)")

    return errors


def check_step_exposition() -> list[str]:
    """One engine step's stats through a fresh registry: every required
    phase and both kinds of step must come out on /metrics."""
    from vllm_tpu.core.sched_output import SchedulerStats
    from vllm_tpu.metrics.prometheus import PrometheusRegistry
    from vllm_tpu.metrics.stats import IterationStats

    phases = {f"step_{p}_times": [0.001]
              for p in REQUIRED_STEP_PHASES if p != "output"}
    by_kind = {k: 1 for k in STEP_KINDS}
    reg = PrometheusRegistry()
    try:
        reg.record(
            SchedulerStats(**phases, steps_by_kind=by_kind,
                           step_tokens_by_kind=by_kind,
                           attn_context_tokens_by_kind=by_kind,
                           attn_ragged_pairs={"live": 1, "computed": 1},
                           attn_decode_tiles={"run": 1, "least": 1.0}),
            IterationStats(output_s=0.001))
    except (TypeError, AttributeError) as e:
        return [f"the step stats no longer reach the registry: {e}"]
    text = reg.render()
    wanted = [f'vllm:engine_step_duration_seconds_count{{phase="{p}"}} 1'
              for p in REQUIRED_STEP_PHASES]
    wanted += [f'{name}{{kind="{k}"}} 1.0' for k in STEP_KINDS
               for name in ("vllm:engine_steps_total",
                            "vllm:engine_step_tokens_total",
                            "vllm:attn_context_tokens_total")]
    wanted += [f'vllm:attn_ragged_pairs_total{{kind="{k}"}} 1.0'
               for k in ("live", "computed")]
    wanted += [f'vllm:attn_decode_tiles_total{{kind="{k}"}} 1.0'
               for k in ("run", "least")]
    return [f"/metrics lacks the sample {w!r}" for w in wanted
            if w not in text]


def check_diffusion_exposition() -> list[str]:
    """The runner's counters of generation by diffusion over blocks and of
    the mixture of experts through a fresh registry: both kinds of pass,
    the block rows' context and the plain counters must come out."""
    from vllm_tpu.core.sched_output import SchedulerStats
    from vllm_tpu.metrics.prometheus import PrometheusRegistry

    reg = PrometheusRegistry()
    try:
        reg.record(SchedulerStats(
            diffusion_passes_by_kind={k: 2 for k in DIFFUSION_PASS_KINDS},
            diffusion_committed_tokens=3, diffusion_stores_fused=4,
            diffusion_head_positions={"live": 8, "computed": 16},
            diffusion_head_steps_by_rung={"quarter": 1},
            moe_assignments=5, moe_experts_hit=7, moe_pad_pairs_skipped=13,
            moe_gmm_visits={"first": 17, "resident": 0},
            attn_context_tokens_by_kind={"block": 11}))
    except (TypeError, AttributeError) as e:
        return [f"the diffusion stats no longer reach the registry: {e}"]
    text = reg.render()
    wanted = [f'vllm:diffusion_passes_total{{kind="{k}"}} 2.0'
              for k in DIFFUSION_PASS_KINDS]
    wanted += ["vllm:diffusion_committed_tokens_total 3.0",
               "vllm:diffusion_stores_fused_total 4.0",
               'vllm:diffusion_head_positions_total{kind="live"} 8.0',
               'vllm:diffusion_head_positions_total{kind="computed"} 16.0',
               'vllm:diffusion_head_steps_total{rung="quarter"} 1.0',
               "vllm:moe_assignments_total 5.0",
               "vllm:moe_experts_hit_total 7.0",
               "vllm:moe_pad_pairs_skipped_total 13.0",
               'vllm:moe_gmm_visits_total{fetch="first"} 17.0',
               'vllm:moe_gmm_visits_total{fetch="resident"} 0.0',
               'vllm:attn_context_tokens_total{kind="block"} 11.0']
    return [f"/metrics lacks the sample {w!r}" for w in wanted
            if w not in text]


def main() -> int:
    errors = check()
    for err in errors:
        print(f"ERROR: {err}", file=sys.stderr)
    if errors:
        return 1
    from vllm_tpu.metrics.prometheus import PrometheusRegistry
    print(f"ok: {len(PrometheusRegistry()._metrics)} metrics checked")
    return 0


if __name__ == "__main__":
    sys.exit(main())
