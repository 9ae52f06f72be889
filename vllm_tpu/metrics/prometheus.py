"""Prometheus metrics (text exposition, no client-library dependency).

Reference analog: ``vllm/v1/metrics/prometheus.py`` + the metric definitions
in ``vllm/v1/metrics/loggers.py``; same metric names where they map, so
vLLM dashboards point at this server unchanged.
"""

from __future__ import annotations

import time
from typing import Any

from vllm_tpu.core.sched_output import STEP_PHASES, SchedulerStats


class Counter:
    def __init__(self, name: str, doc: str) -> None:
        self.name, self.doc, self.value = name, doc, 0.0

    def inc(self, v: float = 1.0) -> None:
        self.value += v

    def inc_to(self, v: float) -> None:
        """Monotonic ratchet: adopt an externally tracked cumulative value
        without ever letting the rendered counter decrease (a render
        racing the source's reset/respawn must not show a decrease)."""
        if v > self.value:
            self.value = v

    def render(self) -> str:
        return (
            f"# HELP {self.name} {self.doc}\n# TYPE {self.name} counter\n"
            f"{self.name} {self.value}\n"
        )


class Gauge:
    def __init__(self, name: str, doc: str) -> None:
        self.name, self.doc, self.value = name, doc, 0.0

    def set(self, v: float) -> None:
        self.value = v

    def render(self) -> str:
        return (
            f"# HELP {self.name} {self.doc}\n# TYPE {self.name} gauge\n"
            f"{self.name} {self.value}\n"
        )


class Histogram:
    def __init__(self, name: str, doc: str, buckets: list[float]) -> None:
        self.name, self.doc = name, doc
        self.buckets = sorted(buckets)
        self.counts = [0] * len(self.buckets)
        self.total = 0
        self.sum = 0.0

    def observe(self, v: float) -> None:
        self.total += 1
        self.sum += v
        for i, b in enumerate(self.buckets):
            if v <= b:
                self.counts[i] += 1

    def render(self) -> str:
        out = [
            f"# HELP {self.name} {self.doc}",
            f"# TYPE {self.name} histogram",
        ]
        for b, c in zip(self.buckets, self.counts):
            out.append(f'{self.name}_bucket{{le="{b}"}} {c}')
        out.append(f'{self.name}_bucket{{le="+Inf"}} {self.total}')
        out.append(f"{self.name}_sum {self.sum}")
        out.append(f"{self.name}_count {self.total}")
        return "\n".join(out) + "\n"


class LabeledHistogram:
    """One histogram family with a single label dimension (e.g. engine
    step phase): per-key bucket vectors rendered under one HELP/TYPE."""

    def __init__(self, name: str, doc: str, label: str,
                 buckets: list[float]) -> None:
        self.name, self.doc, self.label = name, doc, label
        self.buckets = sorted(buckets)
        self.series: dict[str, Histogram] = {}

    def observe(self, key: str, v: float) -> None:
        h = self.series.get(key)
        if h is None:
            h = self.series[key] = Histogram(self.name, self.doc, self.buckets)
        h.observe(v)

    def touch(self, key: str) -> None:
        """Pre-create an empty series so the family renders zeroed
        buckets before the first observation (scrapers and the render
        grammar expect every histogram family to carry samples)."""
        if key not in self.series:
            self.series[key] = Histogram(self.name, self.doc, self.buckets)

    def render(self) -> str:
        out = [
            f"# HELP {self.name} {self.doc}",
            f"# TYPE {self.name} histogram",
        ]
        for key in sorted(self.series):
            h = self.series[key]
            kv = f'{self.label}="{key}"'
            for b, c in zip(h.buckets, h.counts):
                out.append(f'{self.name}_bucket{{{kv},le="{b}"}} {c}')
            out.append(f'{self.name}_bucket{{{kv},le="+Inf"}} {h.total}')
            out.append(f'{self.name}_sum{{{kv}}} {h.sum}')
            out.append(f'{self.name}_count{{{kv}}} {h.total}')
        return "\n".join(out) + "\n"


class LabeledCounter:
    """One counter family with a single label dimension (e.g. finish
    reason)."""

    def __init__(self, name: str, doc: str, label: str) -> None:
        self.name, self.doc, self.label = name, doc, label
        self.values: dict[str, float] = {}

    def inc(self, key: str, v: float = 1.0) -> None:
        self.values[key] = self.values.get(key, 0.0) + v

    def inc_to(self, key: str, v: float) -> None:
        """Monotonic ratchet (see Counter.inc_to): counters refreshed from
        a live snapshot must never render a decrease."""
        if v > self.values.get(key, 0.0):
            self.values[key] = v

    def render(self) -> str:
        out = [
            f"# HELP {self.name} {self.doc}",
            f"# TYPE {self.name} counter",
        ]
        for key in sorted(self.values):
            out.append(
                f'{self.name}{{{self.label}="{key}"}} {self.values[key]}'
            )
        return "\n".join(out) + "\n"


class BiLabeledCounter:
    """One counter family with two label dimensions (e.g. scale
    direction x outcome)."""

    kind = "counter"  # the exposition's TYPE

    def __init__(self, name: str, doc: str, label1: str,
                 label2: str) -> None:
        self.name, self.doc = name, doc
        self.label1, self.label2 = label1, label2
        self.values: dict[tuple[str, str], float] = {}

    def inc(self, key1: str, key2: str, v: float = 1.0) -> None:
        self.values[(key1, key2)] = self.values.get((key1, key2), 0.0) + v

    def inc_to(self, key1: str, key2: str, v: float) -> None:
        """Monotonic ratchet (see Counter.inc_to): counters refreshed from
        a live snapshot must never render a decrease."""
        if v > self.values.get((key1, key2), 0.0):
            self.values[(key1, key2)] = v

    def render(self) -> str:
        out = [
            f"# HELP {self.name} {self.doc}",
            f"# TYPE {self.name} {self.kind}",
        ]
        for (k1, k2) in sorted(self.values):
            out.append(
                f'{self.name}{{{self.label1}="{k1}",{self.label2}="{k2}"}}'
                f" {self.values[(k1, k2)]}"
            )
        return "\n".join(out) + "\n"


class BiLabeledGauge(BiLabeledCounter):
    """One gauge family with two label dimensions: set, not counted up."""

    kind = "gauge"

    def set(self, key1: str, key2: str, v: float) -> None:
        self.values[(key1, key2)] = v


class InfoGauge:
    """Info-style gauge family: every sample has value 1 and the labels
    ARE the payload (the Prometheus ``*_info`` convention — identity,
    not a quantity). One series per key; ``set`` replaces the key's
    labels wholesale so an upgraded engine's fingerprint swap renders as
    one series changing, never two coexisting."""

    def __init__(self, name: str, doc: str) -> None:
        self.name, self.doc = name, doc
        self.series: dict[str, dict[str, str]] = {}

    def set(self, key: str, labels: dict) -> None:
        self.series[key] = {
            k: str(v) for k, v in labels.items() if v is not None
        }

    def prune(self, keys) -> None:
        """Drop series whose key is no longer live (a retired engine
        slot must not keep exporting its old version forever)."""
        keep = set(keys)
        for key in list(self.series):
            if key not in keep:
                del self.series[key]

    def render(self) -> str:
        out = [
            f"# HELP {self.name} {self.doc}",
            f"# TYPE {self.name} gauge",
        ]
        for key in sorted(self.series):
            kv = ",".join(
                f'{k}="{v}"' for k, v in sorted(self.series[key].items())
            )
            out.append(f"{self.name}{{{kv}}} 1.0")
        return "\n".join(out) + "\n"


class LabeledGauge:
    """One gauge family with a single label dimension (e.g. engine id)."""

    def __init__(self, name: str, doc: str, label: str) -> None:
        self.name, self.doc, self.label = name, doc, label
        self.values: dict[str, float] = {}

    def set(self, key: str, v: float) -> None:
        self.values[key] = v

    def render(self) -> str:
        out = [
            f"# HELP {self.name} {self.doc}",
            f"# TYPE {self.name} gauge",
        ]
        for key in sorted(self.values):
            out.append(
                f'{self.name}{{{self.label}="{key}"}} {self.values[key]}'
            )
        return "\n".join(out) + "\n"


class PrometheusRegistry:
    """StatLogger + /metrics renderer."""

    def __init__(self, engine: Any = None) -> None:
        self.num_running = Gauge(
            "vllm:num_requests_running", "Number of running requests")
        self.num_waiting = Gauge(
            "vllm:num_requests_waiting", "Number of waiting requests")
        self.kv_usage = Gauge(
            "vllm:gpu_cache_usage_perc", "KV cache usage fraction")
        self.prefix_queries = Counter(
            "vllm:prefix_cache_queries", "Prefix-cache block queries")
        self.prefix_hits = Counter(
            "vllm:prefix_cache_hits", "Prefix-cache block hits")
        self.spec_draft = Counter(
            "vllm:spec_decode_num_draft_tokens",
            "Speculative draft tokens proposed")
        self.spec_accepted = Counter(
            "vllm:spec_decode_num_accepted_tokens",
            "Speculative draft tokens accepted")
        self.preempted = Counter(
            "vllm:num_preemptions", "Cumulative preemptions")
        self.generation_tokens = Counter(
            "vllm:generation_tokens", "Cumulative generated tokens")
        self.prompt_tokens = Counter(
            "vllm:prompt_tokens", "Cumulative prefilled tokens")
        self.ttft = Histogram(
            "vllm:time_to_first_token_seconds", "TTFT",
            [0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0])
        self.tpot = Histogram(
            "vllm:time_per_output_token_seconds", "Inter-token latency",
            [0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0])
        self.e2e = Histogram(
            "vllm:e2e_request_latency_seconds", "Request E2E latency",
            [0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0])
        self.queue_time = Histogram(
            "vllm:request_queue_time_seconds",
            "Time spent waiting before first schedule",
            [0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 20.0, 60.0])
        self.accept_length = Histogram(
            "vllm:spec_decode_acceptance_length",
            "Generated tokens per spec verification step (accepted+bonus)",
            [1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 8.0, 12.0])
        self.spec_acceptance_rate = Gauge(
            "vllm:spec_decode_acceptance_rate",
            "Global per-position draft acceptance rate (adaptive EMA)")
        self.spec_draft_len = Histogram(
            "vllm:spec_decode_draft_len",
            "Draft tokens scheduled per spec verification step",
            [1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0])
        self.spec_suspended = Gauge(
            "vllm:spec_decode_suspended",
            "1 while adaptive speculation is occupancy-suspended")
        self.spec_suspensions = Counter(
            "vllm:spec_decode_suspensions_total",
            "Occupancy-gated speculation suspensions (high-watermark trips)")
        self.bucket_compiles = Counter(
            "vllm:step_bucket_compiles",
            "Jitted-step bucket cache misses (new (tokens,reqs,blocks))")
        self.bucket_hits = Counter(
            "vllm:step_bucket_hits", "Jitted-step bucket cache hits")
        self.first_use_seconds = Counter(
            "vllm:step_first_use_seconds_total",
            "Seconds spent in first uses of a step program (traced, "
            "lowered, compiled or loaded inside the step's dispatch); "
            "vllm:step_bucket_compiles counts them")
        self.first_use_stage_seconds = LabeledCounter(
            "vllm:step_first_use_stage_seconds_total",
            "The seconds of vllm:step_first_use_seconds_total by stage: "
            "trace and lower (Python), backend (XLA's compile or the load "
            "from the persistent cache), run (the rest of the call)",
            "stage")
        # What JAX itself reports of compiling, for the whole engine
        # process (tracing.CompileEvents): the memory profile's compile
        # and every small jit beside the step programs.
        self.compile_seconds = LabeledCounter(
            "vllm:compile_seconds_total",
            "Seconds JAX spent tracing (trace), lowering (lower) and in the "
            "backend (XLA's compile or the load from the persistent "
            "cache), by stage, from jax.monitoring", "stage")
        self.compile_cache = LabeledCounter(
            "vllm:compile_cache_total",
            "Programs the persistent compilation cache had (hit) or got "
            "(miss), from jax.monitoring", "result")
        # The start, by phase (tracing.StartupTimeline): set once, when
        # the server is ready and when an engine process's phases arrive
        # with its first stats snapshot.
        self.startup_seconds = LabeledGauge(
            "vllm:startup_seconds",
            "Seconds of the start by phase, process creation to a server "
            "that answers; a phase's seconds are its own, less the phases "
            "inside it, and the phases add up", "phase")
        # By kind of step: "decode" = the decode-only step that runs the
        # decode attention kernel, "mixed" = every other. Counted where
        # the runner builds the step.
        self.steps_by_kind = LabeledCounter(
            "vllm:engine_steps_total",
            "Engine steps dispatched, by kind of step", "kind")
        self.step_tokens_by_kind = LabeledCounter(
            "vllm:engine_step_tokens_total",
            "Tokens scheduled in dispatched steps, by kind of step", "kind")
        self.attn_context_tokens = LabeledCounter(
            "vllm:attn_context_tokens_total",
            "Context tokens attended: the sum over a step's rows of the "
            "positions each row attends to, by kind of step", "kind")
        self.attn_ragged_pairs = LabeledCounter(
            "vllm:attn_ragged_pairs_total",
            "(query token, key) pairs of the ragged attention kernel's "
            "calls, a q head a layer: live (the mask lets them through) "
            "and computed (the kernel's tiles work them out)", "kind")
        self.attn_decode_tiles = LabeledCounter(
            "vllm:attn_decode_tiles_total",
            "Tiles (a block of rows x the pages of one tile) of the decode "
            "attention kernel, one call a decode-only step that takes it: "
            "run (its walk over the live blocks) and least (what the "
            "rows' own keys need)", "kind")
        # Generation by diffusion over blocks and mixture of experts,
        # counted by the runner (cumulative).
        self.diffusion_passes = LabeledCounter(
            "vllm:diffusion_passes_total",
            "Passes over a request's open block under generation by "
            "diffusion, one a row a step: denoise (samples and commits; "
            "one that also stores the clean block before its own counts "
            "here, once) or store (runs the clean block once more for its "
            "K/V and nothing else)", "kind")
        self.diffusion_stores_fused = Counter(
            "vllm:diffusion_stores_fused_total",
            "Clean blocks whose store rode with the next block's first "
            "denoising pass, in one row of two blocks, under generation "
            "by diffusion over blocks")
        self.diffusion_committed = Counter(
            "vllm:diffusion_committed_tokens_total",
            "Positions that denoising passes committed under generation "
            "by diffusion over blocks")
        self.diffusion_head_positions = LabeledCounter(
            "vllm:diffusion_head_positions_total",
            "Positions of the open blocks in steps under generation by "
            "diffusion over blocks: live (those of the step's live rows) "
            "and computed (those the head and the sampler ran over: the "
            "least of a quarter, a half or all of the request bucket "
            "that holds the live rows)", "kind")
        self.diffusion_head_steps = LabeledCounter(
            "vllm:diffusion_head_steps_total",
            "Steps under generation by diffusion over blocks, by the "
            "share of the request bucket their head ran over: quarter, "
            "half or whole", "rung")
        self.moe_assignments = Counter(
            "vllm:moe_assignments_total",
            "Token-expert pairs over the live tokens of dispatched steps, "
            "summed over layers")
        self.moe_experts_hit = Counter(
            "vllm:moe_experts_hit_total",
            "Experts that got at least one live token, counted per layer "
            "and step and summed")
        self.moe_pad_pairs_skipped = Counter(
            "vllm:moe_pad_pairs_skipped_total",
            "Token-expert pairs of the pad tokens of dispatched steps, "
            "which are routed (to any expert, held here or not) and kept "
            "out of the grouped matmul, summed over layers")
        self.moe_gmm_visits = LabeledCounter(
            "vllm:moe_gmm_visits_total",
            "Visits of the experts' grouped matmul, a (group, row tile) "
            "pair a column tile a call: first (waits for the expert's "
            "matrix and starts the copy of one further on) and resident "
            "(runs on the matrix its group's first visit brought)", "fetch")
        self.linear_attn_state_rows = Counter(
            "vllm:linear_attn_state_rows_total",
            "Rows whose slot state (a linear-attention matrix, a short "
            "convolution's tail) a dispatched step reads and writes, "
            "summed over the layers of that kind")
        self.linear_attn_tokens = LabeledCounter(
            "vllm:linear_attn_tokens_total",
            "Tokens through the slot-state layers, summed over "
            "them: decode (a row's one token) or chunk (a row of "
            "several)", "kind")
        self.window_attn_rows = Counter(
            "vllm:window_attn_rows_total",
            "Rows a dispatched step runs through window attention, summed "
            "over the layers of that kind (a model that mixes window and "
            "full layers)")
        self.window_attn_pairs = Counter(
            "vllm:window_attn_pairs_total",
            "(query token, key) pairs the window's mask lets through over "
            "the rows of dispatched steps, a q head a window layer")
        # KV groups over the one page pool (core/kv_cache_manager.py).
        self.kv_group_pages_in_use = LabeledGauge(
            "vllm:kv_group_pages_in_use",
            "Pages of the pool the requests of a KV group hold", "group")
        self.kv_pages_allocated = LabeledCounter(
            "vllm:kv_pages_allocated_total",
            "Pages a KV group took from the pool (prefix-cache hits "
            "touched count too)", "group")
        self.kv_window_pages_freed = Counter(
            "vllm:kv_window_pages_freed_total",
            "Pages the window groups gave back to the pool before their "
            "request ended: wholly under the window's floor")
        self.kv_group_page_tokens = LabeledGauge(
            "vllm:kv_group_page_tokens",
            "Tokens one page of the pool holds of a KV group's layers "
            "(groups whose layers cost unlike bytes a token differ in it, "
            "never in a page's bytes); fixed at start", "group")
        self.kv_token_bytes = BiLabeledGauge(
            "vllm:kv_token_bytes",
            "Bytes one token costs a KV group's layers: published (a key "
            "and a value a KV head as the model's config states them) and "
            "held (as the cache lays them out: lane padding, and a short "
            "group's places no layer writes); fixed at start",
            "group", "what")
        self.attn_sink_rows = Counter(
            "vllm:attn_sink_rows_total",
            "Rows a dispatched step ran through attention with a sink "
            "logit a head inside the softmax, summed over the layers that "
            "have one")
        self.kv_page_steps = LabeledCounter(
            "vllm:kv_page_steps_total",
            "Pages summed over the scheduler's steps: held (by requests, "
            "all groups) and pool (all there are); held over pool between "
            "two scrapes is the mean share of the pool in use", "kind")
        self.state_slots_in_use = Gauge(
            "vllm:state_slots_in_use",
            "Per-request state slots held by the runner's rows")
        self.pipeline_stall = Counter(
            "vllm:pipeline_stall_seconds",
            "Seconds the async lag-N pipeline blocked on device results")
        # Decode-path efficiency (runner cumulative counters -> derived
        # gauges): what fraction of jitted-step launches took the
        # decode-only shape (sequence-pipelined attention kernel), and
        # how many sampled tokens each launch emits (more than one a
        # row under speculation and generation by diffusion).
        self.decode_batch_ratio = Gauge(
            "vllm:decode_batch_ratio",
            "Fraction of jitted-step launches that were decode-only "
            "(cumulative since engine start)")
        self.tokens_per_launch = Gauge(
            "vllm:sampled_tokens_per_launch",
            "Sampled tokens per jitted-step launch (cumulative average)")
        self.prep_fallback_rows = Counter(
            "vllm:prep_fallback_rows_total",
            "Step-input rows assembled by the Python fallback instead of "
            "the native host-prep fill")
        self.sampler_kernel_launches = Counter(
            "vllm:sampler_kernel_launches_total",
            "In-jit sample() calls routed to the fused sort-free sampling "
            "kernel")
        self.sampler_fallback_rows = Counter(
            "vllm:sampler_fallback_rows_total",
            "Sampling (non-greedy) rows sampled by the XLA reference path "
            "because the fused sampling kernel was ineligible or disabled")
        self.request_success = LabeledCounter(
            "vllm:request_success_total",
            "Finished requests by reason", "finished_reason")
        # Engine-step phase timing (plumbed from the trace_span sites in
        # engine_core.step via SchedulerStats): one histogram family
        # labeled by phase, plus batch-occupancy / step-interval gauges.
        self.step_duration = LabeledHistogram(
            "vllm:engine_step_duration_seconds",
            "Engine step phase duration, in the order of a step: schedule, "
            "dispatch (prepare, the host building the step's inputs, nests "
            "inside it), finalize (fetch, the host waiting for the device, "
            "nests inside it), update (the scheduler folding the outputs "
            "in) and output (the frontend's detokenise-and-deliver)",
            "phase",
            [0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
             0.05, 0.1, 0.25, 0.5, 1.0, 2.5])
        self.batch_tokens = Gauge(
            "vllm:engine_batch_tokens",
            "Tokens in the last dispatched engine batch")
        self.batch_requests = Gauge(
            "vllm:engine_batch_requests",
            "Requests in the last dispatched engine batch")
        self.batch_occupancy = Gauge(
            "vllm:engine_batch_occupancy",
            "Fraction of the token budget used by the last dispatched batch")
        self.step_interval = Gauge(
            "vllm:engine_step_interval_seconds",
            "Wall time between the last two engine step completions")
        # Resilience (vllm_tpu/resilience): refreshed from the engine's
        # live snapshot at render time, so /metrics reflects the crash/
        # recovery state without event plumbing through stat records.
        self.engine_up = LabeledGauge(
            "vllm:engine_up",
            "Engine-core liveness (1 = serving, 0 = down/respawning)",
            "engine_id")
        self.engine_restarts = LabeledCounter(
            "vllm:engine_restarts_total",
            "Engine-core process respawns", "engine_id")
        self.requests_replayed = Counter(
            "vllm:requests_replayed_total",
            "Requests resumed on a respawned engine core")
        self.requests_failed_on_crash = Counter(
            "vllm:requests_failed_on_crash_total",
            "Requests failed because an engine core crashed")
        self.requests_lost_on_restart = Counter(
            "vllm:requests_lost_on_restart_total",
            "Requests found in the persisted journal after a frontend "
            "restart (lost in flight)")
        # DP coordinator failover + fault injection (refreshed from the
        # live engine snapshot at render time, same scheme as above).
        self.coordinator_up = Gauge(
            "vllm:coordinator_up",
            "DP coordinator liveness (1 = running, 0 = down/respawning); "
            "control-plane only, never part of data-plane readiness")
        self.coordinator_restarts = Counter(
            "vllm:coordinator_restarts_total",
            "DP coordinator process respawns")
        self.coordinator_snapshot_age = Gauge(
            "vllm:dp_snapshot_age_seconds",
            "Age of the newest coordinator load snapshot (heartbeats at "
            "1 Hz; staleness flips routing to round-robin)")
        self.routing_degraded = Gauge(
            "vllm:dp_routing_degraded",
            "1 while DP routing runs round-robin on a stale coordinator "
            "snapshot, else 0")
        self.failpoints_fired = LabeledCounter(
            "vllm:failpoints_fired_total",
            "Fault injections fired, by failpoint site "
            "(nonzero only under VLLM_TPU_FAILPOINTS)", "site")
        # Lifecycle / overload protection (vllm_tpu/resilience/lifecycle):
        # refreshed from the engine's live snapshot at render time, same
        # scheme as the resilience metrics above.
        self.requests_shed = BiLabeledCounter(
            "vllm:requests_shed_total",
            "Requests rejected by admission control, by reason and "
            "tenant (the per-reason sums across tenants equal the "
            "pre-QoS reason-only totals)", "reason", "tenant")
        self.request_timeouts = LabeledCounter(
            "vllm:request_timeouts_total",
            "Requests finished by deadline enforcement", "kind")
        self.stream_outputs_dropped = Counter(
            "vllm:stream_outputs_dropped_total",
            "Intermediate outputs dropped on bounded streams "
            "(slow clients, drop_oldest policy)")
        self.slow_client_aborts = Counter(
            "vllm:requests_aborted_slow_client_total",
            "Requests aborted because the client consumed too slowly "
            "(abort policy)")
        self.lifecycle_draining = Gauge(
            "vllm:lifecycle_draining",
            "1 while the server is draining (admission closed), else 0")
        self.inflight_prompt_tokens = Gauge(
            "vllm:inflight_prompt_tokens",
            "Prompt tokens reserved by admitted in-flight requests")
        # QoS under pressure (vllm_tpu/resilience/qos): brownout-ladder
        # state, per-tenant WFQ accounting, and load-based priority
        # preemptions. Ladder/WFQ families refresh from the engine's
        # qos_status() at render time; the rung gauge and preemption
        # counter also ride SchedulerStats from the engine core.
        self.brownout_rung = Gauge(
            "vllm:brownout_rung",
            "Current brownout-ladder rung (0 = normal, 1 = speculation "
            "suspended, 2 = prefill chunks shrunk, 3 = batch-class "
            "admissions shed, 4 = batch decodes preempted)")
        self.brownout_transitions = BiLabeledCounter(
            "vllm:brownout_transitions_total",
            "Brownout-ladder transitions, by rung entered and direction "
            "(up = escalation, down = hysteresis-gated disengage)",
            "rung", "direction")
        self.brownout_time_at_rung = LabeledGauge(
            "vllm:brownout_time_at_rung_seconds",
            "Cumulative seconds the brownout ladder has spent at each "
            "rung (the time-at-rung histogram for bench artifacts)",
            "rung")
        self.pressure_preemptions = Counter(
            "vllm:pressure_preemptions_total",
            "Running decodes preempted by the load-based priority "
            "trigger (queued higher-priority work missing its TTFT "
            "budget, or brownout rung 4); journal-backed, token-"
            "identical resume")
        self.tenant_inflight_tokens = LabeledGauge(
            "vllm:tenant_inflight_tokens",
            "Prompt tokens reserved per tenant in the weighted-fair-"
            "queueing admission ledger", "tenant")
        self.tenant_debt = LabeledGauge(
            "vllm:tenant_debt",
            "Per-tenant WFQ virtual-time debt (how far ahead of its "
            "weighted share the tenant has consumed; 0 = at or below "
            "share)", "tenant")
        # Execution-layer fault containment (PR 5): numeric guards,
        # step watchdog, poison-request quarantine.
        self.numeric_guard_trips = LabeledCounter(
            "vllm:numeric_guard_trips_total",
            "Requests failed by the numeric integrity guard "
            "(nan = non-finite logits row, sampled = out-of-range token)",
            "kind")
        self.step_watchdog_trips = Counter(
            "vllm:step_watchdog_trips_total",
            "Device steps that exceeded the step-watchdog deadline "
            "(wedged device step, escalated to an engine restart)")
        self.requests_quarantined = Counter(
            "vllm:requests_quarantined_total",
            "Requests dead-lettered by poison-request quarantine")
        # Frontend scale-out + prefix-cache-aware DP routing (PR 6):
        # decision counters drained from the client's RoutingStats at
        # render time (drain=True — each prefix-hit length must land in
        # the histogram exactly once; /health peeks with drain=False).
        self.dp_routing_decisions = LabeledCounter(
            "vllm:dp_routing_decisions_total",
            "DP routing decisions by ladder rung "
            "(prefix = cached-prefix placement, least_loaded = fewest "
            "in-flight, round_robin = stale-snapshot fallback)", "kind")
        self.dp_prefix_hit_blocks = Histogram(
            "vllm:dp_prefix_hit_blocks",
            "Cached-prefix length (blocks) of prefix-routed requests",
            [1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0])
        self.api_server_index = Gauge(
            "vllm:api_server_index",
            "This frontend's shard index (0-based; 0 when single-server)")
        self.api_server_count = Gauge(
            "vllm:api_server_count",
            "Number of API-server frontends sharing the listen port")
        self.api_server_count.set(1.0)
        # Multi-host mesh fault tolerance (PR 7): refreshed from the
        # engine's mesh status at render time; all zero/absent-valued
        # unless the heartbeat ring (VLLM_TPU_MESH_HB_ADDRS) is armed.
        self.mesh_rank_losses = Counter(
            "vllm:mesh_rank_losses_total",
            "Mesh ranks declared lost (silent past the death timeout)")
        self.mesh_recoveries = Counter(
            "vllm:mesh_recoveries_total",
            "Completed mesh recoveries (supervised shrink or grow-back)")
        self.mesh_size = Gauge(
            "vllm:mesh_size",
            "Live mesh member count (world size minus lost ranks)")
        self.mesh_recovery_duration = Histogram(
            "vllm:mesh_recovery_duration_seconds",
            "Wall time of a mesh recovery (loss/rejoin noticed -> "
            "re-bootstrapped, resharded, serving)",
            [0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0])
        # Tiered KV fabric (vllm_tpu/kv_fabric): per-tier occupancy and
        # the fetch-vs-recompute decision counters, attached to
        # SchedulerStats by EngineCore when the fabric connector is
        # active (all absent-valued otherwise).
        self.kv_fabric_tier_blocks = LabeledGauge(
            "vllm:kv_fabric_tier_blocks",
            "KV blocks resident per fabric tier (device = HBM prefix "
            "cache, host = host-RAM cold tier)", "tier")
        self.kv_fabric_fetches = LabeledCounter(
            "vllm:kv_fabric_fetch_total",
            "Fabric remote-prefix decisions by outcome (fetched = "
            "cost model accepted a peer fetch, recompute = fetch costed "
            "out, miss = no peer held the prefix, failed = transfer "
            "tore and the request fell back to recompute)", "outcome")
        self.kv_fabric_demotions = LabeledCounter(
            "vllm:kv_fabric_demotions_total",
            "Blocks demoted down the tier ladder (device = last HBM "
            "copy evicted, host = host-tier LRU eviction, store = "
            "write-through to the shared block store)", "tier")
        self.kv_fabric_fetch_bytes = Counter(
            "vllm:kv_fabric_fetch_bytes_total",
            "Encoded bytes pulled over the fabric wire by peer fetches")
        self.kv_fabric_tier_bytes = LabeledGauge(
            "vllm:kv_fabric_tier_bytes",
            "Encoded KV bytes resident per fabric tier (device = HBM "
            "prefix cache estimated from block bytes, host = host-RAM "
            "cold tier actual encoded footprint)", "tier")
        self.kv_fabric_tier_occupancy = LabeledGauge(
            "vllm:kv_fabric_tier_occupancy",
            "Fraction of each fabric tier's budget in use (host = "
            "encoded bytes over the --kv-connector-cache-gb budget, "
            "device = HBM prefix-cache blocks over capacity); feeds "
            "the elastic-capacity controller's memory-pressure signal",
            "tier")
        # Disaggregated prefill/decode serving (vllm_tpu/disagg):
        # handoff outcomes are refreshed from the client coordinator's
        # live snapshot at render time (same pull scheme as routing);
        # push bytes ride SchedulerStats from the prefill engine.
        self.disagg_handoffs = LabeledCounter(
            "vllm:disagg_handoffs_total",
            "Prefill->decode handoffs by outcome (pushed = decode side "
            "resumed on pushed KV, recompute = push torn/missed and the "
            "decode side re-prefilled locally, local = request finished "
            "during its prefill leg, aborted = client/engine abort "
            "mid-handoff)", "outcome")
        self.disagg_push_bytes = Counter(
            "vllm:disagg_push_bytes_total",
            "Encoded KV bytes pushed over the fabric wire by "
            "prefill->decode handoffs")
        self.disagg_handoff_duration = Histogram(
            "vllm:disagg_handoff_duration_seconds",
            "Handoff wall time (prefill admission -> decode side's first "
            "post-resume tokens)",
            [0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0])
        self.disagg_pending = Gauge(
            "vllm:disagg_pending_handoffs",
            "Handoffs currently in flight (clamped prefill leg admitted, "
            "decode side not yet producing)")
        # Elastic capacity (vllm_tpu/resilience/autoscale): pool sizing
        # and scale-event outcomes, refreshed from the AsyncLLM pool
        # snapshot at render time (same pull scheme as routing/disagg).
        self.pool_size_desired = Gauge(
            "vllm:pool_size_desired",
            "Engine count the elastic-capacity controller wants (tracks "
            "actual when no controller is armed)")
        self.pool_size_actual = Gauge(
            "vllm:pool_size_actual",
            "Routable engines right now (up, not draining, not retired)")
        self.scale_events = BiLabeledCounter(
            "vllm:scale_events_total",
            "Completed pool scale events by direction and outcome "
            "(reseeded = newcomer booted from a live peer's weights, "
            "fallback_checkpoint = peer re-seed failed and the slot "
            "reloaded from checkpoint, drained = victim retired after "
            "its in-flight requests finished, deadline_replay = drain "
            "deadline hit and stragglers replayed on survivors, "
            "timeout/died_draining/orphaned = chaos paths)",
            "direction", "outcome")
        self.engine_drain_duration = Histogram(
            "vllm:engine_drain_duration_seconds",
            "Wall time from scale-down victim selection to slot "
            "retirement",
            [0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0])
        self.weight_reseed = LabeledCounter(
            "vllm:weight_reseed_total",
            "Peer weight re-seed attempts by outcome (ok = newcomer "
            "adopted a live peer's weights over the fabric push path, "
            "fallback = checkpoint reload)", "outcome")
        # Zero-downtime operations (vllm_tpu/resilience/rolling +
        # versioning): upgrade-cycle outcomes, live-config pushes, and
        # the version identity of every pool member, refreshed from the
        # AsyncLLM upgrade/version snapshots at render time.
        self.upgrade_events = LabeledCounter(
            "vllm:upgrade_events_total",
            "Completed rolling-upgrade cycles by outcome (ok = every "
            "slot promoted, rolled_back = a newcomer failed its health "
            "gate and the old slot kept serving, aborted = operator "
            "abort honored at the next safe point)", "outcome")
        self.upgrade_in_progress = Gauge(
            "vllm:upgrade_in_progress",
            "1 while a rolling-upgrade cycle is active (spawning/"
            "booting/gating/draining/rolling_back), else 0")
        self.engine_version_info = InfoGauge(
            "vllm:engine_version_info",
            "Version identity per pool member (engine slots plus the "
            "frontend): package version, wire-schema version, config "
            "hash, weights fingerprint; value is always 1")
        self.config_reloads_total = LabeledCounter(
            "vllm:config_reloads_total",
            "Live-config push attempts by outcome (ok = applied "
            "pool-wide without restart, rejected = a non-updatable key "
            "was refused, error = the engine-side apply failed)",
            "outcome")
        self.schema_mismatch = LabeledCounter(
            "vllm:schema_mismatch_total",
            "Version-stamped artifacts rejected for speaking a "
            "different wire/journal schema, by boundary kind (ready = "
            "ZMQ engine handshake, journal = crash-journal snapshot, "
            "handoff = disagg KV handoff record, trace = request-trace "
            "replay)", "kind")
        # SLO scoreboard (vllm_tpu/metrics/reqtrace + goodput): per-class
        # latency families fed from the class-labeled IterationStats
        # samples, a sliding-window attainment gauge pulled from the
        # engine at render time, and the trace-capture counter.
        self.slo_ttft = LabeledHistogram(
            "vllm:request_ttft_seconds",
            "Time to first token by SLO class (unlabeled requests land "
            "in the 'default' class)", "slo_class",
            [0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0])
        self.slo_itl = LabeledHistogram(
            "vllm:request_itl_seconds",
            "Inter-token latency by SLO class (unlabeled requests land "
            "in the 'default' class)", "slo_class",
            [0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0])
        self.slo_attainment = LabeledGauge(
            "vllm:slo_attainment",
            "Sliding-window fraction of finished requests meeting their "
            "class SLO targets (--slo-targets; absent classes have no "
            "configured targets)", "slo_class")
        self.trace_records = Counter(
            "vllm:request_trace_records_total",
            "Requests journaled to the --request-trace-dir JSONL trace")
        from vllm_tpu.metrics.stats import DEFAULT_SLO_CLASS
        self.slo_ttft.touch(DEFAULT_SLO_CLASS)
        self.slo_itl.touch(DEFAULT_SLO_CLASS)
        self._metrics = [
            self.num_running, self.num_waiting, self.kv_usage,
            self.prefix_queries, self.prefix_hits, self.preempted,
            self.spec_draft, self.spec_accepted,
            self.generation_tokens, self.prompt_tokens,
            self.ttft, self.tpot, self.e2e,
            self.queue_time, self.accept_length,
            self.spec_acceptance_rate, self.spec_draft_len,
            self.spec_suspended, self.spec_suspensions,
            self.bucket_compiles, self.bucket_hits, self.first_use_seconds,
            self.first_use_stage_seconds, self.compile_seconds,
            self.compile_cache, self.startup_seconds,
            self.steps_by_kind, self.step_tokens_by_kind,
            self.attn_context_tokens, self.attn_ragged_pairs,
            self.attn_decode_tiles, self.pipeline_stall,
            self.diffusion_passes, self.diffusion_committed,
            self.diffusion_stores_fused,
            self.diffusion_head_positions, self.diffusion_head_steps,
            self.moe_assignments, self.moe_experts_hit,
            self.moe_pad_pairs_skipped, self.moe_gmm_visits,
            self.linear_attn_state_rows, self.linear_attn_tokens,
            self.window_attn_rows, self.window_attn_pairs,
            self.kv_group_pages_in_use, self.kv_pages_allocated,
            self.kv_window_pages_freed, self.kv_page_steps,
            self.kv_group_page_tokens, self.kv_token_bytes,
            self.attn_sink_rows,
            self.state_slots_in_use,
            self.decode_batch_ratio, self.tokens_per_launch,
            self.prep_fallback_rows,
            self.sampler_kernel_launches, self.sampler_fallback_rows,
            self.request_success,
            self.step_duration, self.batch_tokens, self.batch_requests,
            self.batch_occupancy, self.step_interval,
            self.engine_up, self.engine_restarts,
            self.requests_replayed, self.requests_failed_on_crash,
            self.requests_lost_on_restart,
            self.coordinator_up, self.coordinator_restarts,
            self.coordinator_snapshot_age, self.routing_degraded,
            self.failpoints_fired,
            self.requests_shed, self.request_timeouts,
            self.stream_outputs_dropped, self.slow_client_aborts,
            self.lifecycle_draining, self.inflight_prompt_tokens,
            self.brownout_rung, self.brownout_transitions,
            self.brownout_time_at_rung, self.pressure_preemptions,
            self.tenant_inflight_tokens, self.tenant_debt,
            self.numeric_guard_trips, self.step_watchdog_trips,
            self.requests_quarantined,
            self.dp_routing_decisions, self.dp_prefix_hit_blocks,
            self.api_server_index, self.api_server_count,
            self.mesh_rank_losses, self.mesh_recoveries,
            self.mesh_size, self.mesh_recovery_duration,
            self.kv_fabric_tier_blocks, self.kv_fabric_fetches,
            self.kv_fabric_demotions, self.kv_fabric_fetch_bytes,
            self.kv_fabric_tier_bytes, self.kv_fabric_tier_occupancy,
            self.disagg_handoffs, self.disagg_push_bytes,
            self.disagg_handoff_duration, self.disagg_pending,
            self.pool_size_desired, self.pool_size_actual,
            self.scale_events, self.engine_drain_duration,
            self.weight_reseed,
            self.upgrade_events, self.upgrade_in_progress,
            self.engine_version_info, self.config_reloads_total,
            self.schema_mismatch,
            self.slo_ttft, self.slo_itl, self.slo_attainment,
            self.trace_records,
        ]
        self._engine = engine
        self._last_prefix = (0, 0)
        self._last_preempted = 0
        self._last_spec = (0, 0)
        self._last_buckets = (0, 0)
        self._last_stall = 0.0
        self._last_prep_fallback = 0
        self._last_sampler_kernel = 0
        self._last_sampler_fallback = 0
        # pid -> the phases of that process's start; and when this
        # process's own start was over (perf_counter's clock).
        self._startup_by_pid: dict[int, dict[str, float]] = {}
        self._startup_ready_at: float | None = None

    def record_startup(self, snapshot: dict) -> None:
        """This process's own start, when it is ready
        (``StartupTimeline.ready()``)."""
        self._startup_ready_at = time.perf_counter()
        self._take_startup(snapshot)

    def _take_startup(self, snapshot: dict) -> None:
        """One process's start: this one's, or an engine process's with its
        first stats snapshot. A phase reads the sum over the processes that
        took part (the front end's ``import`` and the engine's follow each
        other). A process created after this one was ready is a respawn,
        not the start: left out."""
        if (self._startup_ready_at is not None
                and snapshot["created"] > self._startup_ready_at):
            return
        self._startup_by_pid[snapshot["pid"]] = snapshot["phases"]
        for phases in self._startup_by_pid.values():
            for phase in phases:
                self.startup_seconds.set(phase, sum(
                    p.get(phase, 0.0)
                    for p in self._startup_by_pid.values()))

    # StatLoggerBase interface -----------------------------------------

    def record(self, scheduler_stats: SchedulerStats | None,
               iteration_stats: Any | None = None) -> None:
        if scheduler_stats is not None:
            s = scheduler_stats
            self.num_running.set(s.num_running_reqs)
            self.num_waiting.set(s.num_waiting_reqs)
            self.kv_usage.set(s.kv_cache_usage)
            lq, lh = self._last_prefix
            self.prefix_queries.inc(max(0, s.prefix_cache_queries - lq))
            self.prefix_hits.inc(max(0, s.prefix_cache_hits - lh))
            self._last_prefix = (s.prefix_cache_queries, s.prefix_cache_hits)
            self.preempted.inc(max(0, s.num_preempted_reqs - self._last_preempted))
            self._last_preempted = s.num_preempted_reqs
            ld, la = self._last_spec
            self.spec_draft.inc(max(0, s.spec_num_draft_tokens - ld))
            self.spec_accepted.inc(max(0, s.spec_num_accepted_tokens - la))
            self._last_spec = (
                s.spec_num_draft_tokens, s.spec_num_accepted_tokens,
            )
            for t in s.queue_times:
                self.queue_time.observe(t)
            for n in s.spec_accept_lengths:
                self.accept_length.observe(n)
            if s.spec_acceptance_rate_ema is not None:
                self.spec_acceptance_rate.set(s.spec_acceptance_rate_ema)
            for n in s.spec_draft_lens:
                self.spec_draft_len.observe(n)
            self.spec_suspended.set(1.0 if s.spec_suspended else 0.0)
            self.spec_suspensions.inc_to(s.spec_suspensions)
            lc, lh = self._last_buckets
            self.bucket_compiles.inc(max(0, s.bucket_compiles - lc))
            self.bucket_hits.inc(max(0, s.bucket_hits - lh))
            self._last_buckets = (s.bucket_compiles, s.bucket_hits)
            self.pipeline_stall.inc(
                max(0.0, s.pipeline_stall_s - self._last_stall)
            )
            self._last_stall = s.pipeline_stall_s
            if s.step_launches > 0:
                self.decode_batch_ratio.set(
                    s.decode_only_launches / s.step_launches)
                self.tokens_per_launch.set(
                    s.launch_sampled_tokens / s.step_launches)
            self.prep_fallback_rows.inc(
                max(0, s.prep_fallback_rows - self._last_prep_fallback))
            self._last_prep_fallback = s.prep_fallback_rows
            self.sampler_kernel_launches.inc(
                max(0, s.sampler_kernel_launches - self._last_sampler_kernel))
            self._last_sampler_kernel = s.sampler_kernel_launches
            self.sampler_fallback_rows.inc(
                max(0, s.sampler_fallback_rows - self._last_sampler_fallback))
            self._last_sampler_fallback = s.sampler_fallback_rows
            for phase in STEP_PHASES:
                for t in getattr(s, f"step_{phase}_times"):
                    self.step_duration.observe(phase, t)
            # Runner-side cumulative counters: ratchet, never assign.
            self.first_use_seconds.inc_to(s.step_first_use_s)
            if s.compile_seconds is not None:
                # The three ride together, and only after they moved.
                for counter, moved in (
                    (self.first_use_stage_seconds, s.step_first_use_stage_s),
                    (self.compile_seconds, s.compile_seconds),
                    (self.compile_cache, s.compile_cache),
                ):
                    for key, v in (moved or {}).items():
                        counter.inc(key, 0.0)  # a sample also where it is 0
                        counter.inc_to(key, float(v))
            if s.startup is not None:
                self._take_startup(s.startup)
            for counter, by_kind in (
                (self.steps_by_kind, s.steps_by_kind),
                (self.step_tokens_by_kind, s.step_tokens_by_kind),
                (self.attn_context_tokens, s.attn_context_tokens_by_kind),
                (self.attn_ragged_pairs, s.attn_ragged_pairs),
                (self.attn_decode_tiles, s.attn_decode_tiles),
                (self.linear_attn_tokens, s.linear_attn_tokens_by_kind),
                (self.diffusion_head_positions, s.diffusion_head_positions),
                (self.diffusion_head_steps, s.diffusion_head_steps_by_rung),
            ):
                for kind, n in by_kind.items():
                    counter.inc_to(kind, float(n))
            if any(s.diffusion_passes_by_kind.values()):
                # Once a pass is counted, a sample of each kind, also
                # where it is 0: with every store riding along "store"
                # stays there for whoever divides by denoise + store.
                for kind, n in s.diffusion_passes_by_kind.items():
                    self.diffusion_passes.inc(kind, 0.0)
                    self.diffusion_passes.inc_to(kind, float(n))
            self.diffusion_committed.inc_to(
                float(s.diffusion_committed_tokens))
            self.diffusion_stores_fused.inc_to(
                float(s.diffusion_stores_fused))
            self.moe_assignments.inc_to(float(s.moe_assignments))
            self.moe_experts_hit.inc_to(float(s.moe_experts_hit))
            self.moe_pad_pairs_skipped.inc_to(
                float(s.moe_pad_pairs_skipped))
            if any(s.moe_gmm_visits.values()):
                # Both labels once a visit is counted: "resident" stays 0
                # where no group straddles a row tile.
                for fetch, n in s.moe_gmm_visits.items():
                    self.moe_gmm_visits.inc(fetch, 0.0)
                    self.moe_gmm_visits.inc_to(fetch, float(n))
            self.linear_attn_state_rows.inc_to(
                float(s.linear_attn_state_rows))
            self.window_attn_rows.inc_to(float(s.window_attn_rows))
            self.window_attn_pairs.inc_to(float(s.window_attn_pairs))
            for g, n in enumerate(s.kv_group_pages_in_use):
                self.kv_group_pages_in_use.set(str(g), n)
            for g, n in enumerate(s.kv_pages_allocated):
                self.kv_pages_allocated.inc_to(str(g), float(n))
            self.kv_window_pages_freed.inc_to(float(s.kv_window_pages_freed))
            for g, n in enumerate(s.kv_group_page_tokens):
                self.kv_group_page_tokens.set(str(g), n)
            for g, costs in enumerate(s.kv_token_bytes):
                for what, n in costs.items():
                    self.kv_token_bytes.set(str(g), what, n)
            self.attn_sink_rows.inc_to(float(s.attn_sink_rows))
            for kind, n in s.kv_page_steps.items():
                self.kv_page_steps.inc_to(kind, float(n))
            self.state_slots_in_use.set(s.state_slots_in_use)
            self.batch_tokens.set(s.batch_num_tokens)
            self.batch_requests.set(s.batch_num_reqs)
            self.batch_occupancy.set(s.batch_occupancy)
            self.step_interval.set(s.step_interval_s)
            # Runner-side cumulative counters (cross the proc boundary
            # inside SchedulerStats): ratchet, never assign.
            for kind, n in s.numeric_guard_trips.items():
                self.numeric_guard_trips.inc_to(kind, float(n))
            self.step_watchdog_trips.inc_to(float(s.step_watchdog_trips))
            self.brownout_rung.set(float(s.brownout_rung))
            self.pressure_preemptions.inc_to(
                float(s.pressure_preemptions))
            if s.kv_fabric:
                fab = s.kv_fabric
                for tier, n in (fab.get("tier_blocks") or {}).items():
                    self.kv_fabric_tier_blocks.set(tier, float(n))
                # Cumulative engine-side counters crossing the proc
                # boundary: ratchet, never assign.
                for outcome, n in (fab.get("fetch") or {}).items():
                    self.kv_fabric_fetches.inc_to(outcome, float(n))
                for tier, n in (fab.get("demotions") or {}).items():
                    self.kv_fabric_demotions.inc_to(tier, float(n))
                self.kv_fabric_fetch_bytes.inc_to(
                    float(fab.get("fetch_bytes", 0)))
                for tier, n in (fab.get("tier_bytes") or {}).items():
                    self.kv_fabric_tier_bytes.set(tier, float(n))
                for tier, n in (fab.get("tier_occupancy") or {}).items():
                    self.kv_fabric_tier_occupancy.set(tier, float(n))
                self.disagg_push_bytes.inc_to(
                    float(fab.get("push_bytes", 0)))
        if iteration_stats is not None:
            if iteration_stats.output_s is not None:
                self.step_duration.observe(
                    "output", iteration_stats.output_s)
            self.generation_tokens.inc(iteration_stats.num_generation_tokens)
            self.prompt_tokens.inc(iteration_stats.num_prompt_tokens)
            for t in iteration_stats.ttfts:
                self.ttft.observe(t)
            for t in iteration_stats.inter_token_latencies:
                self.tpot.observe(t)
            for t in iteration_stats.e2e_latencies:
                self.e2e.observe(t)
            for cls, t in iteration_stats.ttfts_by_class:
                self.slo_ttft.observe(cls, t)
            for cls, t in iteration_stats.itls_by_class:
                self.slo_itl.observe(cls, t)
            for reason in iteration_stats.finished_reasons:
                self.request_success.inc(reason)

    def _refresh_resilience(self) -> None:
        engine = self._engine
        if engine is None or not hasattr(engine, "resilience_status"):
            return
        try:
            status = engine.resilience_status()
        except Exception:
            return
        for eid, st in status.get("engines", {}).items():
            self.engine_up.set(eid, 1.0 if st.get("up") else 0.0)
            # Ratchet, don't assign: a render racing an engine respawn
            # (snapshot briefly rebuilt from scratch) must never show a
            # counter decrease, which scrapers read as a process restart.
            self.engine_restarts.inc_to(eid, float(st.get("restarts", 0)))
        self.requests_replayed.inc_to(
            float(status.get("requests_replayed_total", 0)))
        self.requests_failed_on_crash.inc_to(
            float(status.get("requests_failed_on_crash_total", 0)))
        self.requests_lost_on_restart.inc_to(
            float(status.get("requests_lost_on_restart_total", 0)))
        self.requests_quarantined.inc_to(
            float(status.get("requests_quarantined_total", 0)))
        # MP engines hard-exit on a watchdog trip (their stats never
        # flow), so the client-side count is the authoritative source
        # there; in-proc trips arrive via SchedulerStats instead.
        self.step_watchdog_trips.inc_to(
            float(status.get("step_watchdog_trips_total", 0)))
        coord = status.get("coordinator")
        if coord is not None:
            self.coordinator_up.set(1.0 if coord.get("up") else 0.0)
            self.coordinator_restarts.inc_to(
                float(coord.get("restarts", 0)))
            self.coordinator_snapshot_age.set(
                float(coord.get("snapshot_age_s", 0.0)))
            self.routing_degraded.set(
                1.0 if coord.get("routing_degraded") else 0.0)
        mesh = status.get("mesh")
        if mesh is not None:
            self.mesh_size.set(float(mesh.get("size", 0)))
            self.mesh_rank_losses.inc_to(
                float(mesh.get("rank_losses_total", 0)))
            self.mesh_recoveries.inc_to(
                float(mesh.get("recoveries_total", 0)))
            # The durations list is cumulative (it also feeds /health) —
            # a high-water mark keeps each recovery observed exactly once.
            durations = mesh.get("recovery_durations", []) or []
            seen = getattr(self, "_mesh_durations_seen", 0)
            for d in durations[seen:]:
                self.mesh_recovery_duration.observe(float(d))
            self._mesh_durations_seen = max(seen, len(durations))

    def _refresh_failpoints(self) -> None:
        from vllm_tpu.resilience import failpoints

        if not failpoints.is_active():
            return
        for site, counts in failpoints.snapshot().items():
            self.failpoints_fired.inc_to(site, float(counts["fires"]))

    def set_frontend(self, index: int, count: int) -> None:
        """Stamp this registry with its API-server shard identity
        (called by the multi-server topology launcher)."""
        self.api_server_index.set(float(index))
        self.api_server_count.set(float(count))

    def _refresh_routing(self) -> None:
        engine = self._engine
        if engine is None or not hasattr(engine, "routing_status"):
            return
        try:
            status = engine.routing_status(drain=True)
        except Exception:
            return
        if not status:
            return
        # Decision totals are cumulative in RoutingStats → ratchet; hit
        # lengths arrive drained (since last render) → observe each once.
        for kind, n in status.get("decisions", {}).items():
            self.dp_routing_decisions.inc_to(kind, float(n))
        # Phase-rung narrowings (disagg pools) ride the same labeled
        # counter; they are not terminal rungs, so they live apart from
        # the decision totals in the snapshot.
        for phase, n in status.get("phases", {}).items():
            self.dp_routing_decisions.inc_to(f"phase_{phase}", float(n))
        for blocks in status.get("hit_blocks", []):
            self.dp_prefix_hit_blocks.observe(float(blocks))

    def _refresh_disagg(self) -> None:
        engine = self._engine
        if engine is None or not hasattr(engine, "disagg_status"):
            return
        try:
            status = engine.disagg_status(drain=True)
        except Exception:
            return
        if not status:
            return
        # Outcome totals are cumulative in the coordinator → ratchet;
        # durations arrive drained (since last render) → observe once.
        for outcome, n in status.get("outcomes", {}).items():
            self.disagg_handoffs.inc_to(outcome, float(n))
        for d in status.get("durations_s", []):
            self.disagg_handoff_duration.observe(float(d))
        self.disagg_pending.set(float(status.get("pending", 0)))

    def _refresh_autoscale(self) -> None:
        engine = self._engine
        if engine is None or not hasattr(engine, "autoscale_status"):
            return
        try:
            status = engine.autoscale_status(drain=True)
        except Exception:
            return
        if not status:
            return
        pool = status.get("pool", {})
        ctrl = status.get("controller")
        actual = float(pool.get("actual", 0))
        self.pool_size_actual.set(actual)
        self.pool_size_desired.set(
            float(ctrl["desired"]) if ctrl is not None else actual)
        # Event totals are cumulative in the controller snapshot →
        # ratchet; drain durations arrive drained (since last render)
        # → observe each once.
        if ctrl is not None:
            for key, n in (ctrl.get("scale_events_total") or {}).items():
                direction, _, outcome = key.partition("/")
                self.scale_events.inc_to(direction, outcome, float(n))
            for outcome, n in (ctrl.get("weight_reseed_total")
                               or {}).items():
                self.weight_reseed.inc_to(outcome, float(n))
        for d in pool.get("drain_durations_s", []):
            self.engine_drain_duration.observe(float(d))

    def _refresh_upgrade(self) -> None:
        engine = self._engine
        if engine is None:
            return
        if hasattr(engine, "upgrade_status"):
            try:
                status = engine.upgrade_status()
            except Exception:
                status = None
            if status is not None:
                ctrl = status.get("controller") or {}
                self.upgrade_in_progress.set(
                    1.0 if ctrl.get("active") else 0.0)
                # Cycle/reload totals are cumulative in the controller
                # snapshot → ratchet.
                for outcome, n in (ctrl.get("upgrade_events_total")
                                   or {}).items():
                    self.upgrade_events.inc_to(outcome, float(n))
                for outcome, n in (status.get("config_reloads_total")
                                   or {}).items():
                    self.config_reloads_total.inc_to(outcome, float(n))
        if hasattr(engine, "version_status"):
            try:
                versions = engine.version_status()
            except Exception:
                return
            live: list[str] = []
            frontend = versions.get("frontend")
            if frontend:
                live.append("frontend")
                self.engine_version_info.set(
                    "frontend", {"member": "frontend", **frontend})
            for eid, block in (versions.get("engines") or {}).items():
                key = f"engine-{eid}"
                live.append(key)
                self.engine_version_info.set(
                    key, {"member": key, **(block or {})})
            self.engine_version_info.prune(live)
            for kind, n in (versions.get("schema_mismatch_total")
                            or {}).items():
                self.schema_mismatch.inc_to(kind, float(n))

    def _refresh_lifecycle(self) -> None:
        engine = self._engine
        if engine is None or not hasattr(engine, "lifecycle_status"):
            return
        try:
            status = engine.lifecycle_status()
        except Exception:
            return
        shed_by_tenant = status.get("shed_by_tenant")
        if shed_by_tenant is not None:
            for reason, by_tenant in shed_by_tenant.items():
                for tenant, n in by_tenant.items():
                    self.requests_shed.inc_to(reason, tenant, float(n))
        else:
            # Older snapshot shape (engine stubs): fold the reason-only
            # totals into the default tenant.
            for reason, n in status.get("shed", {}).items():
                self.requests_shed.inc_to(reason, "default", float(n))
        for kind, n in status.get("timeouts", {}).items():
            self.request_timeouts.inc_to(kind, float(n))
        self.stream_outputs_dropped.inc_to(
            float(status.get("stream_outputs_dropped_total", 0)))
        self.slow_client_aborts.inc_to(
            float(status.get("slow_client_aborts_total", 0)))
        self.lifecycle_draining.set(1.0 if status.get("draining") else 0.0)
        self.inflight_prompt_tokens.set(
            float(status.get("inflight_prompt_tokens", 0)))

    def _refresh_qos(self) -> None:
        engine = self._engine
        if engine is None or not hasattr(engine, "qos_status"):
            return
        try:
            status = engine.qos_status()
        except Exception:
            return
        wfq = status.get("wfq") or {}
        for tenant, n in (wfq.get("inflight_tokens") or {}).items():
            self.tenant_inflight_tokens.set(tenant, float(n))
        for tenant, d in (wfq.get("debt") or {}).items():
            self.tenant_debt.set(tenant, float(d))
        brown = status.get("brownout")
        if brown is not None:
            self.brownout_rung.set(float(brown.get("rung", 0)))
            for rung, t in (brown.get("time_at_rung") or {}).items():
                self.brownout_time_at_rung.set(rung, float(t))
            # Transition totals are cumulative in the controller →
            # ratchet ("<rung>:<direction>" keys in the snapshot).
            for key, n in (brown.get("transitions") or {}).items():
                rung, _, direction = key.partition(":")
                self.brownout_transitions.inc_to(
                    rung, direction, float(n))

    def _refresh_slo(self) -> None:
        engine = self._engine
        if engine is None or not hasattr(engine, "slo_status"):
            return
        try:
            status = engine.slo_status()
        except Exception:
            return
        if not status:
            return
        for cls, entry in status.get("attainment", {}).items():
            self.slo_attainment.set(cls, float(entry["attainment"]))
        trace = status.get("trace")
        if trace is not None:
            self.trace_records.inc_to(float(trace.get("records_total", 0)))

    def render(self) -> str:
        self._refresh_resilience()
        self._refresh_lifecycle()
        self._refresh_qos()
        self._refresh_routing()
        self._refresh_disagg()
        self._refresh_autoscale()
        self._refresh_upgrade()
        self._refresh_failpoints()
        self._refresh_slo()
        return "".join(m.render() for m in self._metrics)


_SAMPLE_RE = None  # compiled lazily in merge_expositions


def merge_expositions(texts: dict[str, str]) -> str:
    """Merge per-frontend Prometheus expositions into one pool view
    (the /metrics/cluster endpoint body).

    Counter and histogram samples with identical name+labels are SUMMED
    across frontends — a pool-wide total is the only coherent reading of
    a cumulative series. Gauges (and untyped samples) are NOT summable
    in general (an attainment fraction summed over frontends is
    nonsense), so each keeps its per-frontend value under an added
    ``frontend="<key>"`` label. HELP/TYPE headers come from the first
    frontend that carries the metric; metric order follows first
    appearance."""
    global _SAMPLE_RE
    import re

    if _SAMPLE_RE is None:
        _SAMPLE_RE = re.compile(
            r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{.*\})?\s+(\S+)$"
        )

    order: list[str] = []
    headers: dict[str, list[str]] = {}
    types: dict[str, str] = {}
    # base -> {(sample_name, labels): value} for summable metrics
    summed: dict[str, dict[tuple[str, str], float]] = {}
    # base -> [(frontend, sample_name, labels, raw_value)] otherwise
    labeled: dict[str, list[tuple[str, str, str, str]]] = {}

    for fe in sorted(texts):
        local_types: dict[str, str] = {}
        for line in texts[fe].splitlines():
            line = line.rstrip()
            if not line:
                continue
            if line.startswith("#"):
                parts = line.split(None, 3)
                if len(parts) >= 4 and parts[1] in ("HELP", "TYPE"):
                    name = parts[2]
                    if parts[1] == "TYPE":
                        local_types[name] = parts[3]
                        types.setdefault(name, parts[3])
                    if name not in headers:
                        headers[name] = []
                        order.append(name)
                    if len(headers[name]) < 2 and line not in headers[name]:
                        # First frontend's HELP + TYPE pair only.
                        if not any(
                            h.split(None, 2)[1] == parts[1]
                            for h in headers[name]
                        ):
                            headers[name].append(line)
                continue
            m = _SAMPLE_RE.match(line)
            if m is None:
                continue
            sample_name, labels, raw = m.group(1), m.group(2) or "", m.group(3)
            base = sample_name
            for suffix in ("_bucket", "_sum", "_count"):
                candidate = sample_name[: -len(suffix)] if sample_name.endswith(suffix) else None
                if candidate and local_types.get(candidate) == "histogram":
                    base = candidate
                    break
            if base not in headers:
                headers[base] = []
                order.append(base)
            mtype = types.get(base)
            if mtype in ("counter", "histogram"):
                try:
                    value = float(raw)
                except ValueError:
                    continue
                bucket = summed.setdefault(base, {})
                key = (sample_name, labels)
                bucket[key] = bucket.get(key, 0.0) + value
            else:
                labeled.setdefault(base, []).append(
                    (fe, sample_name, labels, raw)
                )

    out: list[str] = []
    for base in order:
        out.extend(headers.get(base, []))
        if base in summed:
            for (sample_name, labels), value in summed[base].items():
                out.append(f"{sample_name}{labels} {value}")
        for fe, sample_name, labels, raw in labeled.get(base, []):
            fe_label = f'frontend="{fe}"'
            if labels:
                merged = "{" + fe_label + "," + labels[1:]
            else:
                merged = "{" + fe_label + "}"
            out.append(f"{sample_name}{merged} {raw}")
    return "\n".join(out) + ("\n" if out else "")


class LoggingStatLogger:
    """Console stats every `interval` seconds (reference:
    ``v1/metrics/loggers.py:99 LoggingStatLogger``)."""

    def __init__(self, interval: float = 10.0) -> None:
        from vllm_tpu.logger import init_logger

        self._logger = init_logger("vllm_tpu.metrics")
        self.interval = interval
        self._last = time.monotonic()
        self._gen_tokens = 0
        self._prompt_tokens = 0

    def record(self, scheduler_stats: SchedulerStats | None,
               iteration_stats: Any | None = None) -> None:
        if iteration_stats is not None:
            self._gen_tokens += iteration_stats.num_generation_tokens
            self._prompt_tokens += iteration_stats.num_prompt_tokens
        nowt = time.monotonic()
        if nowt - self._last < self.interval or scheduler_stats is None:
            return
        dt = nowt - self._last
        self._logger.info(
            "tput: %.1f gen tok/s, %.1f prefill tok/s | running %d waiting %d"
            " | kv %.1f%% | prefix hit %.1f%%",
            self._gen_tokens / dt,
            self._prompt_tokens / dt,
            scheduler_stats.num_running_reqs,
            scheduler_stats.num_waiting_reqs,
            100 * scheduler_stats.kv_cache_usage,
            100 * scheduler_stats.prefix_cache_hits
            / max(1, scheduler_stats.prefix_cache_queries),
        )
        self._gen_tokens = self._prompt_tokens = 0
        self._last = nowt
