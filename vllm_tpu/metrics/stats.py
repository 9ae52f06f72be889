"""Frontend iteration stats (reference: ``vllm/v1/metrics/stats.py``
IterationStats — assembled client-side from engine-core outputs)."""

from __future__ import annotations

from dataclasses import dataclass, field

# Label applied to requests that carry no explicit SLO class: every
# request lands in exactly one class, so per-class histograms partition
# the traffic instead of sampling it.
DEFAULT_SLO_CLASS = "default"


@dataclass
class IterationStats:
    num_generation_tokens: int = 0
    num_prompt_tokens: int = 0
    ttfts: list[float] = field(default_factory=list)
    inter_token_latencies: list[float] = field(default_factory=list)
    e2e_latencies: list[float] = field(default_factory=list)
    # Class-labeled twins of ttfts / inter_token_latencies:
    # (slo_class, seconds) pairs feeding the per-class
    # vllm:request_ttft_seconds / vllm:request_itl_seconds histograms.
    ttfts_by_class: list[tuple[str, float]] = field(default_factory=list)
    itls_by_class: list[tuple[str, float]] = field(default_factory=list)
    # Finish reasons of requests completed this iteration ("stop",
    # "length", "abort", ...) — exported as the labeled
    # vllm:request_success_total counter family.
    finished_reasons: list[str] = field(default_factory=list)
    # Seconds OutputProcessor.process_outputs took for this batch of
    # outputs (detokenise and deliver): the "output" phase of
    # vllm:engine_step_duration_seconds.
    output_s: float | None = None


@dataclass
class RequestTimings:
    """Per-request lifecycle timing breakdown, assembled by the output
    processor as engine-core outputs stream through it. Feeds the
    ``/debug/requests`` recently-finished ring (and mirrors the span
    structure the tracer emits: queue -> prefill -> decode, plus the
    frontend-side detokenize cost).

    All timestamps are ``time.monotonic`` seconds; durations are seconds.
    """

    request_id: str
    trace_id: str | None = None
    # Tenant/SLO labels (from SamplingParams; None when the request
    # carried none). Surfaced on /debug/requests and in trace records.
    slo_class: str | None = None
    tenant_id: str | None = None
    arrival_time: float = 0.0
    finished_time: float | None = None
    finish_reason: str | None = None
    num_prompt_tokens: int = 0
    num_output_tokens: int = 0
    num_cached_tokens: int = 0
    peak_kv_blocks: int = 0
    # Phase breakdown.
    queue_s: float | None = None  # waiting -> first schedule (engine-side)
    prefill_s: float | None = None  # first schedule -> first token
    decode_s: float | None = None  # first token -> last token
    detokenize_s: float = 0.0  # cumulative frontend detokenizer time
    e2e_s: float | None = None  # arrival -> finish

    def as_dict(self) -> dict:
        return {
            "request_id": self.request_id,
            "trace_id": self.trace_id,
            "slo_class": self.slo_class,
            "tenant_id": self.tenant_id,
            "finish_reason": self.finish_reason,
            "num_prompt_tokens": self.num_prompt_tokens,
            "num_output_tokens": self.num_output_tokens,
            "num_cached_tokens": self.num_cached_tokens,
            "peak_kv_blocks": self.peak_kv_blocks,
            "phases": {
                "queue_s": self.queue_s,
                "prefill_s": self.prefill_s,
                "decode_s": self.decode_s,
                "detokenize_s": self.detokenize_s,
                "e2e_s": self.e2e_s,
            },
        }
