"""Per-request sampling parameters.

Reference analog: ``vllm/sampling_params.py`` (SamplingParams). The sampler
pipeline order they feed (reference ``vllm/v1/sample/sampler.py:22-60``):
allowed-tokens -> bad words -> logit processors -> penalties -> temperature
-> min-p -> top-k/top-p -> sample -> logprobs.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from enum import IntEnum
from typing import Any


class RequestOutputKind(IntEnum):
    CUMULATIVE = 0  # full text so far on every stream event
    DELTA = 1  # only newly generated text
    FINAL_ONLY = 2  # one output at completion


@dataclass
class StructuredOutputParams:
    """Grammar-constrained decoding spec (reference: GuidedDecodingParams)."""

    json_schema: dict[str, Any] | str | None = None
    regex: str | None = None
    grammar: str | None = None
    choice: list[str] | None = None
    # Per-request recursion bound for the depth-bounded CFG/JSON-schema
    # expansion (None -> VLLM_TPU_GRAMMAR_MAX_DEPTH). Deeply-nested
    # grammars that the default rejects can raise it; simple grammars
    # can lower it for faster compiles.
    max_depth: int | None = None

    @property
    def is_set(self) -> bool:
        return any(
            v is not None for v in (self.json_schema, self.regex, self.grammar, self.choice)
        )


@dataclass
class PoolingParams:
    """Embedding/pooling request parameters (reference:
    ``vllm/pooling_params.py``). Causal-LM pooling: hidden state of the
    last token or the masked mean over the prompt."""

    pooling_type: str = "last"  # "last" | "mean" | "cls" | "classify"
    normalize: bool = True

    def __post_init__(self) -> None:
        # "cls" (first-position pooler vector) and "classify"
        # (classification-head logits) require an encoder-only model with
        # a pooled_extra hook (models/bert.py); validated at admission.
        if self.pooling_type not in ("last", "mean", "cls", "classify"):
            raise ValueError(f"unknown pooling_type {self.pooling_type!r}")


@dataclass
class SamplingParams:
    n: int = 1
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0  # 0 or -1 -> disabled
    min_p: float = 0.0
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    repetition_penalty: float = 1.0
    max_tokens: int | None = 16
    min_tokens: int = 0
    stop: list[str] = field(default_factory=list)
    stop_token_ids: list[int] = field(default_factory=list)
    ignore_eos: bool = False
    skip_special_tokens: bool = True
    include_stop_str_in_output: bool = False
    logprobs: int | None = None
    prompt_logprobs: int | None = None
    seed: int | None = None
    detokenize: bool = True
    output_kind: RequestOutputKind = RequestOutputKind.CUMULATIVE
    bad_words: list[str] = field(default_factory=list)
    # Filled by the input processor (tokenized bad_words variants).
    bad_words_token_ids: list[list[int]] | None = None
    allowed_token_ids: list[int] | None = None
    logit_bias: dict[int, float] | None = None
    structured_outputs: StructuredOutputParams | None = None
    # Per-request end-to-end deadline, seconds from admission; None falls
    # back to LifecycleConfig.default_deadline_s. Past the deadline the
    # request is aborted engine-side and finished with
    # finish_reason="timeout" (enforced in AsyncLLM, not the engine core).
    deadline_s: float | None = None
    # SLO/tenant labels (``X-SLO-Class`` / ``X-Tenant-Id`` headers or the
    # matching body fields). Ride the existing EngineCoreRequest wire
    # inside sampling_params, so old peers decode them transparently;
    # consumed frontend-side by the output processor (per-class latency
    # histograms, sliding-window attainment) and the trace recorder.
    slo_class: str | None = None
    tenant_id: str | None = None
    # Scheduling priority (``X-Priority`` header or the matching body
    # field): lower = more urgent, 0 = interactive default. Resolved into
    # Request.priority at admission; under --scheduling-policy priority
    # it orders the waiting queue, and the QoS layer (resilience/qos.py)
    # treats priority > 0 as batch-class for brownout shed/preemption.
    # None = unset (lets header-vs-body precedence detect a body value).
    priority: int | None = None
    # Generation by diffusion over blocks (models/generation.py): the
    # commit rule, the passes a block of masks takes (block_length /
    # denoising_steps positions a pass) and the confidence above which the
    # dynamic rule commits. None = the model's own defaults, filled in by
    # the input processor; refused on an autoregressive model.
    remasking: str | None = None
    denoising_steps: int | None = None
    confidence_threshold: float | None = None
    # Extension hook carried through untouched.
    extra_args: dict[str, Any] | None = None

    def __post_init__(self) -> None:
        if isinstance(self.stop, str):
            self.stop = [self.stop]
        if self.logit_bias is not None and len(self.logit_bias) > 512:
            raise ValueError("logit_bias supports at most 512 entries")
        if self.allowed_token_ids is not None:
            if not self.allowed_token_ids:
                raise ValueError("allowed_token_ids must be non-empty")
            if len(self.allowed_token_ids) > 512:
                raise ValueError(
                    "allowed_token_ids supports at most 512 entries"
                )
            if not all(isinstance(t, int) for t in self.allowed_token_ids):
                raise ValueError("allowed_token_ids must be integers")
        if len(self.bad_words) > 128:
            raise ValueError("bad_words supports at most 128 entries")
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if not 0 < self.top_p <= 1:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.top_k < -1:
            raise ValueError(f"top_k must be >= -1, got {self.top_k}")
        if self.top_k == -1:
            self.top_k = 0
        if not 0 <= self.min_p <= 1:
            raise ValueError(f"min_p must be in [0, 1], got {self.min_p}")
        if self.max_tokens is not None and self.max_tokens < 1:
            raise ValueError(f"max_tokens must be >= 1, got {self.max_tokens}")
        if self.min_tokens < 0:
            raise ValueError(f"min_tokens must be >= 0, got {self.min_tokens}")
        if not -2 <= self.presence_penalty <= 2:
            raise ValueError("presence_penalty must be in [-2, 2]")
        if not -2 <= self.frequency_penalty <= 2:
            raise ValueError("frequency_penalty must be in [-2, 2]")
        if self.repetition_penalty <= 0:
            raise ValueError("repetition_penalty must be > 0")
        if self.deadline_s is not None and self.deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {self.deadline_s}")
        from vllm_tpu.models.generation import validate_diffusion_params

        validate_diffusion_params(
            self.remasking, self.denoising_steps, self.confidence_threshold
        )
        for label_name in ("slo_class", "tenant_id"):
            label = getattr(self, label_name)
            if label is None:
                continue
            if not isinstance(label, str) or not label or len(label) > 64:
                raise ValueError(
                    f"{label_name} must be a non-empty string of <= 64 chars"
                )
        if self.priority is not None:
            if (isinstance(self.priority, bool)
                    or not isinstance(self.priority, int)
                    or not 0 <= self.priority <= 100):
                raise ValueError(
                    f"priority must be an integer in [0, 100], got "
                    f"{self.priority!r}"
                )

    @property
    def sampling_type(self) -> str:
        return "greedy" if self.temperature == 0.0 else "random"

    @property
    def all_stop_token_ids(self) -> set[int]:
        return set(self.stop_token_ids)


@dataclass
class BeamSearchParams:
    """Beam search spec (reference: ``vllm/sampling_params.py``
    BeamSearchParams; driven by ``LLM.beam_search``). Beam scores use the
    model's raw logprobs; temperature != 0 is rejected (scaled-score
    search is not implemented)."""

    beam_width: int = 4
    max_tokens: int = 16
    ignore_eos: bool = False
    temperature: float = 0.0
    length_penalty: float = 1.0


def beam_search_params(beam_width: int) -> SamplingParams:
    """Per-step params used internally by ``LLM.beam_search``: one greedy
    token, top-``2w`` logprobs (the HF expansion width), no incremental
    detokenization."""
    return SamplingParams(
        n=1,
        temperature=0.0,
        logprobs=2 * beam_width,
        max_tokens=1,
        ignore_eos=True,
        output_kind=RequestOutputKind.FINAL_ONLY,
    )
