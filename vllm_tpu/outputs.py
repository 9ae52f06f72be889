"""Public output types returned by the engine.

Reference analog: ``vllm/outputs.py`` (RequestOutput / CompletionOutput).
"""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class Logprob:
    logprob: float
    rank: int | None = None
    decoded_token: str | None = None


# For each generated position: dict token_id -> Logprob.
LogprobsList = list[dict[int, Logprob]]


@dataclass
class CompletionOutput:
    index: int
    text: str
    token_ids: list[int]
    cumulative_logprob: float | None = None
    logprobs: LogprobsList | None = None
    finish_reason: str | None = None  # "stop" | "length" | "abort"
    stop_reason: int | str | None = None
    # Generation by diffusion over blocks: for each of token_ids the
    # request's block pass (from 0; a block's store counted, whether it ran
    # alone or rode with the next block's first pass) that committed it.
    # None for an autoregressive model.
    commit_passes: list[int] | None = None

    @property
    def finished(self) -> bool:
        return self.finish_reason is not None


@dataclass
class RequestOutput:
    request_id: str
    prompt: str | None
    prompt_token_ids: list[int]
    outputs: list[CompletionOutput]
    finished: bool
    prompt_logprobs: LogprobsList | None = None
    num_cached_tokens: int = 0
    metrics: "RequestMetrics | None" = None
    # Pooling/embedding result (embed requests).
    pooled: list[float] | None = None


@dataclass
class RequestMetrics:
    """Per-request timing (reference: vllm/v1/metrics/stats.py RequestStateStats)."""

    arrival_time: float = 0.0
    first_scheduled_time: float | None = None
    first_token_time: float | None = None
    finished_time: float | None = None

    @property
    def ttft(self) -> float | None:
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.arrival_time


@dataclass
class BeamSearchSequence:
    """One ranked beam (generated tokens only; reference:
    ``vllm/beam_search.py`` BeamSearchSequence)."""

    tokens: list[int]
    cum_logprob: float
    text: str = ""


@dataclass
class BeamSearchOutput:
    sequences: list[BeamSearchSequence]


@dataclass
class PoolingOutput:
    """Embedding/classify result (reference: vllm/outputs.py PoolingOutput)."""

    data: "object"  # numpy array


@dataclass
class PoolingRequestOutput:
    request_id: str
    prompt_token_ids: list[int]
    outputs: PoolingOutput
    finished: bool = True
