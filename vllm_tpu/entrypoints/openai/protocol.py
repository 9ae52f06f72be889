"""OpenAI API request/response schemas.

Reference analog: ``vllm/entrypoints/openai/protocol.py`` (pydantic models).
This build uses plain dataclasses + explicit validation — the image carries
no pydantic/fastapi; the server is aiohttp.
"""

from __future__ import annotations

import time
import uuid
from dataclasses import dataclass, field
from typing import Any

from vllm_tpu.sampling_params import (
    RequestOutputKind,
    SamplingParams,
    StructuredOutputParams,
)


def _token_id_list(d: dict, key: str) -> list[int] | None:
    v = d.get(key)
    if v is None:
        return None
    if not isinstance(v, list):
        raise ValidationError(f"{key} must be a list of token ids")
    try:
        return [int(t) for t in v]
    except (TypeError, ValueError):
        raise ValidationError(f"{key} must contain integers") from None


def _logit_bias(d: dict) -> dict[int, float] | None:
    """OpenAI logit_bias: {"<token id>": bias} with string keys."""
    lb = d.get("logit_bias")
    if lb is None:
        return None
    if not isinstance(lb, dict):
        raise ValidationError("logit_bias must be an object")
    try:
        return {int(k): float(v) for k, v in lb.items()}
    except (TypeError, ValueError) as e:
        raise ValidationError(f"invalid logit_bias: {e}") from None


def _structured_outputs(d: dict) -> StructuredOutputParams | None:
    """OpenAI ``response_format`` plus the reference's ``guided_*``
    extension fields -> StructuredOutputParams. ``structured_max_depth``
    overrides the CFG/JSON-schema recursion bound per request."""
    depth = d.get("structured_max_depth")
    depth = int(depth) if depth is not None else None

    def make(**kw) -> StructuredOutputParams:
        return StructuredOutputParams(max_depth=depth, **kw)

    rf = d.get("response_format")
    if isinstance(rf, dict):
        t = rf.get("type")
        if t == "json_object":
            return make(json_schema="{}")
        if t == "json_schema":
            schema = (rf.get("json_schema") or {}).get("schema")
            if not isinstance(schema, dict):
                raise ValidationError(
                    "response_format.json_schema.schema must be an object"
                )
            return make(json_schema=schema)
        if t not in (None, "text"):
            raise ValidationError(f"unsupported response_format type {t!r}")
    if d.get("guided_regex") is not None:
        return make(regex=str(d["guided_regex"]))
    if d.get("guided_json") is not None:
        return make(json_schema=d["guided_json"])
    if d.get("guided_grammar") is not None:
        return make(grammar=str(d["guided_grammar"]))
    if d.get("guided_choice") is not None:
        choice = d["guided_choice"]
        if not isinstance(choice, list) or not choice:
            raise ValidationError("guided_choice must be a non-empty list")
        return make(choice=[str(c) for c in choice])
    return None


class ValidationError(ValueError):
    pass


def _priority(d: dict) -> int | None:
    """QoS ``priority`` body field (lower = more urgent, 0 = interactive,
    None = unset so the X-Priority header can fill it in)."""
    v = d.get("priority")
    if v is None:
        return None
    if isinstance(v, bool) or not isinstance(v, int) or not 0 <= v <= 100:
        raise ValidationError("'priority' must be an integer in [0, 100]")
    return v


def _get(d: dict, key: str, typ, default=None):
    v = d.get(key, default)
    if v is None:
        return None
    if typ is float and isinstance(v, int):
        v = float(v)
    if not isinstance(v, typ):
        raise ValidationError(f"'{key}' must be {typ}, got {type(v).__name__}")
    return v


@dataclass
class CompletionRequest:
    model: str
    prompt: Any  # str | list[str] | list[int] | list[list[int]]
    max_tokens: int = 16
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0
    min_p: float = 0.0
    n: int = 1
    stream: bool = False
    stop: list[str] = field(default_factory=list)
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    repetition_penalty: float = 1.0
    logprobs: int | None = None
    echo: bool = False
    seed: int | None = None
    ignore_eos: bool = False
    min_tokens: int = 0
    structured_outputs: Any = None
    logit_bias: dict[int, float] | None = None
    bad_words: list[str] = field(default_factory=list)
    allowed_token_ids: list[int] | None = None
    # Lifecycle extension: per-request end-to-end deadline (seconds);
    # overrides the server default. Also settable via the
    # X-Request-Deadline-S header (body wins).
    deadline_s: float | None = None
    # SLO scoreboard labels; also settable via the X-SLO-Class /
    # X-Tenant-Id headers (body wins).
    slo_class: str | None = None
    tenant_id: str | None = None
    # QoS scheduling priority (lower = more urgent, 0 = interactive);
    # also settable via the X-Priority header (body wins).
    priority: int | None = None
    # Generation by diffusion over blocks (refused on other models).
    remasking: str | None = None
    denoising_steps: int | None = None
    confidence_threshold: float | None = None

    @classmethod
    def from_json(cls, d: dict) -> "CompletionRequest":
        if "prompt" not in d:
            raise ValidationError("'prompt' is required")
        stop = d.get("stop") or []
        if isinstance(stop, str):
            stop = [stop]
        return cls(
            model=str(d.get("model", "")),
            prompt=d["prompt"],
            max_tokens=_get(d, "max_tokens", int, 16),
            temperature=_get(d, "temperature", (int, float), 1.0),
            top_p=_get(d, "top_p", (int, float), 1.0),
            top_k=_get(d, "top_k", int, 0),
            min_p=_get(d, "min_p", (int, float), 0.0),
            n=_get(d, "n", int, 1),
            stream=bool(d.get("stream", False)),
            stop=stop,
            presence_penalty=_get(d, "presence_penalty", (int, float), 0.0),
            frequency_penalty=_get(d, "frequency_penalty", (int, float), 0.0),
            repetition_penalty=_get(d, "repetition_penalty", (int, float), 1.0),
            logprobs=_get(d, "logprobs", int),
            echo=bool(d.get("echo", False)),
            seed=_get(d, "seed", int),
            ignore_eos=bool(d.get("ignore_eos", False)),
            min_tokens=_get(d, "min_tokens", int, 0),
            structured_outputs=_structured_outputs(d),
            logit_bias=_logit_bias(d),
            bad_words=list(d.get("bad_words") or []),
            allowed_token_ids=_token_id_list(d, "allowed_token_ids"),
            deadline_s=_get(d, "deadline_s", (int, float)),
            slo_class=_get(d, "slo_class", str),
            tenant_id=_get(d, "tenant_id", str),
            priority=_priority(d),
            remasking=_get(d, "remasking", str),
            denoising_steps=_get(d, "denoising_steps", int),
            confidence_threshold=_get(d, "confidence_threshold", (int, float)),
        )

    def to_sampling_params(self, stream: bool) -> SamplingParams:
        return SamplingParams(
            max_tokens=self.max_tokens,
            temperature=float(self.temperature),
            top_p=float(self.top_p),
            top_k=self.top_k,
            min_p=float(self.min_p),
            stop=list(self.stop),
            presence_penalty=float(self.presence_penalty),
            frequency_penalty=float(self.frequency_penalty),
            repetition_penalty=float(self.repetition_penalty),
            logprobs=self.logprobs,
            seed=self.seed,
            ignore_eos=self.ignore_eos,
            min_tokens=self.min_tokens,
            structured_outputs=self.structured_outputs,
            logit_bias=self.logit_bias,
            bad_words=self.bad_words,
            allowed_token_ids=self.allowed_token_ids,
            deadline_s=(
                float(self.deadline_s)
                if self.deadline_s is not None else None
            ),
            slo_class=self.slo_class,
            tenant_id=self.tenant_id,
            priority=self.priority,
            remasking=self.remasking,
            denoising_steps=self.denoising_steps,
            confidence_threshold=self.confidence_threshold,
            output_kind=(
                RequestOutputKind.DELTA if stream
                else RequestOutputKind.FINAL_ONLY
            ),
        )


@dataclass
class ChatCompletionRequest:
    model: str
    messages: list[dict]
    max_tokens: int = 4096
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0
    min_p: float = 0.0
    n: int = 1
    stream: bool = False
    stop: list[str] = field(default_factory=list)
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    repetition_penalty: float = 1.0
    logprobs: bool = False
    top_logprobs: int | None = None
    seed: int | None = None
    ignore_eos: bool = False
    min_tokens: int = 0
    chat_template: str | None = None
    add_generation_prompt: bool = True
    structured_outputs: Any = None
    tools: list[dict] | None = None
    tool_choice: Any = "auto"
    logit_bias: dict[int, float] | None = None
    bad_words: list[str] = field(default_factory=list)
    allowed_token_ids: list[int] | None = None
    deadline_s: float | None = None
    slo_class: str | None = None
    tenant_id: str | None = None
    priority: int | None = None
    remasking: str | None = None
    denoising_steps: int | None = None
    confidence_threshold: float | None = None

    @classmethod
    def from_json(cls, d: dict) -> "ChatCompletionRequest":
        msgs = d.get("messages")
        if not isinstance(msgs, list) or not msgs:
            raise ValidationError("'messages' must be a non-empty list")
        for m in msgs:
            if not isinstance(m, dict) or "role" not in m:
                raise ValidationError("each message needs a 'role'")
        stop = d.get("stop") or []
        if isinstance(stop, str):
            stop = [stop]
        mt = d.get("max_tokens", d.get("max_completion_tokens", 4096))
        return cls(
            model=str(d.get("model", "")),
            messages=msgs,
            max_tokens=int(mt),
            temperature=_get(d, "temperature", (int, float), 1.0),
            top_p=_get(d, "top_p", (int, float), 1.0),
            top_k=_get(d, "top_k", int, 0),
            min_p=_get(d, "min_p", (int, float), 0.0),
            n=_get(d, "n", int, 1),
            stream=bool(d.get("stream", False)),
            stop=stop,
            presence_penalty=_get(d, "presence_penalty", (int, float), 0.0),
            frequency_penalty=_get(d, "frequency_penalty", (int, float), 0.0),
            repetition_penalty=_get(d, "repetition_penalty", (int, float), 1.0),
            logprobs=bool(d.get("logprobs", False)),
            top_logprobs=_get(d, "top_logprobs", int),
            seed=_get(d, "seed", int),
            ignore_eos=bool(d.get("ignore_eos", False)),
            min_tokens=_get(d, "min_tokens", int, 0),
            chat_template=d.get("chat_template"),
            add_generation_prompt=bool(d.get("add_generation_prompt", True)),
            structured_outputs=_structured_outputs(d),
            tools=d.get("tools"),
            tool_choice=d.get("tool_choice", "auto"),
            logit_bias=_logit_bias(d),
            bad_words=list(d.get("bad_words") or []),
            allowed_token_ids=_token_id_list(d, "allowed_token_ids"),
            deadline_s=_get(d, "deadline_s", (int, float)),
            slo_class=_get(d, "slo_class", str),
            tenant_id=_get(d, "tenant_id", str),
            priority=_priority(d),
            remasking=_get(d, "remasking", str),
            denoising_steps=_get(d, "denoising_steps", int),
            confidence_threshold=_get(d, "confidence_threshold", (int, float)),
        )

    def to_sampling_params(self, stream: bool) -> SamplingParams:
        n_logprobs = None
        if self.logprobs:
            n_logprobs = self.top_logprobs or 1
        return SamplingParams(
            max_tokens=self.max_tokens,
            temperature=float(self.temperature),
            top_p=float(self.top_p),
            top_k=self.top_k,
            min_p=float(self.min_p),
            stop=list(self.stop),
            presence_penalty=float(self.presence_penalty),
            frequency_penalty=float(self.frequency_penalty),
            repetition_penalty=float(self.repetition_penalty),
            logprobs=n_logprobs,
            seed=self.seed,
            ignore_eos=self.ignore_eos,
            min_tokens=self.min_tokens,
            structured_outputs=self.structured_outputs,
            logit_bias=self.logit_bias,
            bad_words=self.bad_words,
            allowed_token_ids=self.allowed_token_ids,
            deadline_s=(
                float(self.deadline_s)
                if self.deadline_s is not None else None
            ),
            slo_class=self.slo_class,
            tenant_id=self.tenant_id,
            priority=self.priority,
            remasking=self.remasking,
            denoising_steps=self.denoising_steps,
            confidence_threshold=self.confidence_threshold,
            output_kind=(
                RequestOutputKind.DELTA if stream
                else RequestOutputKind.FINAL_ONLY
            ),
        )


def random_id(prefix: str) -> str:
    return f"{prefix}-{uuid.uuid4().hex}"


def now() -> int:
    return int(time.time())
