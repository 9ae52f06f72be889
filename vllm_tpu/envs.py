"""Environment flags for vllm-tpu.

Analog of the reference's ``vllm/envs.py`` (739 lazy env vars) at the scale
this framework needs: lazily evaluated, cached after first read, all flags
prefixed ``VLLM_TPU_``.
"""

from __future__ import annotations

import os
from collections.abc import Callable
from typing import Any

_cache: dict[str, Any] = {}


def _bool(name: str, default: bool) -> Callable[[], bool]:
    def read() -> bool:
        return os.environ.get(name, "1" if default else "0") not in ("0", "false", "False", "")

    return read


def _int(name: str, default: int) -> Callable[[], int]:
    def read() -> int:
        return int(os.environ.get(name, str(default)))

    return read


def _str(name: str, default: str | None) -> Callable[[], str | None]:
    def read() -> str | None:
        return os.environ.get(name, default)

    return read


# Flag registry: name -> lazy reader.
_readers: dict[str, Callable[[], Any]] = {
    # Logging
    "VLLM_TPU_LOGGING_LEVEL": _str("VLLM_TPU_LOGGING_LEVEL", "INFO"),
    "VLLM_TPU_CONFIGURE_LOGGING": _bool("VLLM_TPU_CONFIGURE_LOGGING", True),
    # Engine
    "VLLM_TPU_ENABLE_MULTIPROCESSING": _bool("VLLM_TPU_ENABLE_MULTIPROCESSING", False),
    "VLLM_TPU_ENGINE_ITERATION_TIMEOUT_S": _int("VLLM_TPU_ENGINE_ITERATION_TIMEOUT_S", 60),
    # Fault injection (vllm_tpu/resilience/failpoints). NOTE: the
    # failpoints module reads these from os.environ directly at import so
    # spawned engine/coordinator processes inherit arming through the
    # environment; registered here for discoverability only.
    "VLLM_TPU_FAILPOINTS": _str("VLLM_TPU_FAILPOINTS", None),
    "VLLM_TPU_FAILPOINT_SEED": _int("VLLM_TPU_FAILPOINT_SEED", 0),
    # Compilation / runner
    "VLLM_TPU_DISABLE_PALLAS": _bool("VLLM_TPU_DISABLE_PALLAS", False),
    "VLLM_TPU_PALLAS_INTERPRET": _bool("VLLM_TPU_PALLAS_INTERPRET", False),
    # INT8 weight matmuls via native int8xint8 MXU dot with per-token
    # dynamic activation quantization (w8a8). "auto" = on TPU only (the
    # dequant-into-bf16 path materializes a full-width weight copy there:
    # measured 1.44x SLOWER than bf16, while the native int8 dot reads
    # 1 byte/param and beats bf16); "1" forces it on every backend
    # (tests), "0" restores weight-only dequant everywhere.
    # Reference analog: csrc/quantization/w8a8/ scaled_mm semantics.
    "VLLM_TPU_W8A8": _str("VLLM_TPU_W8A8", "auto"),
    # Escape hatch for the decode-specialized ragged attention kernel
    # (ops/rpa_decode_kernel.py): decode-only batches fall back to the
    # general ragged kernel when set. A/B this before filing kernel bugs.
    "VLLM_TPU_DISABLE_DECODE_KERNEL": _bool(
        "VLLM_TPU_DISABLE_DECODE_KERNEL", False
    ),
    # Decode-kernel block-shape overrides (0 = tuned defaults): sequences
    # per grid program and KV pages per sequence per tile. Sweep with
    # tools/probe_decode_attn.py before changing the defaults.
    "VLLM_TPU_DECODE_SEQS_PER_BLOCK": _int(
        "VLLM_TPU_DECODE_SEQS_PER_BLOCK", 0
    ),
    "VLLM_TPU_DECODE_KV_PAGES_PER_BLOCK": _int(
        "VLLM_TPU_DECODE_KV_PAGES_PER_BLOCK", 0
    ),
    # Escape hatch for the adaptive speculation controller
    # (spec_decode/adaptive.py): draft budgets revert to the static
    # num_speculative_tokens and the occupancy gate never suspends.
    # Accepted text is verification-identical either way; A/B this
    # before filing adaptive-spec bugs.
    "VLLM_TPU_DISABLE_ADAPTIVE_SPEC": _bool(
        "VLLM_TPU_DISABLE_ADAPTIVE_SPEC", False
    ),
    # Escape hatch for disaggregated prefill/decode (vllm_tpu/disagg/):
    # --engine-roles keeps its phase-aware ROUTING bias but no request
    # is handed off between engines (no clamped prefill leg, no KV
    # push). Outputs are token-identical either way under greedy
    # decoding; A/B this before filing disagg bugs.
    "VLLM_TPU_DISABLE_DISAGG": _bool("VLLM_TPU_DISABLE_DISAGG", False),
    # Escape hatch for elastic capacity (vllm_tpu/resilience/autoscale):
    # --autoscale stops DRIVING scale events (no controller is built)
    # while the execution layer stays available for manual
    # scale_up()/scale_down() calls and in-flight drains still finish.
    # Serving behavior is otherwise identical; A/B this before filing
    # autoscale bugs.
    "VLLM_TPU_DISABLE_AUTOSCALE": _bool(
        "VLLM_TPU_DISABLE_AUTOSCALE", False
    ),
    # Escape hatch for rolling upgrades (vllm_tpu/resilience/rolling):
    # POST /admin/upgrade refuses to start a cycle (no controller is
    # built) while the manual client primitives (scale_up/scale_down/
    # probe_engine) and the live-config set_config RPC stay available.
    "VLLM_TPU_DISABLE_ROLLING": _bool(
        "VLLM_TPU_DISABLE_ROLLING", False
    ),
    # Escape hatch for the fused sort-free sampling kernel
    # (ops/sampler_kernel.py): sampling batches fall back to the XLA
    # sort-free reference in sample/sampler.py when set. Both paths are
    # bit-exact; A/B this before filing kernel bugs.
    "VLLM_TPU_DISABLE_SAMPLER_KERNEL": _bool(
        "VLLM_TPU_DISABLE_SAMPLER_KERNEL", False
    ),
    # Sampler-kernel block-shape overrides (0 = tuned defaults): request
    # rows per grid program and logits lanes per streamed DMA tile.
    # Sweep with tools/probe_sampler.py before changing the defaults.
    "VLLM_TPU_SAMPLER_ROW_BLOCK": _int("VLLM_TPU_SAMPLER_ROW_BLOCK", 0),
    "VLLM_TPU_SAMPLER_LOGITS_TILE": _int("VLLM_TPU_SAMPLER_LOGITS_TILE", 0),
    # LRU size bound for the persistent compilation cache directory.
    "VLLM_TPU_COMPILE_CACHE_MAX_GB": _int("VLLM_TPU_COMPILE_CACHE_MAX_GB", 32),
    # Unroll the layer loop instead of lax.scan (scan's xs layout
    # assignment can materialize a run-time copy of the whole weight
    # stack; unrolling trades compile time for that transient).
    "VLLM_TPU_UNROLL_LAYERS": _bool("VLLM_TPU_UNROLL_LAYERS", False),
    # Structured output: max recursion re-entries per rule/$ref when
    # expanding context-free grammars (EBNF) and recursive JSON schemas
    # into the finite device mask table. Deeper nesting becomes
    # unreachable (never silently loosened).
    "VLLM_TPU_GRAMMAR_MAX_DEPTH": _int("VLLM_TPU_GRAMMAR_MAX_DEPTH", 6),
    # Profiling
    "VLLM_TPU_PROFILER_DIR": _str("VLLM_TPU_PROFILER_DIR", None),
    # Count NaNs in the step logits and log an error when any appear
    # (reference: _get_nans_in_logits, gpu_model_runner.py:5193).
    "VLLM_TPU_NAN_CHECK": _bool("VLLM_TPU_NAN_CHECK", False),
    # Numeric integrity guard (env override of --numeric-guard): per-row
    # isfinite reduction on step logits + sampled-token range check; a
    # trip fails only the afflicted requests with finish_reason="error".
    "VLLM_TPU_NUMERIC_GUARD": _bool("VLLM_TPU_NUMERIC_GUARD", False),
    # Opt-out local usage telemetry (reference: VLLM_NO_USAGE_STATS).
    "VLLM_TPU_NO_USAGE_STATS": _bool("VLLM_TPU_NO_USAGE_STATS", False),
    # Disable the C++ host-prep fast path (pure-python fallback).
    "VLLM_TPU_DISABLE_NATIVE_PREP": _bool("VLLM_TPU_DISABLE_NATIVE_PREP", False),
    # KV sizing: measure the compiled max-bucket step's peak memory (XLA
    # memory analysis) instead of assuming a fixed activation-headroom
    # fraction. Costs one AOT compile at startup; 0 restores the fraction.
    "VLLM_TPU_PROFILE_KV_SIZING": _bool("VLLM_TPU_PROFILE_KV_SIZING", True),
    # Escape hatch for the QoS layer (vllm_tpu/resilience/qos.py):
    # per-tenant weighted fair queueing degrades to the plain global
    # prompt-token cap, the brownout ladder never engages, and pressure
    # preemption is off — admission caps, deadlines, and KV-exhaustion
    # preemption all still work. Serving is otherwise identical; A/B
    # this before filing QoS bugs.
    "VLLM_TPU_DISABLE_QOS": _bool("VLLM_TPU_DISABLE_QOS", False),
    # API server
    "VLLM_TPU_API_KEY": _str("VLLM_TPU_API_KEY", None),
    # Testing
    "VLLM_TPU_USE_CPU_BACKEND": _bool("VLLM_TPU_USE_CPU_BACKEND", False),
}


def __getattr__(name: str) -> Any:
    if name in _cache:
        return _cache[name]
    if name in _readers:
        value = _readers[name]()
        _cache[name] = value
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def refresh() -> None:
    """Drop the cache (tests that mutate os.environ call this)."""
    _cache.clear()
