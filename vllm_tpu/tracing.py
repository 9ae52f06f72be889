"""Request/engine tracing: chrome-trace (Perfetto) spans.

Reference analog: ``vllm/tracing/`` (OTLP span exporter ``otel.py:19``,
``@instrument`` on init/hot paths) — this environment ships the
opentelemetry API but no SDK/exporter, so the collector here is
dependency-free: spans land in chrome-trace-format JSON
(``chrome://tracing`` / https://ui.perfetto.dev) under
``VLLM_TPU_TRACE_DIR``, one file per process, flushed incrementally. The
OTLP exporter is the transport seam: `trace_span` is the single
instrumentation point to rebind.

Spans cover the serving lifecycle the reference traces per request
(arrival -> queue -> prefill -> decode -> detokenize -> finish) plus the
engine step phases (schedule / dispatch / finalize). Request lifecycle
phases are *async* spans (``ph: b/e``) keyed by a trace id the frontend
assigns at admission and carries across the ZMQ process split, so
``tools/merge_traces.py`` can fuse the per-process files into one
timeline with a flow per request.

Timestamps are ``time.perf_counter_ns`` (CLOCK_MONOTONIC on Linux), the
same epoch in every process on a host — cross-process spans line up in
the merged view without clock translation.

Second sink: while a JAX profile runs in this process (between
``start_profiler`` and ``stop_profiler``, the one pair that ever touches
``jax.profiler``), every ``trace_span`` outside the per-request category
also opens a ``jax.profiler.TraceAnnotation`` named
``vllm_tpu.<category>.<name>``, so the engine's step phases sit on the
profiler's clock beside the device's operations. JAX is imported by
``start_profiler`` only: a process that never profiles (the frontend of
the multiprocess client) never imports it here.
"""

from __future__ import annotations

import atexit
import json
import os
import threading
import time
import uuid
from contextlib import contextmanager

_lock = threading.Lock()
_file = None
_enabled: bool | None = None
_wrote_any = False

# The profiler sink. ``_annotation`` is ``jax.profiler.TraceAnnotation``
# while a profile runs in this process and None otherwise: the one flag
# ``trace_span`` checks. ``_profile`` describes the running profile.
_annotation = None
_profile: dict | None = None
DEFAULT_PROFILE_DIR = "/tmp/vllm-tpu-trace"


def _trace_file():
    global _file, _enabled, _wrote_any
    if _enabled is None:
        trace_dir = os.environ.get("VLLM_TPU_TRACE_DIR")
        _enabled = bool(trace_dir)
        if _enabled:
            os.makedirs(trace_dir, exist_ok=True)
            path = os.path.join(trace_dir, f"trace-{os.getpid()}.json")
            _file = open(path, "wb")
            _wrote_any = False
            _file.write(b"[\n")
            # Terminate the JSON array on interpreter exit so the file on
            # disk is valid JSON, not a dangling ``[...,`` (crashed
            # processes still leave the dangling form; readers strip the
            # trailing comma as a fallback).
            atexit.register(close_trace)
    return _file


def trace_enabled() -> bool:
    _trace_file()
    return bool(_enabled)


def close_trace() -> None:
    """Terminate the JSON event array and close this process's trace file.

    Idempotent; registered via atexit at first emission, callable early
    (e.g. by tests or an orderly shutdown path). Further emissions after
    close are dropped.
    """
    global _file, _enabled, _wrote_any
    with _lock:
        f, _file = _file, None
        if f is None:
            return
        _enabled = False
        if _wrote_any:
            # Events are written as ``{...},\n``: back over the trailing
            # separator so the terminator yields strict JSON.
            f.seek(-2, os.SEEK_END)
            f.truncate()
            f.write(b"\n]\n")
        else:
            f.write(b"]\n")
        f.close()
        _wrote_any = False


def new_trace_id() -> str:
    """Frontend-assigned per-request correlation id, carried across the
    core-client wire so every process's spans for one request share it."""
    return uuid.uuid4().hex[:16]


def _emit(event: dict) -> None:
    f = _trace_file()
    if f is None:
        return
    with _lock:
        if _file is None:  # closed concurrently
            return
        global _wrote_any
        _wrote_any = True
        f.write(json.dumps(event).encode() + b",\n")
        f.flush()


def _base(name: str, category: str, ph: str, **attrs) -> dict:
    return {
        "name": name,
        "cat": category,
        "ph": ph,
        "ts": time.perf_counter_ns() // 1000,
        "pid": os.getpid(),
        "tid": threading.get_ident() % 2**31,
        "args": {k: v for k, v in attrs.items() if v is not None},
    }


class ProfilerBusyError(RuntimeError):
    """A profile is already running (or none is, on a stop)."""


def profiler_running() -> dict | None:
    """The running profile (``trace_dir``, ``thread``, ``started``) or None."""
    return _profile


def start_profiler(trace_dir: str | None = None,
                   python_tracer_level: int = 0) -> dict:
    """Start the JAX profiler and turn the program's own spans on.

    With ``stop_profiler`` the ONLY caller of ``jax.profiler`` in the
    serving path: ``Worker.start_profile`` is the RPC that reaches it, and
    ``EngineCore.start_profile`` (HTTP, the multiprocess utility call)
    is the only caller of that. It runs on the thread that steps the
    engine, between two steps. The python tracer is off unless asked for:
    its frames swamp the host plane and make the stop slow.
    """
    global _annotation, _profile
    if _profile is not None:
        raise ProfilerBusyError(
            f"a profile is already running -> {_profile['trace_dir']}")
    import jax

    from vllm_tpu import envs

    trace_dir = trace_dir or envs.VLLM_TPU_PROFILER_DIR or DEFAULT_PROFILE_DIR
    options = jax.profiler.ProfileOptions()  # host tracer on (its default)
    options.python_tracer_level = int(python_tracer_level)
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    _profile = {"trace_dir": trace_dir, "started": time.monotonic(),
                "thread": threading.current_thread().name}
    _annotation = jax.profiler.TraceAnnotation
    return {"trace_dir": trace_dir, "thread": _profile["thread"]}


def stop_profiler() -> dict:
    """Stop the profile ``start_profiler`` began and turn the spans off.
    Returns its description with ``stop_s``, the seconds the stop took
    (the profiler writes the trace inside it), and ``traced_s``."""
    global _annotation, _profile
    if _profile is None:
        raise ProfilerBusyError("no profile is running")
    import jax

    info, _profile, _annotation = _profile, None, None
    t0 = time.monotonic()
    jax.profiler.stop_trace()
    now = time.monotonic()
    return {"trace_dir": info["trace_dir"], "thread": info["thread"],
            "stop_thread": threading.current_thread().name,
            "traced_s": t0 - info["started"], "stop_s": now - t0}


@contextmanager
def trace_span(name: str, category: str = "engine", **attrs):
    """Complete-event span; no-op (near-zero cost) when both sinks are
    off. Chrome sink: ``name`` as given. Profiler sink (while a profile
    runs): ``vllm_tpu.<category>.<name>``; per-request spans (category
    ``request``) stay out of it, one a step is what a gap is read by."""
    annotate = _annotation if category != "request" else None
    chrome = trace_enabled()
    if annotate is None and not chrome:
        yield
        return
    attrs = {k: v for k, v in attrs.items() if v is not None}
    span = None
    if annotate is not None:
        span = annotate(f"vllm_tpu.{category}.{name}", **attrs)
        span.__enter__()
    t0 = time.perf_counter_ns() // 1000  # chrome trace wants microseconds
    try:
        yield
    finally:
        if span is not None:
            span.__exit__(None, None, None)
        if chrome:
            t1 = time.perf_counter_ns() // 1000
            _emit({
                "name": name,
                "cat": category,
                "ph": "X",
                "ts": t0,
                "dur": t1 - t0,
                "pid": os.getpid(),
                "tid": threading.get_ident() % 2**31,
                "args": attrs,
            })


def trace_instant(name: str, category: str = "request", **attrs) -> None:
    """Point event (request arrival, finish, preemption...)."""
    if not trace_enabled():
        return
    ev = _base(name, category, "i", **attrs)
    ev["s"] = "p"
    _emit(ev)


def trace_async_begin(name: str, trace_id: str | None,
                      category: str = "request", **attrs) -> None:
    """Open an async (``ph: b``) span keyed by the request's trace id.

    Async spans may begin and end in different threads — or, with the
    trace id carried over the core-client wire, different *processes* —
    which is exactly the request lifecycle shape (queue/prefill/decode
    progress in the engine core while the frontend holds the request
    span open end-to-end).
    """
    if trace_id is None or not trace_enabled():
        return
    ev = _base(name, category, "b", trace_id=trace_id, **attrs)
    ev["id"] = trace_id
    _emit(ev)


def trace_async_end(name: str, trace_id: str | None,
                    category: str = "request", **attrs) -> None:
    """Close the matching async span (same name/category/trace id)."""
    if trace_id is None or not trace_enabled():
        return
    ev = _base(name, category, "e", trace_id=trace_id, **attrs)
    ev["id"] = trace_id
    _emit(ev)
