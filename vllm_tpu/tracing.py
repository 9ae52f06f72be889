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

Two more things sit on the same sinks (PR 39). ``startup`` is the start of
this process as a timeline of phases (``StartupTimeline``): each phase a
span of category ``startup`` and a number of seconds, the numbers exposed
once as ``vllm:startup_seconds{phase}``. ``compiles`` listens to what JAX
itself reports of tracing, lowering and compiling (``CompileEvents``;
``jax.monitoring``, imported by ``listen`` only): cumulative seconds by
stage, persistent-cache hits and misses, and a ``compile.<stage>`` span an
event. JAX calls it only where something is traced, lowered or compiled,
so a steady step never enters it.
"""

from __future__ import annotations

import atexit
import json
import os
import threading
import time
import uuid
from contextlib import contextmanager

from vllm_tpu.logger import init_logger

logger = init_logger(__name__)

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


def _emit_complete(name: str, category: str, t0_us: int, t1_us: int,
                   attrs: dict) -> None:
    """A complete (``ph: X``) event from ``t0_us`` to ``t1_us``,
    microseconds on ``perf_counter``'s clock."""
    _emit({
        "name": name,
        "cat": category,
        "ph": "X",
        "ts": t0_us,
        "dur": t1_us - t0_us,
        "pid": os.getpid(),
        "tid": threading.get_ident() % 2**31,
        "args": attrs,
    })


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
    ``request``) stay out of it, one a step is what a gap is read by.
    Yields the chrome event's ``args`` (None when both sinks are off):
    what the body adds there is written when the span closes. The
    profiler's annotation is fixed when it opens."""
    annotate = _annotation if category != "request" else None
    chrome = trace_enabled()
    if annotate is None and not chrome:
        yield None
        return
    attrs = {k: v for k, v in attrs.items() if v is not None}
    span = None
    if annotate is not None:
        span = annotate(f"vllm_tpu.{category}.{name}", **attrs)
        span.__enter__()
    t0 = time.perf_counter_ns() // 1000  # chrome trace wants microseconds
    try:
        yield attrs
    finally:
        if span is not None:
            span.__exit__(None, None, None)
        if chrome:
            _emit_complete(name, category, t0,
                           time.perf_counter_ns() // 1000, attrs)


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


# ----------------------------------------------------------------------
# The start's timeline
# ----------------------------------------------------------------------

def process_created() -> float:
    """``perf_counter``'s reading at the moment this process was created:
    its start time from ``/proc/self/stat`` (field 22, clock ticks since
    boot) moved onto ``perf_counter``'s clock by way of the boot-time clock
    read now. To a clock tick (10 ms); now, where ``/proc`` has none."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat", "rb") as f:
            # The command (field 2) may hold spaces: count from its ")".
            after_comm = f.read().rpartition(b")")[2].split()
        ticks = int(after_comm[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return now
    return now - max(age, 0.0)


class StartupTimeline:
    """One process's start, from its creation to ``ready()``, by phase.

    A phase is opened and closed where its work happens (``phase``, or
    ``begin`` and ``end`` where the two lie in different functions). Phases
    nest, and a phase's seconds are its SELF time: its span less the
    phases inside it. So ``engine`` (all of ``EngineCore.__init__``) reads
    what is left beside the worker's phases, ``frontend`` (``main`` to the
    bound socket) what is left beside the engine, and the phases of a
    process add up to ``created`` .. ``ready()`` with nothing in between.
    ``phase(None)`` is time another process accounts for (the wait for a
    spawned engine). A name counts once, the first time: a second engine
    built in this process, and anything after ``ready()``, changes nothing.
    Each phase is also a span of category ``startup`` on the chrome sink,
    under one ``startup`` span that ``ready()`` writes.
    """

    def __init__(self, created: float | None = None) -> None:
        self.created = process_created() if created is None else created
        self.phases: dict[str, float] = {}  # in the order they ended
        self.ready_at: float | None = None
        # The open phases, outermost first: [name, began, seconds of the
        # phases that ended inside it].
        self._open: list[list] = []
        # Where the last outermost phase ended, once ``imported()`` has
        # run: the next outermost one begins there, so that the phases of
        # a process with a main of its own leave nothing between them.
        self._mark: float | None = None

    def _record(self, name: str | None, began: float, ended: float,
                inside: float, detail: str) -> None:
        if self.ready_at is not None or name is None or name in self.phases:
            return
        seconds = self.phases[name] = ended - began - inside
        if trace_enabled():
            _emit_complete(name, "startup", int(began * 1e6),
                           int(ended * 1e6), {"self_s": seconds})
        logger.info("startup: %s %.1f s%s", name, seconds,
                    f" ({detail})" if detail else "")

    def imported(self) -> None:
        """The entry of the process's own main: ``import`` ends here. It
        began when the process was created, so it holds the interpreter's
        start and every module imported on the way."""
        if self._mark is None:
            self._mark = time.perf_counter()
            self._record("import", self.created, self._mark, 0.0, "")

    def begin(self, name: str | None) -> None:
        began = time.perf_counter()
        if not self._open and self._mark is not None:
            began = self._mark
        self._open.append([name, began, 0.0])

    def end(self, detail: str = "") -> None:
        name, began, inside = self._open.pop()
        ended = time.perf_counter()
        if self._open:
            self._open[-1][2] += ended - began
        elif self._mark is not None:
            self._mark = ended
        self._record(name, began, ended, inside, detail)

    @contextmanager
    def phase(self, name: str | None):
        """``with startup.phase("load_model") as note:``; what the body
        appends to ``note`` ends the phase's log line (``7.5 GB``)."""
        note: list[str] = []
        self.begin(name)
        try:
            yield note
        finally:
            self.end(", ".join(note))

    def ready(self) -> dict:
        """This process's part of the start is over: close what is open
        (the front end's own phase), write the ``startup`` span over all of
        it and the one log line, and return ``snapshot()``."""
        while self._open:
            self.end()
        if self.ready_at is None:
            self.ready_at = self._mark or time.perf_counter()
            if trace_enabled():
                _emit_complete("startup", "startup", int(self.created * 1e6),
                               int(self.ready_at * 1e6), dict(self.phases))
            logger.info("startup: %s, total %.1f s", ", ".join(
                f"{name} {s:.1f} s" for name, s in self.phases.items()),
                self.ready_at - self.created)
        return self.snapshot()

    def snapshot(self) -> dict:
        """What crosses to the metrics registry, once: whose phases these
        are, when that process was created (``perf_counter``'s clock, one
        for every process of a host) and the seconds of each phase."""
        return {"pid": os.getpid(), "created": self.created,
                "phases": dict(self.phases)}


# This process's start. Module state because the thing itself is one a
# process: its creation time and its one start.
startup = StartupTimeline()


# ----------------------------------------------------------------------
# What compiling costs, from JAX's own events
# ----------------------------------------------------------------------

class CompileEvents:
    """Listener to ``jax.monitoring``: seconds of tracing, lowering and
    backend compilation (XLA's compile or the load from the persistent
    cache, whichever happened), and persistent-cache hits and misses, for
    the whole process since ``listen()``.

    JAX reports a trace when it ends, with its duration, and reports the
    traces of the jitted functions called inside it first, each in full:
    summed as they come, a nested function would count twice. ``_traces``
    holds (start, seconds) of the traces counted so far that no later one
    has covered; a trace that ends takes back those that began inside it.
    Lowering and backend compilation happen once a program and do not nest.
    """

    STAGE_OF = {
        "/jax/core/compile/jaxpr_trace_duration": "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        "/jax/core/compile/backend_compile_duration": "backend",
    }
    RESULT_OF = {
        "/jax/compilation_cache/cache_hits": "hit",
        "/jax/compilation_cache/cache_misses": "miss",
    }
    MAX_OPEN_TRACES = 1024

    def __init__(self) -> None:
        self.seconds = {"trace": 0.0, "lower": 0.0, "backend": 0.0}
        self.cache = {"hit": 0, "miss": 0}
        self.heard = 0  # events counted: a reader's cheap "anything new?"
        self._traces: list[tuple[float, float]] = []
        self._listening = False

    def listen(self) -> None:
        """Register with JAX, once a process and for its life."""
        if self._listening:
            return
        self._listening = True
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self.on_duration)
        monitoring.register_event_listener(self.on_event)

    def on_duration(self, event: str, duration: float, **kwargs) -> None:
        stage = self.STAGE_OF.get(event)
        if stage is None:
            return
        now = time.perf_counter()
        began = now - duration
        with _lock:
            if stage == "trace":
                traces = self._traces
                while traces and traces[-1][0] >= began:
                    self.seconds["trace"] -= traces.pop()[1]
                traces.append((began, duration))
                del traces[:-self.MAX_OPEN_TRACES]
            self.seconds[stage] += duration
            self.heard += 1
        if trace_enabled():
            _emit_complete(f"compile.{stage}", "compile", int(began * 1e6),
                           int(now * 1e6),
                           {"fun_name": str(kwargs.get("fun_name", ""))})

    def on_event(self, event: str, **kwargs) -> None:
        result = self.RESULT_OF.get(event)
        if result is not None:
            with _lock:
                self.cache[result] += 1
                self.heard += 1

    def reading(self) -> tuple[float, float, float, int, int]:
        """(trace, lower, backend seconds, cache hits, misses) so far."""
        s, c = self.seconds, self.cache
        return s["trace"], s["lower"], s["backend"], c["hit"], c["miss"]


compiles = CompileEvents()
