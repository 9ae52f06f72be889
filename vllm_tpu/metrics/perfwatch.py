"""Perfwatch: live device-time attribution for the serving engine.

Three cooperating pieces (ISSUE 10 / ROADMAP item 2):

1. **Periodic profiling windows** — opt-in ``--perfwatch-interval-s``
   (plus on-demand ``POST /debug/perf/capture``): the engine core takes
   a short ``jax.profiler`` capture around N steps of live traffic,
   folds it through the streaming ``OpSplitStream`` classifier, and
   publishes ``vllm:device_time_ms_per_step{phase=...}`` gauges plus
   live ``vllm:mfu_est`` / ``vllm:hbm_bw_util_est`` computed from
   scheduler-known token counts and the model's roofline
   (`vllm_tpu/metrics/roofline.py` — the same math ``bench.py`` scores
   with).
2. **Quiet-window kernel A/B** — when the engine has been idle past a
   settle threshold (or an admin forces it), replay a retained
   representative batch shape against kernel-dispatch variants (sampler
   kernel on/off, decode-attention kernel on/off) under profiling and
   report per-variant ``device_ms`` deltas.
3. **Guard rails** — strictly zero-overhead when disabled (the engine
   core holds ``perfwatch = None`` and every hook is a single None
   check), and any real request arriving mid-quiet-window aborts the
   replay (``vllm:perfwatch_captures_aborted_total``).

This module is deliberately side-effect free: ``QuietWindow`` and
``PerfWatch`` are pure state machines over an injectable clock, so the
scheduling logic is unit-testable on CPU without an engine. The engine
core (`vllm_tpu/engine/engine_core.py`) owns the profiler/trace/RPC
side effects and consults these machines for *when*.
"""

from __future__ import annotations

import time
from typing import Any, Callable

from vllm_tpu.metrics.roofline import RooflineModel

# Synthetic A/B replay requests carry this id prefix; the abort guard
# treats anything else in the scheduler as real traffic.
AB_REQUEST_PREFIX = "perfwatch-ab-"


class QuietWindow:
    """Idle-settle detector: BUSY -> SETTLING -> QUIET.

    The engine is "quiet" only after ``settle_s`` of *continuous* idle —
    a momentary gap between a stream's decode steps must not trigger an
    A/B replay that would then immediately abort. Any busy observation
    resets the machine.
    """

    BUSY = "busy"
    SETTLING = "settling"
    QUIET = "quiet"

    def __init__(self, settle_s: float,
                 clock: Callable[[], float] = time.monotonic) -> None:
        self.settle_s = settle_s
        self._clock = clock
        self._idle_since: float | None = None

    @property
    def state(self) -> str:
        if self._idle_since is None:
            return self.BUSY
        if self._clock() - self._idle_since >= self.settle_s:
            return self.QUIET
        return self.SETTLING

    def update(self, busy: bool) -> str:
        if busy:
            self._idle_since = None
        elif self._idle_since is None:
            self._idle_since = self._clock()
        return self.state


class PerfWatch:
    """Capture/A-B scheduling state for the engine core.

    The engine calls :meth:`poll` every loop iteration (busy or idle);
    the return value — ``"capture"``, ``"ab"``, or ``None`` — is the
    only coupling. Captures run over live traffic, so they fire only
    when busy; A/B replays synthesize traffic, so they fire only when
    quiet (or admin-forced past the settle timer — never past live
    requests).
    """

    def __init__(self, interval_s: float = 0.0, capture_steps: int = 8,
                 ab_steps: int = 8, quiet_settle_s: float = 2.0,
                 clock: Callable[[], float] = time.monotonic) -> None:
        self.interval_s = interval_s
        self.capture_steps = max(1, int(capture_steps))
        self.ab_steps = max(1, int(ab_steps))
        self.quiet = QuietWindow(quiet_settle_s, clock)
        self._clock = clock
        self._next_due = (
            clock() + interval_s if interval_s > 0 else None
        )
        # Counters (exported as vllm:perfwatch_*_total).
        self.captures_total = 0
        self.captures_aborted = 0
        self.ab_runs_total = 0
        # Latest results (served on GET /debug/perf and folded into
        # SchedulerStats for /metrics).
        self.last_capture: dict | None = None
        self.last_ab: dict | None = None
        # Retained representative batch shape (runner-observed; feeds
        # the A/B replay request synthesis).
        self.last_batch_shape: dict | None = None
        # One-shot admin arm ({"mode","steps","force"}); a plain
        # attribute swap — GIL-atomic, written from the HTTP/utility
        # thread, consumed from the engine loop thread.
        self._armed: dict | None = None
        # In-flight capture session bookkeeping.
        self.active: dict | None = None

    # -- arming (HTTP / utility thread) --------------------------------

    def arm(self, mode: str = "auto", steps: int | None = None,
            force: bool = False) -> dict:
        """Queue a one-shot capture ("capture"), A/B replay ("ab"), or
        whichever fits the engine's state ("auto"). Returns an ack; the
        engine loop executes on its next poll."""
        if mode not in ("auto", "capture", "ab"):
            return {"error": f"unknown mode {mode!r}"}
        self._armed = {
            "mode": mode,
            "steps": int(steps) if steps else None,
            "force": bool(force),
        }
        return {"armed": mode, "force": bool(force)}

    # -- scheduling (engine loop thread) -------------------------------

    def poll(self, busy: bool) -> str | None:
        """Advance the quiet-window machine; decide whether the engine
        should start a capture or an A/B replay *now*."""
        state = self.quiet.update(busy)
        if self.active is not None:
            return None  # a capture window is already open
        armed = self._armed
        if armed is not None:
            mode = armed["mode"]
            if mode == "auto":
                mode = "capture" if busy else "ab"
            if mode == "capture" and busy:
                self._armed = None
                return "capture"
            if mode == "ab" and not busy and (
                    armed["force"] or state == QuietWindow.QUIET):
                self._armed = None
                return "ab"
            # Armed but the engine is in the wrong state (capture wants
            # traffic, ab wants quiet): stay armed, fire when it flips.
            return None
        if self._next_due is not None and self._clock() >= self._next_due:
            if busy:
                self._next_due = self._clock() + self.interval_s
                return "capture"
            if state == QuietWindow.QUIET:
                self._next_due = self._clock() + self.interval_s
                return "ab"
            # Due but mid-settle: hold the tick until quiet or busy.
        return None

    @property
    def armed(self) -> bool:
        return self._armed is not None

    # -- capture session lifecycle -------------------------------------

    def begin_capture(self, trace_dir: str, steps: int | None,
                      counters: dict | None) -> None:
        self.active = {
            "trace_dir": trace_dir,
            "target": max(1, steps or self.capture_steps),
            "done": 0,
            "t0": self._clock(),
            "counters0": dict(counters or {}),
        }

    def note_step(self) -> bool:
        """Count one finalized engine step inside the open window;
        True when the window has seen its target."""
        if self.active is None:
            return False
        self.active["done"] += 1
        return self.active["done"] >= self.active["target"]

    def finish_capture(self, split: dict | None, counters: dict | None,
                       ctx_tokens: int,
                       roofline: RooflineModel | None) -> dict:
        """Close the window: per-step attribution + live roofline
        estimates from the window's counter deltas."""
        assert self.active is not None
        sess, self.active = self.active, None
        dt = max(self._clock() - sess["t0"], 1e-9)
        c0, c1 = sess["counters0"], dict(counters or {})
        # launch_sampled_tokens counts REALIZED emissions (finalize-side
        # accumulation) — exact under dynamic multi-step decode, where a
        # launch's per-row token run varies with on-device stop exits; a
        # fixed rows*K estimate here would overstate tok_per_s.
        tokens = max(0, c1.get("launch_sampled_tokens", 0)
                     - c0.get("launch_sampled_tokens", 0))
        launches = max(0, c1.get("step_launches", 0)
                       - c0.get("step_launches", 0))
        tok_per_s = tokens / dt
        steps_per_s = launches / dt
        snapshot: dict[str, Any] = {
            "kind": "capture",
            "steps": sess["done"],
            "window_s": round(dt, 3),
            "tok_per_s": round(tok_per_s, 1),
            "device_ms_per_step": split,  # None on CPU backends
            "mfu_est": None,
            "hbm_bw_util_est": None,
        }
        # A device without published peaks (the CPU in tests) gets no
        # utilization estimate, not a share of some other chip's.
        if roofline is not None and roofline.has_peaks:
            snapshot["mfu_est"] = round(roofline.mfu(tok_per_s), 4)
            snapshot["hbm_bw_util_est"] = round(
                roofline.hbm_bw_util(steps_per_s, ctx_tokens), 4)
            snapshot["device_kind"] = roofline.device_kind
        self.captures_total += 1
        self.last_capture = snapshot
        return snapshot

    def abort_capture(self, reason: str) -> None:
        self.active = None
        self.captures_aborted += 1

    def note_ab(self, result: dict) -> dict:
        """Record a finished (or aborted) A/B replay."""
        if result.get("aborted"):
            self.captures_aborted += 1
        else:
            self.ab_runs_total += 1
        self.last_ab = result
        return result

    # -- exposition ----------------------------------------------------

    def status(self) -> dict:
        """Everything GET /debug/perf serves (msgpack/JSON-able)."""
        return {
            "enabled": self.interval_s > 0,
            "interval_s": self.interval_s,
            "capture_steps": self.capture_steps,
            "ab_steps": self.ab_steps,
            "quiet_state": self.quiet.state,
            "armed": self.armed,
            "capturing": self.active is not None,
            "captures_total": self.captures_total,
            "captures_aborted_total": self.captures_aborted,
            "ab_runs_total": self.ab_runs_total,
            "last_capture": self.last_capture,
            "last_ab": self.last_ab,
            "last_batch_shape": self.last_batch_shape,
        }

    def stats_fields(self) -> dict:
        """The SchedulerStats payload (engine core attaches it every
        step; the Prometheus registry turns it into gauges/counters)."""
        cap = self.last_capture or {}
        return {
            "perfwatch_captures": self.captures_total,
            "perfwatch_captures_aborted": self.captures_aborted,
            "perfwatch_device_ms": cap.get("device_ms_per_step"),
            "perfwatch_mfu_est": cap.get("mfu_est"),
            "perfwatch_hbm_bw_util_est": cap.get("hbm_bw_util_est"),
        }


def ab_delta_pct(on_ms: float | None, off_ms: float | None) -> float | None:
    """Percent change "off -> on" (negative = the kernel wins)."""
    if not on_ms or not off_ms:
        return None
    return round((on_ms - off_ms) / off_ms * 100.0, 2)
