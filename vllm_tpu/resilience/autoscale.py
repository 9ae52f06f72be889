"""Elastic capacity: traffic-driven autoscale decisions for the DP pool.

Every mechanism for changing pool shape already exists as a *reaction*
to failure — engine respawn under a restart budget, mesh shrink/grow,
streaming weight push, degraded-mode routing. This module composes them
into *intentional*, traffic-driven scaling:

- :class:`AutoscaleController` — a pure state machine (injectable
  clock, no engine dependencies; same design discipline as
  ``AdaptiveSpecController``) that turns live signals into scale
  decisions. Signals in: per-engine queue depth, sliding-window SLO
  attainment (the PR-17 scoreboard), and kv-fabric tier occupancy.
  Decisions out: ``"up"`` / ``"down"`` / ``None``, guarded by
  hysteresis (separate up/down queue watermarks), a hold period (a
  signal must *persist* before it acts — one burst never scales), a
  cooldown after every scale event (the pool must re-equilibrate before
  the next decision), and hard min/max pool bounds.

- Role rebalance rides the same machinery: :meth:`decide_rebalance`
  watches per-phase queue pressure and proposes converting an engine of
  the over-provisioned role when the imbalance is sustained.

The controller never touches processes. The DPLB client owns execution
(spawn + peer weight re-seed on scale-up, graceful drain on
scale-down); it reports outcomes back via :meth:`note_scale_finished`
so cooldown and the ``vllm:scale_events_total`` counters reflect what
actually happened, not what was intended.
"""

from __future__ import annotations

import time
from collections.abc import Callable
from dataclasses import dataclass

__all__ = ["AutoscaleController"]


@dataclass
class _Ema:
    """Irregular-interval EMA (same blend as spec_decode.adaptive): an
    observation's weight halves every ``half_life_s`` seconds of wall
    time. ``value is None`` until the first observation."""

    half_life_s: float
    value: float | None = None
    t_last: float = 0.0

    def update(self, x: float, now: float) -> float:
        if self.value is None:
            self.value = float(x)
        else:
            dt = max(0.0, now - self.t_last)
            w = 0.5 ** (dt / self.half_life_s) if self.half_life_s > 0 else 0.0
            alpha = max(1.0 - w, 0.1)
            self.value = (1.0 - alpha) * self.value + alpha * float(x)
        self.t_last = now
        return self.value


class AutoscaleController:
    """Signals in, scale decisions out.

    Pure host-side state machine: the frontend calls :meth:`observe`
    at a sampling cadence it owns, then :meth:`decide` with the actual
    pool size; a non-``None`` decision obliges the caller to execute it
    and report the outcome through :meth:`note_scale_started` /
    :meth:`note_scale_finished`. Everything is deterministic given the
    injected ``clock`` (tests drive a fake clock; no engine required).

    Decision logic per tick:

    - *pressure* — smoothed per-engine queue depth at or above
      ``up_queue_depth``, OR SLO attainment below ``slo_floor``, OR
      kv-fabric tier occupancy at or above ``occupancy_high``. Held for
      ``hold_s`` → scale up (bounded by ``max_engines``).
    - *slack* — smoothed queue depth at or below ``down_queue_depth``
      AND no SLO/occupancy pressure. Held for ``hold_s`` → scale down
      (bounded by ``min_engines``).
    - between the queue watermarks neither timer runs: the band is the
      hysteresis dead zone, so the pool never flaps on noise.
    - while a scale event is in flight, and for ``cooldown_s`` after
      one finishes, :meth:`decide` returns ``None`` unconditionally.
    """

    def __init__(
        self,
        *,
        min_engines: int = 1,
        max_engines: int = 8,
        up_queue_depth: float = 4.0,
        down_queue_depth: float = 0.5,
        slo_floor: float = 0.0,
        occupancy_high: float = 0.95,
        hold_s: float = 5.0,
        cooldown_s: float = 30.0,
        rebalance_ratio: float = 4.0,
        ema_half_life_s: float = 10.0,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        if min_engines < 1:
            raise ValueError(
                f"autoscale_min_engines must be >= 1, got {min_engines}")
        if max_engines < min_engines:
            raise ValueError(
                f"autoscale_max_engines ({max_engines}) must be >= "
                f"autoscale_min_engines ({min_engines})")
        if not (0.0 <= down_queue_depth < up_queue_depth):
            raise ValueError(
                f"queue watermarks must satisfy 0 <= down < up, got "
                f"down={down_queue_depth} up={up_queue_depth}")
        if not (0.0 <= slo_floor <= 1.0):
            raise ValueError(
                f"autoscale_slo_floor must be in [0, 1], got {slo_floor}")
        if not (0.0 < occupancy_high <= 1.0):
            raise ValueError(
                f"autoscale_occupancy_high must be in (0, 1], got "
                f"{occupancy_high}")
        if hold_s < 0 or cooldown_s < 0:
            raise ValueError("hold_s and cooldown_s must be >= 0")
        if rebalance_ratio <= 1.0:
            raise ValueError(
                f"rebalance_ratio must be > 1, got {rebalance_ratio}")
        self.min_engines = min_engines
        self.max_engines = max_engines
        self.up_queue_depth = up_queue_depth
        self.down_queue_depth = down_queue_depth
        self.slo_floor = slo_floor
        self.occupancy_high = occupancy_high
        self.hold_s = hold_s
        self.cooldown_s = cooldown_s
        self.rebalance_ratio = rebalance_ratio
        self._clock = clock

        self._queue = _Ema(ema_half_life_s)
        self._slo = _Ema(ema_half_life_s)
        self._occ = _Ema(ema_half_life_s)
        # Hold timers: the wall-clock instant the current pressure/slack
        # condition became continuously true (None = not currently true).
        self._pressure_since: float | None = None
        self._slack_since: float | None = None
        self._rebalance_since: float | None = None
        self._rebalance_dir: str | None = None
        # Event latch + cooldown anchor.
        self._busy: str | None = None  # "up" | "down" | "rebalance"
        self._cooldown_until = 0.0
        # Desired pool size (exported as vllm:pool_size_desired); the
        # caller owns actual. None until the first decide().
        self.desired: int | None = None

        # Outcome accounting (pull-drained by the metrics registry).
        self.scale_events_total: dict[tuple[str, str], int] = {}
        self.reseed_total: dict[str, int] = {}
        self.observations = 0

    # -- signals --------------------------------------------------------

    def observe(
        self,
        queue_depth: float,
        slo_attainment: float | None = None,
        occupancy: float | None = None,
    ) -> None:
        """Fold one sample into the smoothed signals.

        ``queue_depth``: waiting+running requests per *up* engine.
        ``slo_attainment``: worst per-class sliding-window attainment in
        [0, 1] (None while the scoreboard has no window yet).
        ``occupancy``: max kv-fabric tier occupancy in [0, 1] (None when
        no fabric is configured).
        """
        now = self._clock()
        self._queue.update(max(0.0, queue_depth), now)
        if slo_attainment is not None:
            self._slo.update(min(1.0, max(0.0, slo_attainment)), now)
        if occupancy is not None:
            self._occ.update(min(1.0, max(0.0, occupancy)), now)
        self.observations += 1

    def _pressure(self) -> str | None:
        """Name of the signal currently demanding more capacity."""
        if (self._queue.value is not None
                and self._queue.value >= self.up_queue_depth):
            return "queue_depth"
        if (self.slo_floor > 0 and self._slo.value is not None
                and self._slo.value < self.slo_floor):
            return "slo_attainment"
        if (self._occ.value is not None
                and self._occ.value >= self.occupancy_high):
            return "kv_occupancy"
        return None

    def _slack(self) -> bool:
        """True when every signal says the pool is over-provisioned."""
        if self._queue.value is None:
            return False
        if self._queue.value > self.down_queue_depth:
            return False
        return self._pressure() is None

    # -- decisions ------------------------------------------------------

    def decide(self, actual: int) -> str | None:
        """Scale decision for a pool currently ``actual`` engines big:
        ``"up"``, ``"down"``, or ``None``. A non-None return arms the
        event latch via :meth:`note_scale_started` on the caller."""
        now = self._clock()
        if self.desired is None:
            self.desired = actual
        if self._busy is not None or now < self._cooldown_until:
            # One event at a time; then let the pool re-equilibrate.
            self._pressure_since = None
            self._slack_since = None
            return None

        pressure = self._pressure()
        slack = self._slack()
        if pressure is not None and actual < self.max_engines:
            self._slack_since = None
            if self._pressure_since is None:
                self._pressure_since = now
            if now - self._pressure_since >= self.hold_s:
                self.desired = actual + 1
                return "up"
            return None
        self._pressure_since = None
        if slack and actual > self.min_engines:
            if self._slack_since is None:
                self._slack_since = now
            if now - self._slack_since >= self.hold_s:
                self.desired = actual - 1
                return "down"
            return None
        self._slack_since = None
        self.desired = actual
        return None

    def decide_rebalance(
        self,
        prefill_depth: float,
        decode_depth: float,
        prefill_engines: int,
        decode_engines: int,
    ) -> str | None:
        """Role-rebalance decision for a disaggregated pool: ``"prefill"``
        (convert a decode/unified engine to prefill) or ``"decode"`` (the
        reverse) when one phase's per-engine queue depth exceeds the
        other's by ``rebalance_ratio``, sustained for ``hold_s``. Shares
        the event latch and cooldown with size decisions — a pool never
        resizes and re-roles at once. The donating side must keep at
        least one engine."""
        now = self._clock()
        if self._busy is not None or now < self._cooldown_until:
            self._rebalance_since = None
            self._rebalance_dir = None
            return None
        want: str | None = None
        if (decode_engines > 1 and prefill_engines > 0
                and prefill_depth >= self.rebalance_ratio
                * max(decode_depth, 0.25)):
            want = "prefill"
        elif (prefill_engines > 1 and decode_engines > 0
                and decode_depth >= self.rebalance_ratio
                * max(prefill_depth, 0.25)):
            want = "decode"
        if want is None or want != self._rebalance_dir:
            self._rebalance_dir = want
            self._rebalance_since = now if want is not None else None
            return None
        if now - self._rebalance_since >= self.hold_s:
            return want
        return None

    # -- event lifecycle ------------------------------------------------

    def note_scale_started(self, direction: str) -> None:
        """Latch an in-flight scale event; decide() holds until
        :meth:`note_scale_finished` releases it."""
        self._busy = direction
        self._pressure_since = None
        self._slack_since = None
        self._rebalance_since = None
        self._rebalance_dir = None

    def note_scale_finished(self, direction: str, outcome: str) -> None:
        """Record a finished event (outcome: "reseed" | "ok" |
        "fallback_checkpoint" | "drained" | "replayed" | "failed" | ...)
        and start the cooldown clock."""
        key = (direction, outcome)
        self.scale_events_total[key] = self.scale_events_total.get(key, 0) + 1
        self._busy = None
        self._cooldown_until = self._clock() + self.cooldown_s

    def note_reseed(self, outcome: str) -> None:
        """Count one weight re-seed attempt (vllm:weight_reseed_total)."""
        self.reseed_total[outcome] = self.reseed_total.get(outcome, 0) + 1

    @property
    def busy(self) -> str | None:
        return self._busy

    # -- introspection --------------------------------------------------

    def snapshot(self) -> dict:
        now = self._clock()
        return {
            "desired": self.desired,
            "busy": self._busy,
            "cooldown_remaining_s": max(0.0, self._cooldown_until - now),
            "queue_depth_ema": self._queue.value,
            "slo_attainment_ema": self._slo.value,
            "kv_occupancy_ema": self._occ.value,
            "pressure": self._pressure(),
            "slack": self._slack(),
            "min_engines": self.min_engines,
            "max_engines": self.max_engines,
            "observations": self.observations,
            "scale_events_total": {
                f"{d}/{o}": n
                for (d, o), n in sorted(self.scale_events_total.items())
            },
            "weight_reseed_total": dict(self.reseed_total),
        }
