"""Fetch-vs-recompute cost model for the tiered KV fabric.

Per cached prefix the fabric can either *fetch* the blocks from a cold
tier (host RAM of a peer engine / shared block store) or *recompute*
them by re-running the prefill. The decision compares

    fetch_s     = link_latency + transfer_bytes / link_bandwidth
    recompute_s = prefill_overhead
                  + tokens * flops_per_token / (peak_flops * prefill_eff)

and fetches only when it wins. The compute side reuses
``metrics/roofline.py``: the worker's :class:`RooflineModel`, handed
over once at engine start (``Worker.roofline_info``).

``prefill_overhead`` is the fixed per-prefill cost that is invisible to
a pure-FLOPs model but dominates at short prefix lengths: an extra
scheduling round, host->device input staging, and a dispatch. Skipping a
prefill saves a whole engine step, not just its MACs.

Link bandwidth is a live EWMA over observed fabric transfers, seeded
from (in priority order) an explicit constructor value, the
``VLLM_TPU_KV_FABRIC_LINK_GBPS`` env override (pinned: measurements do
not move it — the forced-cheap / forced-expensive test hook), or a
DCN-class 1 GB/s default.
"""

from __future__ import annotations

import dataclasses
import os
import threading

ENV_LINK_GBPS = "VLLM_TPU_KV_FABRIC_LINK_GBPS"

DEFAULT_LINK_BW = 1.0e9          # bytes/s (DCN-class TPU-host link)
DEFAULT_LINK_LATENCY_S = 2e-3    # per-fetch round-trip floor
DEFAULT_PREFILL_OVERHEAD_S = 8e-3
DEFAULT_PREFILL_EFF = 0.5        # achieved fraction of peak on prefill
# Conservative stand-ins until the worker ships its RooflineModel.
DEFAULT_FLOPS_PER_TOKEN = 2.0 * 7e9
DEFAULT_PEAK_FLOPS = 197e12


@dataclasses.dataclass
class CostDecision:
    fetch: bool
    fetch_s: float
    recompute_s: float
    n_tokens: int
    nbytes: int
    link_bw: float

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


class FetchCostModel:
    """Thread-safe fetch-vs-recompute arbiter with a measured-link EWMA."""

    def __init__(
        self,
        roofline=None,
        link_bw: float | None = None,
        link_latency_s: float = DEFAULT_LINK_LATENCY_S,
        prefill_overhead_s: float = DEFAULT_PREFILL_OVERHEAD_S,
        prefill_eff: float = DEFAULT_PREFILL_EFF,
        ewma_alpha: float = 0.25,
    ) -> None:
        self.roofline = roofline
        self.link_latency_s = link_latency_s
        self.prefill_overhead_s = prefill_overhead_s
        self.prefill_eff = prefill_eff
        self.ewma_alpha = ewma_alpha
        self._lock = threading.Lock()
        env = os.environ.get(ENV_LINK_GBPS)
        if link_bw is not None:
            self._link_bw = float(link_bw)
            self.pinned = True
        elif env:
            self._link_bw = float(env) * 1e9
            self.pinned = True
        else:
            self._link_bw = DEFAULT_LINK_BW
            self.pinned = False
        self.transfers_observed = 0
        self.last_decision: CostDecision | None = None

    # ------------------------------------------------------------------

    def __getstate__(self) -> dict:
        state = self.__dict__.copy()
        del state["_lock"]
        return state

    def __setstate__(self, state: dict) -> None:
        self.__dict__.update(state)
        self._lock = threading.Lock()

    def set_roofline(self, roofline) -> None:
        """Adopt the worker's measured :class:`RooflineModel` (RPC'd once
        at engine init)."""
        self.roofline = roofline

    def observe_transfer(self, nbytes: int, seconds: float) -> None:
        """Fold a completed fabric transfer into the link-bandwidth EWMA.
        Pinned models (explicit/env bandwidth) ignore measurements."""
        if self.pinned or nbytes <= 0 or seconds <= 0:
            return
        bw = nbytes / seconds
        with self._lock:
            self._link_bw = (
                (1.0 - self.ewma_alpha) * self._link_bw
                + self.ewma_alpha * bw
            )
            self.transfers_observed += 1

    @property
    def link_bw(self) -> float:
        with self._lock:
            return self._link_bw

    # ------------------------------------------------------------------

    def fetch_time_s(self, nbytes: int) -> float:
        return self.link_latency_s + nbytes / max(self.link_bw, 1.0)

    def recompute_time_s(self, n_tokens: int) -> float:
        if self.roofline is not None:
            flops_tok = self.roofline.flops_per_token()
            peak = (self.roofline.peak_flops if self.roofline.has_peaks
                    else DEFAULT_PEAK_FLOPS)
        else:
            flops_tok = DEFAULT_FLOPS_PER_TOKEN
            peak = DEFAULT_PEAK_FLOPS
        return (
            self.prefill_overhead_s
            + n_tokens * flops_tok / (peak * max(self.prefill_eff, 1e-6))
        )

    def decide(self, n_tokens: int, nbytes: int) -> CostDecision:
        """Fetch iff moving ``nbytes`` over the measured link beats
        re-prefilling ``n_tokens`` at the device roofline."""
        fetch_s = self.fetch_time_s(nbytes)
        recompute_s = self.recompute_time_s(n_tokens)
        d = CostDecision(
            fetch=fetch_s < recompute_s,
            fetch_s=fetch_s,
            recompute_s=recompute_s,
            n_tokens=n_tokens,
            nbytes=nbytes,
            link_bw=self.link_bw,
        )
        self.last_decision = d
        return d

    def stats(self) -> dict:
        return {
            "link_bw": self.link_bw,
            "link_bw_pinned": self.pinned,
            "transfers_observed": self.transfers_observed,
            "last_decision": (
                self.last_decision.to_dict() if self.last_decision else None
            ),
            "has_roofline": self.roofline is not None,
        }
