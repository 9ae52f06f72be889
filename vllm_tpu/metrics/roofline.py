"""Shared roofline math for MFU / HBM-bandwidth-utilization estimates.

One consumer in the serving path: the KV fabric's fetch-or-recompute
cost model (``kv_fabric/cost_model.py``), which prices a recompute from
``Worker.roofline_info``. Nothing here is published on ``/metrics``: the
device's time is read from a profile (``/start_profile``, reduced by
``benchmark/harness/trace_reduce.py``).

Model: decode is weight-read + KV-read bound. Per decode step every
resident weight byte is read once and each running request's KV context
is read once; FLOPs/token is the standard 2 x (non-embedding logical
params). Quantized weights count one *byte* toward the bandwidth read
but two *logical params* per packed uint8 toward FLOPs (int4).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping

# Per-chip published peaks by ``device_kind`` (Google Cloud TPU docs).
# v5e: 197 TFLOP/s bf16, 819 GB/s. A kind that is not here has no peak:
# ``mfu`` / ``hbm_bw_util`` raise a KeyError naming it (``has_peaks``
# asks first).
PEAK_FLOPS = {"TPU v5 lite": 197e12, "TPU v5e": 197e12,
              "TPU v4": 275e12, "TPU v6 lite": 918e12}
PEAK_HBM = {"TPU v5 lite": 819e9, "TPU v5e": 819e9,
            "TPU v4": 1200e9, "TPU v6 lite": 1640e9}


def weight_bytes(params: Any) -> int:
    """HBM-resident bytes of a parameter pytree (quantized models stream
    ~1 byte per packed param)."""
    import jax

    return sum(
        x.size * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(params)
    )


def logical_params(params: Any) -> int:
    """Logical parameter count of a pytree: int4 packs two params per
    uint8 byte; every other dtype is one param per element."""
    import jax

    return sum(
        x.size * (2 if str(x.dtype) == "uint8" else 1)
        for x in jax.tree_util.tree_leaves(params)
    )


def kv_bytes_per_token(num_layers: int, num_kv_heads: int, head_dim: int,
                       kv_byte: int) -> int:
    """KV-cache bytes appended per generated token (K and V planes)."""
    return 2 * num_layers * num_kv_heads * head_dim * kv_byte


@dataclasses.dataclass
class RooflineModel:
    """A model's bandwidth/compute roofline, portable across processes.

    ``active_params`` is the non-embedding logical parameter count (the
    2-FLOPs/param/token convention); ``kv_tok_bytes`` the KV bytes read
    per token of live context per decode step.
    """

    weight_bytes: int
    active_params: int
    kv_tok_bytes: int
    device_kind: str = ""

    @property
    def has_peaks(self) -> bool:
        return self.device_kind in PEAK_FLOPS

    @property
    def peak_flops(self) -> float:
        return PEAK_FLOPS[self.device_kind]

    @property
    def peak_hbm(self) -> float:
        return PEAK_HBM[self.device_kind]

    def flops_per_token(self) -> float:
        return 2.0 * self.active_params

    def mfu(self, tok_per_s: float) -> float:
        """Model FLOPs utilization at an observed output-token rate."""
        if tok_per_s <= 0:
            return 0.0
        return tok_per_s * self.flops_per_token() / self.peak_flops

    def hbm_bytes_per_step(self, ctx_tokens: int) -> float:
        """HBM bytes one decode step moves: full weight read + the live
        requests' aggregate KV context read."""
        return self.weight_bytes + ctx_tokens * self.kv_tok_bytes

    def hbm_bw_util(self, steps_per_s: float, ctx_tokens: int) -> float:
        """HBM bandwidth utilization at an observed decode-step rate with
        ``ctx_tokens`` total live context tokens in the batch."""
        if steps_per_s <= 0:
            return 0.0
        return (self.hbm_bytes_per_step(ctx_tokens) * steps_per_s
                / self.peak_hbm)

    def to_dict(self) -> dict:
        """msgpack-able form (crosses the worker->engine RPC boundary)."""
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "RooflineModel":
        return cls(
            weight_bytes=int(d["weight_bytes"]),
            active_params=int(d["active_params"]),
            kv_tok_bytes=int(d["kv_tok_bytes"]),
            device_kind=str(d.get("device_kind", "")),
        )
