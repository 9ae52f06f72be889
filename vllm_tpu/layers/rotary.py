"""Rotary position embeddings.

Reference analog: ``vllm/model_executor/layers/rotary_embedding/`` (base
:118 plus ~15 scaling variants). We implement the HF "rotate_half"
convention exactly so logits match transformers numerics, with the scaling
variants the round-1 model zoo needs: none, linear, llama3, yarn.
"""

from __future__ import annotations

import contextlib
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# Tables up to this size inline as trace literals (no device fetch at
# lowering); a larger one would bloat every bucket executable with a
# duplicated constant.
_INLINE_LIMIT_BYTES = 8 << 20

# How many positions the process that is building a model will serve, where
# it has said (``serving_positions``).
_served_positions: int | None = None


@contextlib.contextmanager
def serving_positions(max_model_len: int):
    """While a worker builds its model: a rope table that the published
    ``max_position_embeddings`` would take past the inline limit holds
    ``max_model_len`` rows and no more (positions beyond are never served).
    A long-context model's table is then mostly small enough to inline
    again; one under the limit keeps its published length, so the step
    programs of a model that never had the problem are what they were."""
    global _served_positions
    previous, _served_positions = _served_positions, max_model_len
    try:
        yield
    finally:
        _served_positions = previous


def _base_inv_freq(head_dim: int, theta: float, rotary_dim: int | None = None) -> np.ndarray:
    rd = rotary_dim or head_dim
    return 1.0 / (theta ** (np.arange(0, rd, 2, dtype=np.float64) / rd))


def _llama3_scale(inv_freq: np.ndarray, scaling: dict[str, Any]) -> np.ndarray:
    """Llama-3.1 frequency-dependent scaling (transformers
    ``_compute_llama3_parameters``)."""
    factor = scaling["factor"]
    low = scaling.get("low_freq_factor", 1.0)
    high = scaling.get("high_freq_factor", 4.0)
    orig_len = scaling.get("original_max_position_embeddings", 8192)

    wavelen = 2 * math.pi / inv_freq
    low_wavelen = orig_len / low
    high_wavelen = orig_len / high
    scaled = np.where(wavelen > low_wavelen, inv_freq / factor, inv_freq)
    smooth = (orig_len / wavelen - low) / (high - low)
    smoothed = (1 - smooth) / factor * inv_freq + smooth * inv_freq
    mid = (wavelen <= low_wavelen) & (wavelen >= high_wavelen)
    return np.where(mid, smoothed, scaled)


def _yarn_scale(
    inv_freq: np.ndarray, scaling: dict[str, Any], head_dim: int, theta: float
) -> tuple[np.ndarray, float]:
    """YaRN (NTK-by-parts) scaling; returns (inv_freq, mscale)."""
    factor = scaling["factor"]
    orig_len = scaling.get("original_max_position_embeddings", 4096)
    beta_fast = scaling.get("beta_fast", 32)
    beta_slow = scaling.get("beta_slow", 1)

    def find_dim(num_rot: float) -> float:
        return (
            head_dim * math.log(orig_len / (num_rot * 2 * math.pi))
        ) / (2 * math.log(theta))

    lo = max(math.floor(find_dim(beta_fast)), 0)
    hi = min(math.ceil(find_dim(beta_slow)), head_dim - 1)
    ramp = np.clip(
        (np.arange(head_dim // 2, dtype=np.float64) - lo) / max(hi - lo, 1e-3), 0, 1
    )
    mask = 1.0 - ramp
    scaled = inv_freq / factor * (1 - mask) + inv_freq * mask
    attn_factor = scaling.get("attn_factor", scaling.get("attention_factor", 1.0)) or 1.0

    def get_mscale(scale: float, mscale: float = 1.0) -> float:
        return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0

    if "mscale" in scaling and "mscale_all_dim" in scaling:
        # DeepSeek yarn: the mscale ratio (HF _compute_yarn_parameters).
        m = (
            get_mscale(factor, scaling["mscale"])
            / get_mscale(factor, scaling["mscale_all_dim"])
            * attn_factor
        )
    else:
        m = get_mscale(factor) * attn_factor
    return scaled, m


class RotaryEmbedding:
    """Precomputes cos/sin tables up to ``max_position``; applied by gather
    at runtime positions (ragged batch friendly)."""

    def __init__(
        self,
        head_dim: int,
        max_position: int,
        theta: float = 10000.0,
        rope_scaling: dict[str, Any] | None = None,
        rotary_dim: int | None = None,
        dtype=jnp.float32,
        original_max_position: int | None = None,
    ) -> None:
        self.head_dim = head_dim
        self.rotary_dim = rotary_dim or head_dim
        inv_freq = _base_inv_freq(head_dim, theta, rotary_dim)
        mscale = 1.0
        inv_freq_long = None  # longrope: second basis past original_max
        original_max = max_position
        if rope_scaling:
            rope_type = rope_scaling.get("rope_type", rope_scaling.get("type", "default"))
            if rope_type == "llama3":
                inv_freq = _llama3_scale(inv_freq, rope_scaling)
            elif rope_type == "linear":
                inv_freq = inv_freq / rope_scaling["factor"]
            elif rope_type == "yarn":
                inv_freq, mscale = _yarn_scale(
                    inv_freq, rope_scaling, self.rotary_dim, theta
                )
            elif rope_type in ("longrope", "su"):
                # Phi-3 long-context recipe: per-frequency SHORT factors
                # inside the original window, LONG factors beyond, both
                # attention-scaled. Per-POSITION table choice follows the
                # reference serving implementation; HF instead re-bases
                # the WHOLE sequence once its length crosses original_max
                # (unservable with a paged cache — early K would need
                # recompute), so outputs match HF exactly for sequences
                # within one regime.
                original_max = int(
                    rope_scaling.get(
                        "original_max_position_embeddings",
                        original_max_position or 0,
                    )
                )
                if not original_max:
                    # Without the pivot the long table/mscale would be
                    # silently dropped — numerically wrong, so refuse.
                    raise ValueError(
                        "longrope scaling needs original_max_position_"
                        "embeddings (in rope_scaling or the model config)"
                    )
                short = np.asarray(
                    rope_scaling["short_factor"], np.float64
                )
                long = np.asarray(rope_scaling["long_factor"], np.float64)
                factor = rope_scaling.get(
                    "factor", max(max_position / original_max, 1.0)
                )
                mscale = rope_scaling.get("attention_factor")
                if mscale is None:
                    mscale = (
                        1.0 if factor <= 1.0
                        else math.sqrt(
                            1 + math.log(factor) / math.log(original_max)
                        )
                    )
                inv_freq_long = inv_freq / long
                inv_freq = inv_freq / short
            elif rope_type in ("default", "dynamic"):
                pass  # dynamic NTK beyond max_position: out of round-1 scope
            else:
                raise NotImplementedError(f"rope_type {rope_type}")

        rows = max_position
        row_bytes = len(inv_freq) * np.dtype(dtype).itemsize
        if _served_positions and rows * row_bytes > _INLINE_LIMIT_BYTES:
            rows = min(rows, _served_positions)
        t = np.arange(rows, dtype=np.float64)
        freqs = np.outer(t, inv_freq)  # [P, rd/2]
        if inv_freq_long is not None and rows > original_max:
            freqs[original_max:] = np.outer(
                t[original_max:], inv_freq_long
            )
        # HOST arrays: they reach jit as inline constants, so lowering
        # never needs a device fetch.
        self._cos_np = np.ascontiguousarray(
            (np.cos(freqs) * mscale).astype(dtype)
        )
        self._sin_np = np.ascontiguousarray(
            (np.sin(freqs) * mscale).astype(dtype)
        )

    def _table(self, name: str) -> jnp.ndarray:
        """Small tables inline as trace literals; a table still over the
        limit at the served length is ONE device array, made outside
        whatever trace first asks for it (made inside, it is that trace's
        tracer, and the next step program to be traced meets it:
        ``UnexpectedTracerError``)."""
        host = getattr(self, f"_{name}_np")
        if host.nbytes <= _INLINE_LIMIT_BYTES:
            return jnp.asarray(host)
        if not hasattr(self, f"_{name}_dev"):
            with jax.ensure_compile_time_eval():
                setattr(self, f"_{name}_dev", jnp.asarray(host))
        return getattr(self, f"_{name}_dev")

    @property
    def cos(self) -> jnp.ndarray:
        return self._table("cos")

    @property
    def sin(self) -> jnp.ndarray:
        return self._table("sin")

    def __call__(
        self, positions: jnp.ndarray, q: jnp.ndarray, k: jnp.ndarray
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """positions [T]; q [T, H, D]; k [T, KH, D] (rotate_half layout)."""
        cos = self.cos[positions][:, None, :]  # [T, 1, rd/2]
        sin = self.sin[positions][:, None, :]
        q = _apply_rotate_half(q, cos, sin, self.rotary_dim)
        k = _apply_rotate_half(k, cos, sin, self.rotary_dim)
        return q, k


def _apply_rotate_half(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray, rotary_dim: int
) -> jnp.ndarray:
    dtype = x.dtype
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    x1 = rot[..., : rotary_dim // 2].astype(jnp.float32)
    x2 = rot[..., rotary_dim // 2 :].astype(jnp.float32)
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    rot_out = jnp.concatenate([out1, out2], axis=-1).astype(dtype)
    if rest.shape[-1]:
        return jnp.concatenate([rot_out, rest], axis=-1)
    return rot_out


def _apply_interleaved(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray, rotary_dim: int
) -> jnp.ndarray:
    """GPT-J/GLM/Cohere rope layout: rotation PAIRS are adjacent lanes
    (x[2i], x[2i+1]) instead of rotate_half's (x[i], x[i+rd/2])."""
    dtype = x.dtype
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    x1 = rot[..., 0::2].astype(jnp.float32)
    x2 = rot[..., 1::2].astype(jnp.float32)
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    rot_out = jnp.stack([out1, out2], axis=-1).reshape(rot.shape).astype(dtype)
    if rest.shape[-1]:
        return jnp.concatenate([rot_out, rest], axis=-1)
    return rot_out
