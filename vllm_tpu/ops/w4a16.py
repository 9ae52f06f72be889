"""Pallas w4a16 matmul: int4 weight unpack fused into a blocked matmul.

Reference analog: ``csrc/quantization/gptq/q_gemm.cu`` / awq — the CUDA
mixed-precision GEMMs that dequantize 4-bit weights in registers. TPU has
no native int4 datapath, so the kernel streams the PACKED uint8 weight
tiles from HBM (half the bytes of int8, a quarter of bf16 — the decode
HBM-bandwidth win), unpacks nibbles in VMEM, applies the group
(scale, zero) affine, and feeds the MXU in the activation dtype.

Grid ``(m_tiles, n_tiles, k_tiles)`` with the k-block equal to the quant
group size (one scale/zero row per k-tile); fp32 accumulator scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, q_ref, s_ref, z_ref, o_ref, acc_ref, *, k_tiles):
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.int32)  # [bk//2, bn] (Mosaic: no u8->f32)
    lo = (q & 0xF).astype(jnp.float32)
    hi = (q >> 4).astype(jnp.float32)
    bk2, bn = q.shape
    nib = jnp.stack([lo, hi], axis=1).reshape(bk2 * 2, bn)
    # scale/zero tiles carry ALL groups (a (1, bn) block would violate
    # the sublane tile); pick this k-tile's row dynamically.
    s = s_ref[k_i, :][None, :]
    z = z_ref[k_i, :][None, :]
    # Group affine in f32, then the MXU runs in the activation dtype
    # (bf16 dot is 8x the f32 rate; precision is bounded by the 4-bit
    # weights anyway).
    x = x_ref[...]
    w = ((nib - z) * s).astype(x.dtype)
    acc_ref[...] += jax.lax.dot_general(
        x, w,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k_i == k_tiles - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def w4a16_matmul(
    x: jnp.ndarray,  # [M, K] activations
    w,  # Int4Linear: q [K//2, N] u8, scale/zero [G, N] f32
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    m, k = x.shape
    k2, n = w.q.shape
    g = w.scale.shape[0]
    assert k == 2 * k2, (x.shape, w.q.shape)
    group = k // g
    assert group % 2 == 0, f"group size {group} must be even"

    bm = min(block_m, m)
    bn = min(block_n, n)
    bk = group
    # Pad M to the tile (N/K must already divide: N is a model dim, K
    # divides by the group size by construction).
    m_pad = -(-m // bm) * bm
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))
    if n % bn:
        # Fall back to whole-N blocks when the model dim doesn't tile.
        bn = n
    k_tiles = k // bk

    out = pl.pallas_call(
        functools.partial(_kernel, k_tiles=k_tiles),
        grid=(m_pad // bm, n // bn, k_tiles),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((g, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((g, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w.q, w.scale, w.zero)
    return out[:m]
