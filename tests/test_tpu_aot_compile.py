"""Compile the serving path's Pallas kernels for a described (not attached)
TPU v5e at Llama-3-8B widths.

Interpret mode cannot see what the chip's compiler refuses: a block that
breaks the (8, 128) tiling rule, more scoped VMEM than a kernel may use,
a Mosaic call that GSPMD is asked to partition. The TPU compiler is
installed here and compiles for a described topology, so each case below
asks it directly. Nothing runs; a pass says "compiles", never "is right"
or "is fast" (``chip_smoke.py`` holds the results to the XLA reference on
the chip).

The topology is described inside the module-scoped fixture and nowhere
else: only one process may hold libtpu, so this must not happen at import,
in ``conftest.py``, in a ``skipif`` or in a ``parametrize`` (every xdist
worker imports every test file), and the compiles stay in this one file
and in the test's own process.
"""

from __future__ import annotations

import functools
import math
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jax_core
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

# Llama-3-8B: 32 q heads, 8 KV heads, head_dim 128, vocab 128256, page 16.
H, KH, D, V, PAGE = 32, 8, 128, 128256, 16
LAYERS, NUM_BLOCKS = 32, 2048
PAGES_PER_SEQ = 128  # 2048-token context


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp4_mesh(topo):
    return Mesh(
        np.asarray(topo.devices).reshape(1, 1, 1, 4), ("dp", "pp", "cp", "tp")
    )


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _attn_shapes(sharding, t, r, kv_dtype, kv_sharding=None, q_sharding=None):
    def s(shape, dtype, sh=sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    return dict(
        q=s((t, H, D), jnp.bfloat16, q_sharding or sharding),
        kv=s((LAYERS, NUM_BLOCKS, PAGE, 2 * KH, D), kv_dtype,
             kv_sharding or sharding),
        layer=s((1,), jnp.int32),
        kv_lens=s((r,), jnp.int32),
        pages=s((r, PAGES_PER_SEQ), jnp.int32),
        cu_q_lens=s((r + 1,), jnp.int32),
        num_seqs=s((1,), jnp.int32),
    )


def _kv_scale(kv_dtype):
    return 1.0 if kv_dtype == jnp.float8_e4m3fn else None


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.float8_e4m3fn],
                         ids=["kv_bf16", "kv_fp8"])
@pytest.mark.parametrize("ordered", [True, False],
                         ids=["rows_in_order", "rows_as_given"])
def test_decode_kernel_compiles(one_chip, kv_dtype, ordered):
    """The cell's bucket of 128 rows, with the step's order of the rows as
    the fifth scalar prefetch (q rows fetched and output rows written each
    at its own place) and without one."""
    from vllm_tpu.ops.rpa_decode_kernel import decode_paged_attention

    a = _attn_shapes(one_chip, 128, 128, kv_dtype)
    scale = _kv_scale(kv_dtype)
    fn = functools.partial(
        decode_paged_attention, sm_scale=D**-0.5, k_scale=scale, v_scale=scale
    )
    order = a["kv_lens"] if ordered else None  # (i32[128], as the order is)
    jax.jit(fn).lower(
        a["q"], a["kv"], a["layer"], a["kv_lens"], a["pages"], a["num_seqs"],
        order,
    ).compile()


def test_decode_kernel_compiles_on_several_heads_blocks(one_chip):
    """32 KV heads (Llama-2-7B's) are four heads blocks of 8: a row's
    output leaves by the kernel's own copy into a slice of its heads."""
    from vllm_tpu.ops.rpa_decode_kernel import decode_paged_attention

    a = _attn_shapes(one_chip, 128, 128, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct(
        (4, NUM_BLOCKS, PAGE, 2 * H, D), jnp.bfloat16, sharding=one_chip)
    jax.jit(functools.partial(decode_paged_attention, sm_scale=D**-0.5)).lower(
        a["q"], kv, a["layer"], a["kv_lens"], a["pages"], a["num_seqs"],
        a["kv_lens"],
    ).compile()


def _ragged_shapes(sharding, t, r, pages, q_heads, kv_heads, kv_dtype):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (
        s((t, q_heads, D), jnp.bfloat16),
        s((LAYERS, NUM_BLOCKS, PAGE, 2 * kv_heads, D), kv_dtype),
        s((1,), jnp.int32), s((r,), jnp.int32), s((r, pages), jnp.int32),
        s((r + 1,), jnp.int32), s((1,), jnp.int32),
    )


# (tokens, rows, pages a row, q heads, KV heads, KV dtype, block_len, LSE):
# Llama-3-8B's and mistral7b.chat's 32 q / 8 KV heads at the cell's token
# buckets (512, 16) and page buckets (16 ... 512), sdar30b.chat's 32 / 4
# under the mask over blocks of 4, the tp=4 shard (8 / 2), fp8 KV, the LSE
# output of context parallelism.
RAGGED_SHAPES = {
    "T512_R128": (512, 128, PAGES_PER_SEQ, H, KH, jnp.bfloat16, 1, False),
    "T2048_R64": (2048, 64, PAGES_PER_SEQ, H, KH, jnp.bfloat16, 1, False),
    "T512_R128_kv_fp8": (512, 128, PAGES_PER_SEQ, H, KH, jnp.float8_e4m3fn,
                         1, False),
    "mistral_pages16": (512, 128, 16, 32, 8, jnp.bfloat16, 1, False),
    "mistral_pages512": (512, 128, 512, 32, 8, jnp.bfloat16, 1, False),
    "mistral_T16": (16, 128, 64, 32, 8, jnp.bfloat16, 1, False),
    "sdar_block_len_4": (512, 128, 128, 32, 4, jnp.bfloat16, 4, False),
    "tp4_shard": (512, 128, PAGES_PER_SEQ, 8, 2, jnp.bfloat16, 1, False),
    "lse": (512, 128, PAGES_PER_SEQ, H, KH, jnp.bfloat16, 1, True),
}


@pytest.mark.parametrize("shape", list(RAGGED_SHAPES))
def test_ragged_kernel_compiles_with_own_block_choice(one_chip, shape):
    """The blocks, the rungs and the scoped VMEM ``ragged_tiling`` picks
    for the shapes (both rungs are in the one program wherever the query
    block holds more than a narrow window)."""
    from vllm_tpu.ops.rpa_kernel import ragged_paged_attention, ragged_tiling

    t, r, pages, q_heads, kv_heads, kv_dtype, block_len, lse = (
        RAGGED_SHAPES[shape])
    tiling = ragged_tiling(
        q_heads, 2 * kv_heads, D, PAGE, jnp.bfloat16, kv_dtype, t, pages,
        block_len=block_len,
    )
    # A KV head's wide tile holds 512 rows: 128 tokens at 4 q heads a KV
    # head, 64 at sdar30b.chat's 8.
    assert tiling.q_per_blk == min(t, 512 // (q_heads // kv_heads))
    assert tiling.kv_pages_per_blk == min(pages, 32)
    assert tiling.narrow_q == 4 < tiling.q_per_blk
    assert tiling.narrow_sub_pages == min(pages, 16)
    assert (tiling.vmem_limit_bytes or 0) <= 64 << 20
    scale = _kv_scale(kv_dtype)
    fn = functools.partial(
        ragged_paged_attention, sm_scale=D**-0.5, k_scale=scale,
        v_scale=scale, block_len=block_len, return_lse=lse,
    )
    jax.jit(fn).lower(
        *_ragged_shapes(one_chip, t, r, pages, q_heads, kv_heads, kv_dtype)
    ).compile()


def test_tracing_the_ragged_kernel_once_is_cheap(one_chip):
    """The kernel is traced and lowered once a step program (30 programs
    in mistral7b.chat's warm-up), in Python: a rung that unrolled a long
    loop would cost a start seconds (PR 30 lost 20 s to one). Tracing and
    lowering it takes 0.3-0.7 s here; the bound leaves room for six busy
    workers."""
    import time

    from vllm_tpu.ops.rpa_kernel import ragged_paged_attention

    args = _ragged_shapes(one_chip, 512, 128, 512, 32, 8, jnp.bfloat16)
    fn = jax.jit(functools.partial(ragged_paged_attention, sm_scale=D**-0.5))
    fn.lower(*args)  # imports and caches that are not the kernel's
    fn = jax.jit(functools.partial(ragged_paged_attention, sm_scale=D**-0.25))
    t0 = time.process_time()
    fn.lower(*args)
    assert time.process_time() - t0 < 4.0


# name -> (rows, groups of the [L * E] stack, k, n): the matrices of the
# cells' sparse layers that ``layers/moe.py::_gmm`` sends to the repo's
# kernel.
GMM_SHAPES = {
    "lfm2_gate": (2048, 14 * 32, 2048, 1792),
    "lfm2_down": (2048, 14 * 32, 1792, 2048),
    "sdar_gate": (4096, 7 * 128, 2048, 768),
    "sdar_down": (4096, 7 * 128, 768, 2048),
    "laguna_share_gate": (4096, 39 * 32, 2048, 512),
    "kimi_share_gate": (4096, 12 * 32, 2304, 1024),
    "kimi_share_down": (4096, 12 * 32, 1024, 2304),
    "mimo_share_gate": (4096, 9 * 16, 4096, 2048),
    "mimo_share_down": (4096, 9 * 16, 2048, 4096),
}


def _gmm_shapes(sharding, shape):
    m, g, k, n = GMM_SHAPES[shape]
    s = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=sharding)
    return s((m, k), jnp.bfloat16), s((g, k, n), jnp.bfloat16), s((g,), jnp.int32)


@pytest.mark.parametrize("shape", list(GMM_SHAPES))
def test_grouped_matmul_kernel_compiles(one_chip, shape):
    """``ops/gmm_kernel.py`` as ``layers/moe.py::_gmm`` calls it, on the
    stacks whole: the weights' slots fit the scoped VMEM it asks for, the
    column tile's slice of a matrix in HBM is one Mosaic takes, and the
    program's text still holds a call named ``gmm`` (the name the trace
    prints and the benchmark's three readers sum)."""
    from vllm_tpu.layers import moe
    from vllm_tpu.ops import gmm_kernel

    _, _, k, n = GMM_SHAPES[shape]
    _, tk, tn = moe._gmm_tiling(k, n)
    assert moe._takes_gmm_kernel(k, n) and k % tk == 0 and tk <= 2048
    slots = gmm_kernel.slots_for(2 * k * tn)
    assert gmm_kernel.vmem_bytes(k, tn, slots) <= 24 << 20
    text = jax.jit(moe._gmm).lower(*_gmm_shapes(one_chip, shape)).compile().as_text()
    calls = [l.split(" = ")[0].split()[-1] for l in text.splitlines()
             if "tpu_custom_call" in l]
    assert len(calls) == 1 and calls[0].startswith("%gmm")


def test_tracing_the_grouped_matmul_once_is_cheap(one_chip):
    """The kernel is traced and lowered three times a sparse layer's body,
    a body a step program: one dot and a masked store, no loop unrolled.
    The bound is the ragged kernel's; it takes 0.1-0.2 s here."""
    import time

    from vllm_tpu.ops import gmm_kernel

    args = _gmm_shapes(one_chip, "lfm2_gate")
    jax.jit(functools.partial(gmm_kernel.gmm, tn=1792)).lower(*args)  # imports
    gmm_kernel.gmm.clear_cache()
    fn = jax.jit(functools.partial(gmm_kernel.gmm, tn=1792))
    t0 = time.process_time()
    fn.lower(*args)
    assert time.process_time() - t0 < 4.0


@pytest.mark.parametrize(
    "r,v,penalties,top_k,top_p,return_prob,active",
    [(128, V, False, False, False, False, False),
     (128, V, False, True, True, False, False),
     (128, V, True, True, True, False, False),
     # sdar30b.chat's block pass: 128 rows x 4 positions at the Qwen
     # family's vocabulary (38 noise tiles, no power of two), the drawn
     # token's probability returned, the usable positions counted.
     (512, 151936, False, False, True, True, True),
     (512, 151936, True, True, True, True, True),
     # mistral7b.chat's decode step with its live rows counted.
     (128, 32768, False, False, True, False, True),
     # kimilinear48b.long-answers: all 192 slots' rows at a vocabulary of
     # 163840 (40 noise tiles), its live rows counted.
     (192, 163840, False, False, True, False, True)],
    ids=["plain", "top_k_top_p", "penalties", "wide_prob_active",
         "wide_prob_active_penalties", "narrow_active", "widest_active"],
)
def test_sampler_kernel_compiles(
    one_chip, r, v, penalties, top_k, top_p, return_prob, active
):
    from vllm_tpu.ops.sampler_kernel import fused_sample

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = (r, v) if penalties else (1, 128)
    fn = functools.partial(
        fused_sample, needs_penalties=penalties, needs_top_k=top_k,
        needs_top_p_min_p=top_p, return_prob=return_prob,
    )
    jax.jit(fn).lower(
        s((r, v), jnp.float32), s((r, 128), jnp.float32),
        s((r, 128), jnp.int32), s(state, jnp.int32), s(state, jnp.int8),
        *([s((), jnp.int32)] if active else []),
    ).compile()


# kimilinear48b.long-answers: 32 heads of 128 over 193 slots of 10 layers,
# latent rows of 512 + 64 in 640 lanes over 3 layers, 192 rows a step.
KDA_H, KDA_D, KDA_SLOTS, KDA_LAYERS = 32, 128, 193, 10
KDA_STATE_BYTES = KDA_LAYERS * KDA_SLOTS * KDA_H * KDA_D * KDA_D * 4


def _kda_shapes(sharding, t, r):
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return dict(
        qkva=[s((t, KDA_H, KDA_D))] * 4, beta=s((t, KDA_H)),
        state=s((KDA_LAYERS, KDA_SLOTS, KDA_H, KDA_D, KDA_D)),
        slots=s((r,), jnp.int32), fresh=s((r,), jnp.bool_), n=s((), jnp.int32),
        qsl=s((r + 1,), jnp.int32),
    )


def test_kda_one_token_kernel_compiles_and_updates_the_slots_in_place(one_chip):
    """The Pallas kernel of ``ops/kda.py`` at the cell's widths: a row's 2
    MiB of state a grid step, double-buffered in and out, inside the
    scoped VMEM it asks for; the 4 GB state stack aliased to the output."""
    from vllm_tpu.ops import kda

    sh = _kda_shapes(one_chip, 192, 192)

    def fn(q, k, v, a, beta, state, slots, fresh, n):
        return kda._decode_pallas(q, k, v, a, beta, state, jnp.int32(3), slots, fresh, n)

    compiled = jax.jit(fn, donate_argnums=(5,)).lower(
        *sh["qkva"], sh["beta"], sh["state"], sh["slots"], sh["fresh"], sh["n"]).compile()
    assert "kda_decode_kernel" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= KDA_STATE_BYTES and mem.temp_size_in_bytes < 64 << 20


def test_kda_chunked_form_compiles_with_the_state_in_place(one_chip):
    """The XLA form of the chunks (what runs off the TPU, and the tests'
    oracle) at a 512-token step: matmuls in a scan in a ``while_loop`` of
    rounds, the state stack carried through both without a copy (its
    temporaries are the chunks' operands, not the 4 GB)."""
    from vllm_tpu.ops import kda

    sh = _kda_shapes(one_chip, 512, 192)

    def fn(q, k, v, a, beta, state, slots, fresh, qsl, n):
        return kda._chunks_xla(q, k, v, a, beta, state, jnp.int32(3), slots, fresh, qsl, n,
                               n_chunks=9)

    mem = jax.jit(fn, donate_argnums=(5,)).lower(
        *sh["qkva"], sh["beta"], sh["state"], sh["slots"], sh["fresh"], sh["qsl"],
        sh["n"]).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= KDA_STATE_BYTES and mem.temp_size_in_bytes < 512 << 20


def test_kda_chunks_compile_as_one_kernel_with_fewer_loops_than_the_xla_form(
        one_chip, monkeypatch):
    """A mixed step's KDA state update at the cell's shapes (512 tokens,
    192 rows, 193 slots of 10 layers, 32 heads of 128) with the chunked
    form as ``kda_chunk_kernel``: the kernel is in the compiled program
    beside the one-token one, the 4 GB state stack goes through both in
    place, and what is left around them holds fewer ``while`` operations
    than the same step over the XLA form (its rounds, its scan over the
    places, its scatters)."""
    from vllm_tpu.ops import kda

    sh = _kda_shapes(one_chip, 512, 192)
    # The test's process sees the CPU: name the forms a TPU takes here.
    monkeypatch.setattr(kda, "_pallas_form", lambda state: (True, False))

    def compiled():
        def fn(q, k, v, a, beta, state, slots, fresh, qsl, n):  # traced anew each time
            return kda.kda_attention(q, k, v, a, beta, state, jnp.int32(3), slots, fresh,
                                     qsl, n, decode_only=False)

        return jax.jit(fn, donate_argnums=(5,)).lower(
            *sh["qkva"], sh["beta"], sh["state"], sh["slots"], sh["fresh"], sh["qsl"],
            sh["n"]).compile()

    kernel = compiled()
    text = kernel.as_text()
    assert "kda_chunk_kernel" in text and "kda_decode_kernel" in text
    mem = kernel.memory_analysis()
    assert mem.alias_size_in_bytes >= KDA_STATE_BYTES and mem.temp_size_in_bytes < 128 << 20
    monkeypatch.setattr(
        kda, "_chunks_pallas",
        lambda *args, n_chunks, **kw: kda._chunks_xla(*args, n_chunks=n_chunks))
    parent = compiled().as_text()
    assert "kda_chunk_kernel" not in parent and "kda_decode_kernel" in parent
    assert 0 < text.count(" while(") < parent.count(" while(")


def test_slot_rows_are_read_and_written_without_a_loop_a_row(one_chip):
    """The convolution's tails, 192 rows of 3 x 12288 numbers into 193
    slots of one of 10 layers: XLA's scatter of such rows is a loop of 192
    iterations on the TPU (1.4 ms a layer on the v5e); the one-hot form is
    a matmul and a select, with the stack updated in place."""
    from vllm_tpu.ops import kda

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    width = 3 * 3 * KDA_H * KDA_D

    def fn(stack, layer, slots, rows, n):
        tails = kda.read_slot_rows(stack, layer, slots)
        return tails, kda.write_slot_rows(stack, layer, slots, rows, n)

    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        s((KDA_LAYERS, KDA_SLOTS, width), jnp.bfloat16), s((), jnp.int32),
        s((192,), jnp.int32), s((192, width), jnp.bfloat16), s((), jnp.int32)).compile()
    text = compiled.as_text()
    assert " while(" not in text and " scatter(" not in text and " gather(" not in text
    assert compiled.memory_analysis().alias_size_in_bytes >= KDA_LAYERS * KDA_SLOTS * width * 2


def _kda_conv_shapes(sharding, t, r):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pj = KDA_H * KDA_D
    return ([s((t, pj), jnp.bfloat16)] * 3,
            s((KDA_LAYERS, KDA_SLOTS, 3, 3 * KDA_H, KDA_D), jnp.bfloat16),
            [s((4, pj), jnp.bfloat16)] * 3, s((r,), jnp.int32), s((r,), jnp.bool_),
            s((t,), jnp.int32), s((r + 1,), jnp.int32), s((), jnp.int32))


@pytest.mark.parametrize("t,decode_only", [(512, False), (192, True)],
                         ids=["mixed_512_tokens", "decode_only_192_tokens"])
def test_kda_short_convolution_compiles_as_one_kernel_over_the_slots_in_place(
        one_chip, monkeypatch, t, decode_only):
    """The mixer's convolution at the cell's two steps (512 or 192 tokens,
    192 rows, q, k and v of 32 heads of 128 in bf16, 193 slots of 10 layers
    of 3 x 96 x 128): ONE ``kda_conv_kernel``, nothing gathered, scattered or
    looped over around it, and the 142 MB stack of tails goes through in
    place (a slot is whole tiles: Mosaic refuses a DMA of one row of a
    ``[slots, 36864]`` layer, "slice shape must be aligned to tiling")."""
    from vllm_tpu.ops import kda

    monkeypatch.setattr(kda, "_pallas_form", lambda state=None: (True, False))
    xs, stack, taps, slots, fresh, tok_req, qsl, n = _kda_conv_shapes(one_chip, t, 192)

    def fn(xq, xk, xv, stack, cq, ck, cv, slots, fresh, tok_req, qsl, n):
        return kda.kda_short_conv((xq, xk, xv), stack, (cq, ck, cv), jnp.int32(3), slots,
                                  fresh, tok_req, qsl, n, decode_only=decode_only)

    compiled = jax.jit(fn, donate_argnums=(3,)).lower(
        *xs, stack, *taps, slots, fresh, tok_req, qsl, n).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "kda_conv_kernel" in text
    assert not any(op in text for op in (" gather(", " scatter(", " while("))
    mem = compiled.memory_analysis()
    stack_bytes = KDA_LAYERS * KDA_SLOTS * 3 * 3 * KDA_H * KDA_D * 2
    assert mem.alias_size_in_bytes >= stack_bytes and mem.temp_size_in_bytes < 64 << 20


def test_tracing_the_convolutions_kernel_once_is_cheap(one_chip):
    """``kda_conv_kernel`` is traced and lowered ten times a step program
    (a KDA layer each outside the scan's body, once inside): its body is
    loops over a step's rows and tokens, not an unrolled tile. The bound is
    the ragged kernel's (``test_tracing_the_ragged_kernel_once_is_cheap``);
    it takes 0.3-0.6 s here."""
    import time

    from vllm_tpu.ops import kda

    xs, stack, taps, slots, fresh, _, qsl, n = _kda_conv_shapes(one_chip, 512, 192)

    def lower(layer):
        def fn(xq, xk, xv, stack, cq, ck, cv, slots, fresh, qsl, n):
            return kda._conv_pallas((xq, xk, xv), stack, (cq, ck, cv), jnp.int32(layer),
                                    slots, fresh, qsl, n, tile=kda.CONV_TILE)

        jax.jit(fn).lower(*xs, stack, *taps, slots, fresh, qsl, n)

    lower(0)  # imports and caches that are not the kernel's
    kda._conv_pallas.clear_cache()
    t0 = time.process_time()
    lower(1)
    assert time.process_time() - t0 < 4.0


@pytest.mark.parametrize("t,r,pages", [(512, 192, 768), (192, 192, 256)],
                         ids=["mixed_T512_P768", "decode_T192_P256"])
def test_latent_attention_kernel_compiles(one_chip, t, r, pages):
    """``ops/mla_kernel.py`` at 32 heads over latent rows of 576 numbers in
    640 lanes, tokens on a page's sublanes: the layout with an axis of one
    head was refused here (a page copy cannot slice inside a tile), and a
    row of 576 lanes likewise. 192 rows x 768 pages of page table in SMEM."""
    from vllm_tpu.ops.mla_attention import mla_kv_cache_shape
    from vllm_tpu.ops.mla_kernel import mla_ragged_paged_attention

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cache = mla_kv_cache_shape(3, 30000, PAGE, 512 + 64)
    assert cache == (3, 30000, PAGE, 640)
    fn = functools.partial(mla_ragged_paged_attention, sm_scale=192 ** -0.5, value_dim=512)
    text = jax.jit(fn).lower(
        s((t, 32, 640), jnp.bfloat16), s(cache, jnp.bfloat16), s((1,), jnp.int32),
        s((r,), jnp.int32), s((r, pages), jnp.int32), s((r + 1,), jnp.int32),
        s((1,), jnp.int32)).compile().as_text()
    assert "mla_kernel" in text


@pytest.mark.parametrize("decode_only", [True, False],
                         ids=["decode", "ragged"])
def test_attention_dispatch_compiles_under_tp4(
    tp4_mesh, monkeypatch, decode_only
):
    """Through ``dispatch_ragged_attention`` with q sharded over heads and
    the cache over its KV rows, as ``models/llama.py`` shards them: the
    kernel must sit inside the tp shard_map (GSPMD refuses to partition a
    Mosaic call) and the compiled step must still hold it."""
    from vllm_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    rep = NamedSharding(tp4_mesh, P())
    t = r = 128
    if not decode_only:
        t = 512
    a = _attn_shapes(
        rep, t, r, jnp.bfloat16,
        kv_sharding=NamedSharding(tp4_mesh, P(None, None, None, "tp", None)),
        q_sharding=NamedSharding(tp4_mesh, P(None, "tp", None)),
    )

    def step(q, kv, layer, kv_lens, pages, cu_q_lens, num_seqs):
        md = attention.AttentionMetadata(
            positions=None, slot_mapping=None, block_tables=pages,
            seq_lens=kv_lens, query_start_loc=cu_q_lens, token_req_idx=None,
            logits_indices=None, num_seqs=num_seqs, decode_only=decode_only,
            tp_mesh=tp4_mesh,
        )
        return attention.dispatch_ragged_attention(
            q, kv, layer[0], md, D**-0.5
        )

    compiled = jax.jit(step).lower(
        a["q"], a["kv"], a["layer"], a["kv_lens"], a["pages"],
        a["cu_q_lens"], a["num_seqs"],
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    kernel = "rpa_decode_kernel" if decode_only else "rpa_kernel"
    assert kernel in text


# Mistral-7B-v0.3 at the depth the benchmark's cell runs it (16 of 32
# layers), with the cell's KV cache.
MISTRAL = dict(
    num_hidden_layers=16, hidden_size=4096, num_attention_heads=32,
    num_key_value_heads=8, intermediate_size=14336, vocab_size=32768,
    rms_norm_eps=1e-5, tie_word_embeddings=False,
    max_position_embeddings=12288, rope_theta=1e6, rope_scaling=None,
)
MISTRAL_KV_BLOCKS, MISTRAL_PAGES = 6698, 64

_HLO_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
              "u32": 4, "f32": 4}


def _while_body_instructions(hlo: str):
    """(name, opcode, bytes of the result, its dims) of every instruction
    that sits directly in the body of a ``while`` of the optimised program:
    the layer scan. A fused computation's inside is another computation and
    is not listed: what it holds never reaches HBM."""
    bodies = set(re.findall(r"\bbody=%([\w.\-]+)", hlo))
    assert bodies, "the step program holds no while loop: is the scan gone?"
    current = None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if head:
            current = head.group(1)
            continue
        if current not in bodies:
            continue
        m = re.match(
            r"\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(", line
        )
        if m:
            name, dtype, dims, opcode = m.groups()
            dims = tuple(int(d) for d in dims.split(",") if d)
            yield name, opcode, math.prod(dims) * _HLO_BYTES.get(dtype, 4), dims


@pytest.mark.parametrize("tree", ["bf16", "int8"])
@pytest.mark.parametrize("t,r,decode_only", [(128, 128, True), (512, 128, False)],
                         ids=["decode_T128_R128", "mixed_T512_R128"])
def test_layer_scan_reads_each_weight_once_in_place(
    one_chip, monkeypatch, t, r, decode_only, tree
):
    """The step program of the benchmark's cell, compiled for the v5e: no
    layer may slice or relay a WEIGHT out of its ``[L, ...]`` stack into a
    temporary. PR 27 found three such pairs a layer (``wq``, ``wk``, ``wv``:
    a ``dynamic-slice`` fusion and a transposing ``copy`` each, 50 MB read
    and written twice, 9.9 % of the chip's step), because XLA folded the
    head split into the three projections.

    The limit is the smallest of those weights, one layer of ``wk`` in
    bf16 (8 MiB), and not lower: the relayout of q to the attention
    kernel's [T, heads, head_dim] tiling is an activation of 1 MiB at 128
    tokens and 4 MiB at 512. The int8 tree (native int8 matmuls, as on the
    chip) never had the pairs; it is here so that it stays so, held to the
    same limit: a dequantised layer of any weight, or an int8 copy of any
    but ``wk`` and ``wv``.
    """
    from vllm_tpu.layers import quant
    from vllm_tpu.models.llama import LlamaForCausalLM
    from vllm_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(quant, "_use_w8a8", lambda: True)
    model = LlamaForCausalLM(types.SimpleNamespace(**MISTRAL), dtype=jnp.bfloat16)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(model.init_dummy_params, jax.random.PRNGKey(0)),
    )
    layers = params["layers"]
    if tree == "int8":
        for key in model.QUANT_KEYS:
            w = layers[key].shape
            layers[key] = quant.QuantizedLinear(
                q=s(w, jnp.int8), scale=s(w[:-2] + w[-1:], jnp.float32)
            )
    md = attention.AttentionMetadata(
        positions=s((t,), jnp.int32), slot_mapping=s((t,), jnp.int32),
        block_tables=s((r, MISTRAL_PAGES), jnp.int32),
        seq_lens=s((r,), jnp.int32), query_start_loc=s((r + 1,), jnp.int32),
        token_req_idx=s((t,), jnp.int32), logits_indices=s((r,), jnp.int32),
        num_seqs=s((1,), jnp.int32), decode_only=decode_only,
    )
    kv = s(attention.kv_cache_shape(model.num_layers, MISTRAL_KV_BLOCKS, PAGE,
                                    model.num_kv_heads, model.head_dim),
           jnp.bfloat16)
    compiled = jax.jit(model.apply, donate_argnums=(1,)).lower(
        params, kv, s((t,), jnp.int32), md
    ).compile()
    text = compiled.as_text()

    assert ("rpa_decode_kernel" if decode_only else "rpa_kernel") in text
    limit = 2 * model.hidden_size * model.num_kv_heads * model.head_dim
    moved = [
        (name, opcode, nbytes)
        for name, opcode, nbytes, _ in _while_body_instructions(text)
        if nbytes >= limit
        and (opcode in ("copy", "transpose", "dynamic-slice")
             or "dynamic-slice" in name)
    ]
    assert not moved, (
        f"the layer scan moves weight-sized temporaries (>= {limit} bytes): "
        f"{moved}"
    )


def _gmm_group_sizes_come_from(jaxpr, source: int) -> list[bool]:
    """For every call of the grouped matmul (``ops/gmm_kernel.py::gmm``, or
    megablox's where a shape goes there) in the traced program, in order:
    whether its ``group_sizes`` operand is computed from the program's flat
    input ``source`` through integers and booleans ALONE. Activations are
    floats, so what a token holds never counts; a count of live tokens
    does, through a mask, a select on the expert ids and a bincount."""

    def walk(jaxpr, from_source):
        seen = {v for v, hit in zip(jaxpr.invars, from_source) if hit}
        found = []

        def hit(v):
            return not isinstance(v, jax_core.Literal) and v in seen

        for eqn in jaxpr.eqns:
            ins = [hit(v) for v in eqn.invars]
            name = eqn.primitive.name
            if name in ("jit", "pjit") and eqn.params["name"] == "gmm":
                found.append(ins[2])
            inner = [getattr(p, "jaxpr", p) for p in eqn.params.values()
                     if isinstance(p, (jax_core.Jaxpr, jax_core.ClosedJaxpr))]
            inner = [j for j in inner if len(j.invars) == len(ins)
                     and len(j.outvars) == len(eqn.outvars)]
            if inner and name != "pallas_call":
                outs, calls = walk(inner[0], ins)
                if name == "scan":
                    # A carry that picks it up on the way goes round again.
                    lo = eqn.params["num_consts"]
                    carry = slice(lo, lo + eqn.params["num_carry"])
                    while any(o and not i for i, o in zip(ins[carry], outs)):
                        ins[carry] = [i or o for i, o in zip(ins[carry], outs)]
                        outs, calls = walk(inner[0], ins)
                found += calls
            else:
                outs = [any(ins)] * len(eqn.outvars)
            seen.update(
                v for v, out in zip(eqn.outvars, outs)
                if out and v.aval.dtype.kind in "iub")
        return [hit(v) for v in jaxpr.outvars], found

    flat = jaxpr.jaxpr
    return walk(flat, [i == source for i in range(len(flat.invars))])[1]


# SDAR-30B-A3B-Chat at the depth the benchmark's cell runs it (7 of 48
# layers), with about the cell's KV cache.
SDAR = dict(
    num_hidden_layers=7, hidden_size=2048, num_attention_heads=32,
    num_key_value_heads=4, head_dim=128, intermediate_size=6144,
    moe_intermediate_size=768, num_experts=128, num_experts_per_tok=8,
    norm_topk_prob=True, vocab_size=151936, rms_norm_eps=1e-6,
    tie_word_embeddings=False, max_position_embeddings=12288, rope_theta=1e6,
    rope_scaling=None, sliding_window=None, block_length=4,
    mask_token_id=151669,
)
SDAR_KV_BLOCKS, SDAR_PAGES = 20000, 64


@pytest.mark.parametrize("t,r", [(512, 128), (128, 32)],
                         ids=["mixed_T512_R128", "block_rows_T128_R32"])
def test_block_diffusion_step_compiles_and_reads_each_weight_in_place(
    one_chip, monkeypatch, t, r
):
    """The model's part of the step of ``sdar30b.chat``, compiled for the
    v5e: rows of 4 tokens under the mask over blocks (a 512-token mixed
    step, and a step of 32 block rows) through the ragged kernel and the
    grouped matmul, and PR 27's check on the layer scan. ``mixtral.py``
    writes the head split that folded into the projections in ``llama.py``;
    behind this family's per-head q/k norm XLA does not fold it (compiled
    with and without a barrier, PR 29: the same body), so the file has
    none. What the scan DID copy was a layer's three expert stacks, 1.2 GB,
    into temporaries for the grouped matmul: the kernel now takes the
    stacks whole (``layers/moe.py::fused_experts_of_layer``)."""
    from vllm_tpu.layers import moe
    from vllm_tpu.models.qwen3_moe import SDARMoeForCausalLM
    from vllm_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    # The grouped matmul is the one-chip path on a TPU; here JAX is held to
    # several CPU devices.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert moe.jax is jax
    model = SDARMoeForCausalLM(types.SimpleNamespace(**SDAR), dtype=jnp.bfloat16)
    assert model.generation == "block_diffusion"

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(model.init_dummy_params, jax.random.PRNGKey(0)),
    )
    md = attention.AttentionMetadata(
        positions=s((t,), jnp.int32), slot_mapping=s((t,), jnp.int32),
        block_tables=s((r, SDAR_PAGES), jnp.int32),
        seq_lens=s((r,), jnp.int32), query_start_loc=s((r + 1,), jnp.int32),
        token_req_idx=s((t,), jnp.int32), logits_indices=s((r,), jnp.int32),
        num_seqs=s((1,), jnp.int32), block_len=SDAR["block_length"],
    )
    kv = s(attention.kv_cache_shape(model.num_layers, SDAR_KV_BLOCKS, PAGE,
                                    model.num_kv_heads, model.head_dim),
           jnp.bfloat16)

    model.return_expert_counts = True  # as the runner sets it
    args = (params, kv, s((t,), jnp.int32), md)
    traced = jax.jit(model.apply, donate_argnums=(1,)).trace(*args)
    text = traced.lower().compile().as_text()

    assert "rpa_kernel" in text and "gmm" in text
    # No pad row is booked on a group: the three grouped matmuls of a layer
    # get ``group_sizes`` that are computed from the step's count of live
    # rows (through the live mask). The check can tell: with the mask taken
    # off the call they are the router's choices and nothing else.
    num_seqs = [x is md.num_seqs for x in jax.tree.leaves(args)].index(True)
    assert _gmm_group_sizes_come_from(traced.jaxpr, num_seqs) == [True] * 3
    grouped = moe._grouped_moe
    monkeypatch.setattr(
        moe, "_grouped_moe", lambda *a, live, **kw: grouped(*a, **kw))
    unmasked = jax.jit(model.apply).trace(*args).jaxpr
    assert _gmm_group_sizes_come_from(unmasked, num_seqs) == [False] * 3
    # No result in the layer scan's body has the shape of a layer's weight
    # (or of its transpose): each is read where it lies, the three expert
    # stacks by the grouped matmul itself, whole.
    shapes = {a.shape[1:] for a in jax.tree.leaves(params["layers"]) if a.ndim >= 3}
    shapes |= {sh[:-2] + sh[:-3:-1] for sh in shapes}
    # (but [T, hidden] is the step's own activations, whatever weight's
    # transpose it looks like)
    shapes -= {(t, model.hidden_size), (t * model.top_k, model.hidden_size)}
    moved = [
        (name, opcode, dims)
        for name, opcode, _, dims in _while_body_instructions(text)
        if dims in shapes and (opcode in ("copy", "transpose", "dynamic-slice", "fusion")
                               or "dynamic-slice" in name)
    ]
    assert not moved, f"the layer scan copies a layer's weight: {moved}"
    if (t, r) != (512, 128):
        return

    # The end of that step, the runner's own: the head over the request
    # bucket's 128 rows x 4 positions and the sampler. At a vocabulary of
    # 151936 the fused kernel takes them, the drawn token's probability
    # with them; before, the XLA epilogue did, a ``while`` of 31 bisection
    # passes over two [512, 155648] arrays in HBM.
    from vllm_tpu.sample.sampler import SamplingMetadata
    from vllm_tpu.worker import model_runner
    from vllm_tpu.worker.model_runner import ModelRunner

    b = SDAR["block_length"]
    runner = types.SimpleNamespace(
        model=model, tp_size=1, _nan_check=False, _guard_numerics=True)
    per_row = (jnp.float32, jnp.int32) + (jnp.float32,) * 5
    sampling = SamplingMetadata(
        *(s((r,), d) for d in per_row), prng_keys=s((r, 2), jnp.uint32),
        output_token_counts=s((1, 128), jnp.int32),
        prompt_token_mask=s((1, 128), jnp.bool_),
    )
    blk = (s((r,), jnp.int32),) * 3 + (s((r, b), jnp.int32), s((r,), jnp.float32))

    def epilogue(enable_sampler_kernel, num_logprobs=0):
        def fn(params, hidden, sampling, blk, flat_idx, blk_tok, blk_com, live):
            out = ModelRunner._block_epilogue(
                runner, params, None, None, hidden, sampling, blk, flat_idx,
                blk_tok, blk_com, None, block_len=b, r_pad=r,
                needs_top_k=False, needs_top_p_min_p=True, needs_gumbel=True,
                num_logprobs=num_logprobs,
                enable_sampler_kernel=enable_sampler_kernel, num_live=live,
            )
            return out[2], out[3], out[9]

        return jax.jit(fn).lower(
            params, s((t, model.hidden_size), jnp.bfloat16), sampling, blk,
            s((r, b), jnp.int32), s((r, b), jnp.int32), s((r, b), jnp.bool_),
            s((), jnp.int32),
        ).compile()

    wide = f"[{r * b},155648]"
    compiled = epilogue(True)
    text = compiled.as_text()
    assert "fused_sampler_kernel" in text
    assert wide not in text and "while(" not in text
    # The head runs over the least rung that holds the live rows, picked
    # in the program: ONE conditional of three branches, each with the
    # head's product at its own rows (and XLA did not turn the switch into
    # selects, which would run all three).
    assert model_runner.head_rungs(r) == [32, 64, 128]
    conditionals = re.findall(r"conditional\(.*branch_computations=\{([^}]*)\}", text)
    assert len(conditionals) == 1 and conditionals[0].count(",") == 2, conditionals
    heads = sorted(
        int(rows) for rows in re.findall(
            rf"\[(\d+),{model.vocab_size}\]\S* convolution\(", text))
    assert heads == [128, 256, 512], heads
    # And it holds what the head over every position held, the float32
    # logits of 512 positions (311 MB): a conditional's temporaries are
    # its largest branch's. (Within a MiB: the compiler keeps a few of the
    # sampler's [rows, 128] parameter blocks in another memory space, and
    # reads 0.45 MB more, here and against the parent's own epilogue.)
    with monkeypatch.context() as one_rung:
        one_rung.setattr(model_runner, "head_rungs", lambda rows: [rows])
        whole = epilogue(True)
    assert "conditional(" not in whole.as_text()
    logits_bytes = 4 * r * b * model.vocab_size
    assert (logits_bytes < compiled.memory_analysis().temp_size_in_bytes
            <= whole.memory_analysis().temp_size_in_bytes + (1 << 20))
    # The program of a step with logprobs (the benchmark's check requests
    # ask for 5) takes the same slabs: top-k and rank are a row's own.
    text = epilogue(True, num_logprobs=5).as_text()
    assert "conditional(" in text and wide not in text
    # The check can tell: with the kernel turned off the loop is there.
    text = epilogue(False).as_text()
    assert "fused_sampler_kernel" not in text
    assert wide in text and "while(" in text


# Kimi-Linear-48B-A3B-Instruct as ``kimilinear48b.long-answers`` runs it:
# the cell's two step programs, 192 rows of 768 pages, 193 state slots,
# about the cell's latent pages.
KIMI_CONFIG = "benchmark/configs/kimi-linear-48b-a3b-instruct.json"
KIMI_ROWS, KIMI_PAGES, KIMI_BLOCKS = 192, 768, 32264
# One layer of ``periods/moe/ws_gate`` [2304, 1024] in bf16: the smallest
# of the dense weights that the scan copied a slab of (PR 40).
KIMI_WEIGHT_BYTES = 4_718_592
# What the compiler still takes out of its stack a latent layer: ``wkv_b``
# [512, 8192] bf16, which two einsums absorb through a reshape and a
# split (a slice, and a copy that relays it): 0.1 ms a step.
KIMI_WKV_B = ((512, 8192), 8_388_608)


@pytest.mark.parametrize("t,decode_only", [(192, True), (512, False)],
                         ids=["decode_T192_R192", "mixed_T512_R192"])
def test_kimi_linear_scan_reads_each_weight_once_in_place(
    one_chip, monkeypatch, t, decode_only
):
    """Both step programs of ``kimilinear48b.long-answers``, compiled for
    the v5e with the Pallas forms on: no iteration of the period scan may
    slice a WEIGHT out of its ``[P, n, ...]`` stack into a temporary, nor
    copy or transpose one. PR 40 found 21 such slices an iteration, 617 MB
    (``dynamic-slice_bitcast_fusion`` and its ``.remat3`` twins, 3.7 ms of
    the chip's 23 ms step): the scan handed an iteration the period's
    leaves as ``[n, ...]`` slabs that n layers indexed, and a slice with n
    consumers folds into none of them. Each layer now takes ``leaf[period, place]``
    from the stacks themselves, and the latent query projection ends at a
    barrier as ``models/llama.py``'s do.

    The limit is the smallest of those weights and not lower: at 512
    tokens the activations' relayouts are copies of 4-8 MB, which is why
    a ``copy`` counts only with a weight's dims."""
    import json
    import pathlib

    from transformers import PretrainedConfig

    from vllm_tpu.models.kimi_linear import KimiLinearForCausalLM
    from vllm_tpu.ops import attention
    from vllm_tpu.ops.mla_attention import mla_kv_cache_shape

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    # ops/kda.py, ops/mla_attention.py and layers/moe.py ask the backend;
    # here JAX is held to several CPU devices.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    root = pathlib.Path(__file__).resolve().parents[1]
    config = json.loads((root / KIMI_CONFIG).read_text())
    config.pop("bench")
    model = KimiLinearForCausalLM(PretrainedConfig.from_dict(config), dtype=jnp.bfloat16)
    model.max_state_slots = KIMI_ROWS
    assert (model.period, model.reps) == (4, 3)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    on_chip = lambda tree: jax.tree.map(lambda a: s(a.shape, a.dtype), tree)
    params = on_chip(jax.eval_shape(model.init_dummy_params, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: model.alloc_kv_cache(KIMI_BLOCKS, PAGE, jnp.bfloat16)))
    assert cache["paged"].shape == mla_kv_cache_shape(3, KIMI_BLOCKS, PAGE, 576)
    assert cache["kda"].shape[:2] == (10, KIMI_ROWS + 1)
    r = KIMI_ROWS
    md = attention.AttentionMetadata(
        positions=s((t,), jnp.int32), slot_mapping=s((t,), jnp.int32),
        block_tables=s((r, KIMI_PAGES), jnp.int32),
        seq_lens=s((r,), jnp.int32), query_start_loc=s((r + 1,), jnp.int32),
        token_req_idx=s((t,), jnp.int32), logits_indices=s((r,), jnp.int32),
        num_seqs=s((1,), jnp.int32), decode_only=decode_only,
        state_slots=s((r,), jnp.int32),
    )
    text = jax.jit(model.apply, donate_argnums=(1,)).lower(
        params, cache, s((t,), jnp.int32), md
    ).compile().as_text()

    for kernel in ("kda_decode_kernel", "kda_conv_kernel", "mla_kernel", "gmm"):
        assert kernel in text
    assert ("kda_chunk_kernel" in text) == (not decode_only)
    # A weight's dims: one layer of a leaf, the slab of a period's n
    # layers of it, and either transposed (axes of 1 apart: XLA keeps the
    # sliced ones).
    squeezed = lambda dims: tuple(d for d in dims if d != 1)
    shapes = {squeezed(a.shape[k:]) for a in jax.tree.leaves(params["periods"])
              for k in (1, 2) if a.ndim >= 4}
    shapes |= {sh[:-2] + sh[:-3:-1] for sh in shapes}
    moved = [
        (name, opcode, dims, nbytes)
        for name, opcode, nbytes, dims in _while_body_instructions(text)
        if nbytes >= KIMI_WEIGHT_BYTES
        and ("dynamic-slice" in name or opcode == "dynamic-slice"
             or (opcode in ("copy", "transpose") and squeezed(dims) in shapes))
        and (squeezed(dims), nbytes) != KIMI_WKV_B
    ]
    assert not moved, (
        f"the period scan moves weight-sized temporaries "
        f"(>= {KIMI_WEIGHT_BYTES} bytes): {moved}"
    )


# Laguna-XS.2 (``lagunaxs2.code-mixed``): window layers of 64 query heads
# and full layers of 48 over 8 KV heads of 128, window 512, the cell's two
# step shapes (512 tokens x 64 rows mixed, 64 rows decode-only) at the whole
# page table of 12,288 tokens. A group of 6 query heads a KV head is new to
# the tilings.
LAGUNA_CONFIG = "benchmark/configs/laguna-xs.2.json"
LAGUNA_ROWS, LAGUNA_PAGES, LAGUNA_BLOCKS = 64, 768, 4096
LAGUNA_GROUP_LAYERS = 10


def _laguna_attn_args(sharding, t, r, q_heads, ragged):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    args = [
        s((t, q_heads, D), jnp.bfloat16),
        s((LAGUNA_GROUP_LAYERS, LAGUNA_BLOCKS, PAGE, 2 * 8, D), jnp.bfloat16),
        s((1,), jnp.int32), s((r,), jnp.int32), s((r, LAGUNA_PAGES), jnp.int32),
    ]
    if ragged:
        args.append(s((r + 1,), jnp.int32))
    return args + [s((1,), jnp.int32)]


@pytest.mark.parametrize("window", [512, 0], ids=["window512", "full"])
@pytest.mark.parametrize("q_heads", [48, 64])
def test_laguna_ragged_kernel_compiles(one_chip, q_heads, window):
    """The mixed step's kernel at both kinds' head counts, with the window
    as the traced scalar the layers hand it and with none, under its own
    name in the trace. 6 query heads a KV head: 80 query tokens a block
    (480 rows of the 512 a KV head's wide tile holds, the most that are
    whole sublane tiles), and the narrow rung of 8 tokens is kept for the
    decode rows of a mixed step."""
    from vllm_tpu.ops.rpa_kernel import ragged_paged_attention, ragged_tiling

    tiling = ragged_tiling(q_heads, 16, D, PAGE, jnp.bfloat16, jnp.bfloat16,
                           512, LAGUNA_PAGES)
    assert tiling.q_per_blk == {48: 80, 64: 64}[q_heads]
    assert tiling.narrow_q == {48: 8, 64: 2}[q_heads]
    assert tiling.kv_pages_per_blk == 32
    assert (tiling.vmem_limit_bytes or 0) <= 64 << 20
    name = "rpa_kernel_window" if window else "rpa_kernel"
    fn = functools.partial(ragged_paged_attention, sm_scale=D**-0.5, name=name)
    text = jax.jit(lambda *a: fn(*a, sliding_window=jnp.int32(window))).lower(
        *_laguna_attn_args(one_chip, 512, LAGUNA_ROWS, q_heads, True)
    ).compile().as_text()
    assert f'"{name}"' in text or name in text


def _holds_both_kinds_kernels(text, decode_only):
    """A window layer's call is the ragged kernel's in both step programs;
    a full layer's is the decode kernel's in the decode-only one."""
    base = "rpa_decode_kernel" if decode_only else "rpa_kernel"
    assert "rpa_kernel_window" in text and re.search(base + r'(?!_window)\b', text)
    assert "rpa_decode_kernel_window" not in text


def _decode_only_call(args, window, sm_scale, sinks=None, **kw):
    """(jitted call, its arguments, name) of one layer's attention in the
    decode-only step as the dispatcher lowers it: the decode kernel where
    the layer states no window, the ragged kernel with the window as a
    scalar where it states one (the decode kernel has none). ``args`` are
    the kernel's shapes, with the row offsets where there is a window."""
    from vllm_tpu.ops.rpa_decode_kernel import decode_paged_attention
    from vllm_tpu.ops.rpa_kernel import ragged_paged_attention

    if window:
        name = "rpa_kernel_window"
        fn = functools.partial(ragged_paged_attention, sm_scale=sm_scale, name=name, **kw)
        call = lambda sinks, *a: fn(*a, sliding_window=jnp.int32(window), sinks=sinks)
    else:
        name = "rpa_decode_kernel"
        fn = functools.partial(decode_paged_attention, sm_scale=sm_scale, name=name, **kw)
        call = lambda sinks, *a: fn(*a, sinks=sinks)
    return jax.jit(call), (sinks, *args), name


def _laguna_decode_only_call(sharding, q_heads, window, sm_scale=D**-0.5):
    return _decode_only_call(
        _laguna_attn_args(sharding, LAGUNA_ROWS, LAGUNA_ROWS, q_heads, bool(window)),
        window, sm_scale)


@pytest.mark.parametrize("window", [512, 0], ids=["window512", "full"])
@pytest.mark.parametrize("q_heads", [48, 64])
def test_laguna_decode_only_attention_compiles(one_chip, q_heads, window):
    fn, args, name = _laguna_decode_only_call(one_chip, q_heads, window)
    assert name in fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("t,decode_only", [(64, True), (512, False)],
                         ids=["decode_T64_R64", "mixed_T512_R64"])
def test_laguna_step_compiles_and_reads_each_weight_in_place(
    one_chip, monkeypatch, t, decode_only
):
    """Both step programs of ``lagunaxs2.code-mixed`` over ONE period less
    than the whole (layer 0, one scanned period... the file's 40 layers cut
    to 12: layer 0, two periods, the tail) at the published widths, with a
    block table and a slot mapping a KV group: the two kinds' kernels under
    their two names, the grouped matmul, and no iteration of the period
    scan slicing a weight out of its stack (PR 40's finding)."""
    import json
    import pathlib

    from transformers import PretrainedConfig

    from vllm_tpu.models.laguna import LagunaForCausalLM
    from vllm_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    root = pathlib.Path(__file__).resolve().parents[1]
    config = json.loads((root / LAGUNA_CONFIG).read_text())
    config.pop("bench")
    for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        config[key] = config[key][:12]
    config["num_hidden_layers"] = 12
    from vllm_tpu.layers.rotary import serving_positions

    with serving_positions(LAGUNA_PAGES * PAGE):  # as the worker does
        model = LagunaForCausalLM(PretrainedConfig.from_dict(config), dtype=jnp.bfloat16)
    assert (model.periods, model.tail, model.num_kv_groups) == (2, 3, 4)
    assert model.heads_of == {"full_attention": 48, "sliding_attention": 64}

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(model.init_dummy_params, jax.random.PRNGKey(0)))
    cache = s((model.kv_group_layers, LAGUNA_BLOCKS, PAGE, 16, D), jnp.bfloat16)
    r, g = LAGUNA_ROWS, model.num_kv_groups
    md = attention.AttentionMetadata(
        positions=s((t,), jnp.int32), slot_mapping=s((g, t), jnp.int32),
        block_tables=s((g, r, LAGUNA_PAGES), jnp.int32),
        seq_lens=s((r,), jnp.int32), query_start_loc=s((r + 1,), jnp.int32),
        token_req_idx=s((t,), jnp.int32), logits_indices=s((r,), jnp.int32),
        num_seqs=s((1,), jnp.int32), decode_only=decode_only,
    )
    text = jax.jit(model.apply, donate_argnums=(1,)).lower(
        params, cache, s((t,), jnp.int32), md
    ).compile().as_text()

    _holds_both_kinds_kernels(text, decode_only)
    assert "gmm" in text
    squeezed = lambda dims: tuple(d for d in dims if d != 1)
    shapes = {squeezed(a.shape[k:]) for a in jax.tree.leaves(params["periods"])
              for k in (1, 2) if a.ndim >= 3}
    shapes |= {sh[:-2] + sh[:-3:-1] for sh in shapes if len(sh) >= 2}
    smallest = 2048 * 512 * 2  # a shared expert's matrix
    moved = [
        (name, opcode, dims, nbytes)
        for name, opcode, nbytes, dims in _while_body_instructions(text)
        if nbytes >= smallest
        and ("dynamic-slice" in name or opcode == "dynamic-slice"
             or (opcode in ("copy", "transpose") and squeezed(dims) in shapes))
    ]
    assert not moved, (
        f"the period scan moves weight-sized temporaries (>= {smallest} bytes): {moved}")


# MiMo-V2.5 (``mimov25.long-answers``): keys of 192 lanes (stored as 256)
# beside values of 128 in one head-major page row, 64 query heads over 8 KV
# heads of 16 tokens a page in a window layer (window 128, a sink logit a
# head) and over 4 KV heads of 32 tokens a page in a full layer, ONE cache
# array for both; the cell's two step shapes at the whole page table of
# 12,288 tokens.
MIMO_CONFIG = "benchmark/configs/mimo-v2.5.json"
MIMO_ROWS, MIMO_PAGES, MIMO_BLOCKS = 128, 768, 8192
MIMO_KINDS = {"window": (8, 16, 128, True), "full": (4, 32, 0, False)}


def _mimo_attn_args(sharding, t, r, kind, ragged):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    kh, bs, _, sink = MIMO_KINDS[kind]
    args = [
        s((t, 64, 256), jnp.bfloat16),
        s((2, MIMO_BLOCKS, kh, bs, 384), jnp.bfloat16),
        s((1,), jnp.int32), s((r,), jnp.int32), s((r, MIMO_PAGES), jnp.int32),
    ]
    if ragged:
        args.append(s((r + 1,), jnp.int32))
    args.append(s((1,), jnp.int32))
    return args, (s((64,), jnp.float32) if sink else None)


@pytest.mark.parametrize("kind", list(MIMO_KINDS))
def test_mimo_ragged_kernel_compiles_on_the_split_page(one_chip, kind):
    """The mixed step's kernel over ``[L, NB, KH, BS, 256 + 128]`` pages
    with a query padded to 256 lanes and an output of 128, at 8 and at 16
    query heads a KV head; the window kind with its sinks as an operand."""
    from vllm_tpu.ops.rpa_kernel import ragged_paged_attention

    kh, bs, window, _ = MIMO_KINDS[kind]
    name = "rpa_kernel_window" if window else "rpa_kernel"
    args, sinks = _mimo_attn_args(one_chip, 512, MIMO_ROWS, kind, True)
    fn = functools.partial(ragged_paged_attention, sm_scale=192**-0.5, name=name,
                           head_major=True)
    lowered = jax.jit(lambda sinks, *a: fn(
        *a, sliding_window=jnp.int32(window), sinks=sinks)).lower(sinks, *args)
    assert lowered.out_info.shape == (512, 64, 128)
    assert name in lowered.compile().as_text()


def _mimo_decode_only_call(sharding, kind, sm_scale=192**-0.5):
    window = MIMO_KINDS[kind][2]
    args, sinks = _mimo_attn_args(sharding, MIMO_ROWS, MIMO_ROWS, kind, bool(window))
    return _decode_only_call(args, window, sm_scale, sinks, head_major=True)


@pytest.mark.parametrize("kind", list(MIMO_KINDS))
def test_mimo_decode_only_attention_compiles_on_the_split_page(one_chip, kind):
    fn, args, name = _mimo_decode_only_call(one_chip, kind)
    lowered = fn.lower(*args)
    assert lowered.out_info.shape == (MIMO_ROWS, 64, 128)
    assert name in lowered.compile().as_text()


@pytest.mark.parametrize("model", ["laguna", "mimo"])
def test_tracing_a_window_layers_decode_only_call_once_is_cheap(one_chip, model):
    """``test_tracing_the_ragged_kernel_once_is_cheap``'s bound at the two
    decode-only step programs that hold ``rpa_kernel_window`` since PR 47
    (``lagunaxs2.code-mixed`` T = R = 64, ``mimov25.long-answers`` T = R =
    128 on the split page with sinks): a window layer's call is traced and
    lowered once a run of layers a program, in Python, and that is
    ``setup_first_use_s``."""
    import time

    def call(sm_scale):
        if model == "laguna":
            return _laguna_decode_only_call(one_chip, 64, 512, sm_scale)[:2]
        return _mimo_decode_only_call(one_chip, "window", sm_scale)[:2]

    fn, args = call(0.5)
    fn.lower(*args)  # imports and caches that are not the kernel's
    fn, args = call(0.25)
    t0 = time.process_time()
    fn.lower(*args)
    assert time.process_time() - t0 < 4.0


@pytest.mark.parametrize("t,decode_only", [(MIMO_ROWS, True), (512, False)],
                         ids=["decode_T128_R128", "mixed_T512_R128"])
def test_mimo_step_compiles_and_reads_each_weight_in_place(
    one_chip, monkeypatch, t, decode_only
):
    """Both step programs of ``mimov25.long-answers`` at the file's own
    widths and depth (F W W W W F W W W W: two layers in line and two
    scans over runs of four window layers), with a block table and a slot
    mapping a KV group over ONE cache array ``[2, pages, 128, 384]``: the
    two kinds' kernels under their two names, the grouped matmul, and no
    iteration of a run's scan slicing a weight out of its stack."""
    import json
    import pathlib

    from transformers import PretrainedConfig

    from vllm_tpu.layers.rotary import serving_positions
    from vllm_tpu.models.mimo_v2 import MiMoV2ForCausalLM
    from vllm_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    root = pathlib.Path(__file__).resolve().parents[1]
    config = json.loads((root / MIMO_CONFIG).read_text())
    config.pop("bench")
    with serving_positions(MIMO_PAGES * 16):  # as the worker does
        model = MiMoV2ForCausalLM(PretrainedConfig.from_dict(config), dtype=jnp.bfloat16)
    assert model.runs == [(0, 1), (1, 4), (5, 1), (6, 4)]
    assert (model.num_kv_groups, model.kv_group_places) == (5, 2)
    assert model.kv_cache_shape(MIMO_BLOCKS, 16) == (2, MIMO_BLOCKS, 128, 384)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(model.init_dummy_params, jax.random.PRNGKey(0)))
    cache = s(model.kv_cache_shape(MIMO_BLOCKS, 16), jnp.bfloat16)
    r, g = MIMO_ROWS, model.num_kv_groups
    md = attention.AttentionMetadata(
        positions=s((t,), jnp.int32), slot_mapping=s((g, t), jnp.int32),
        block_tables=s((g, r, MIMO_PAGES), jnp.int32),
        seq_lens=s((r,), jnp.int32), query_start_loc=s((r + 1,), jnp.int32),
        token_req_idx=s((t,), jnp.int32), logits_indices=s((r,), jnp.int32),
        num_seqs=s((1,), jnp.int32), decode_only=decode_only,
    )
    text = jax.jit(model.apply, donate_argnums=(1,)).lower(
        params, cache, s((t,), jnp.int32), md
    ).compile().as_text()

    _holds_both_kinds_kernels(text, decode_only)
    assert "gmm" in text
    squeezed = lambda dims: tuple(d for d in dims if d != 1)
    shapes = {squeezed(a.shape[1:]) for name in ("window", "moe", "experts")
              for a in jax.tree.leaves(params[name]) if a.ndim >= 3}
    shapes |= {sh[:-2] + sh[:-3:-1] for sh in shapes if len(sh) >= 2}
    smallest = 4096 * 256 * 2  # a router's matrix
    moved = [
        (name, opcode, dims, nbytes)
        for name, opcode, nbytes, dims in _while_body_instructions(text)
        if nbytes >= smallest
        and ("dynamic-slice" in name or opcode == "dynamic-slice"
             or (opcode in ("copy", "transpose") and squeezed(dims) in shapes))
    ]
    assert not moved, (
        f"a run's scan moves weight-sized temporaries (>= {smallest} bytes): {moved}")


# Falcon-H1-34B-Instruct (``falconh1-34b.long-answers``): in EVERY layer a
# Mamba-2 mixer (32 heads of 128 over a state of 256, 2 groups: 4 MiB of
# float32 a row a layer, in slots; a convolution over ONE stream of 5120
# channels with a bias) beside rotary GQA attention (20 query heads over 4
# KV heads of 128: 5 a KV head, new to both attention kernels), a vocabulary
# of 261,120 (64 noise tiles, exactly the fused sampler's widest row); 6
# layers, the cell's two step shapes at the whole page table of 12,288.
FALCON_CONFIG = "benchmark/configs/falcon-h1-34b-instruct.json"
FALCON_CELL = "benchmark/cells/falconh1-34b.long-answers.json"
FALCON_PAGES, FALCON_BLOCKS, FALCON_LAYERS = 768, 8192, 6
SSD_H, SSD_P, SSD_G, SSD_N, SSD_C = 32, 128, 2, 256, 5120


def _falcon_rows():
    import json
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]
    return int(json.loads((root / FALCON_CELL).read_text())["engine_args"]["max_num_seqs"])


def test_ssd_one_token_kernel_compiles_and_updates_the_slots_in_place(one_chip):
    """``ssd_decode_kernel`` at the cell's widths and rows: 8 heads of a
    row's state a grid step (1 MiB in, 1 MiB out, double-buffered, inside
    the scoped VMEM it asks for), the state stack of every slot of 6 layers
    aliased to the output, nothing of the state's size beside it."""
    from vllm_tpu.ops import mamba

    r = _falcon_rows()

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = s((FALCON_LAYERS, r + 1, SSD_H, SSD_P, SSD_N))

    def fn(x, dt, ln_a, b, c, state, slots, fresh, n):
        return mamba._ssd_decode_pallas(x, dt, ln_a, b, c, state, jnp.int32(3), slots, fresh, n)

    compiled = jax.jit(fn, donate_argnums=(5,)).lower(
        s((r, SSD_H, SSD_P)), s((r, SSD_H)), s((r, SSD_H)), s((r, SSD_G, SSD_N)),
        s((r, SSD_G, SSD_N)), state, s((r,), jnp.int32), s((r,), jnp.bool_),
        s((), jnp.int32)).compile()
    assert "ssd_decode_kernel" in compiled.as_text()
    mem = compiled.memory_analysis()
    state_bytes = math.prod(state.shape) * 4
    assert mem.alias_size_in_bytes >= state_bytes and mem.temp_size_in_bytes < 64 << 20


def test_ssd_chunked_form_compiles_with_the_state_in_place(one_chip):
    """The XLA form of the chunks at a 512-token step: the state stack is
    carried through the rounds' ``while_loop`` and the places' scan without
    a copy; its temporaries are the chunks' operands, not the slots."""
    from vllm_tpu.ops import mamba

    r, t = _falcon_rows(), 512

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = s((FALCON_LAYERS, r + 1, SSD_H, SSD_P, SSD_N))

    def fn(x, dt, ln_a, b, c, state, slots, fresh, qsl, n):
        return mamba.ssd_chunks(x, dt, ln_a, b, c, state, jnp.int32(3), slots, fresh, qsl, n)

    mem = jax.jit(fn, donate_argnums=(5,)).lower(
        s((t, SSD_H, SSD_P)), s((t, SSD_H)), s((t, SSD_H)), s((t, SSD_G, SSD_N)),
        s((t, SSD_G, SSD_N)), state, s((r,), jnp.int32), s((r,), jnp.bool_),
        s((r + 1,), jnp.int32), s((), jnp.int32)).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= math.prod(state.shape) * 4
    assert mem.temp_size_in_bytes < 512 << 20


@pytest.mark.parametrize("t,decode_only", [(512, False), (0, True)],
                         ids=["mixed_512_tokens", "decode_only"])
def test_the_shared_convolution_compiles_at_one_stream_of_5120_channels_with_a_bias(
        one_chip, monkeypatch, t, decode_only):
    """``kda_conv_kernel`` as the state-space mixer calls it: one stream of
    40 whole lane tiles (a count 32 does not divide: the heads in hand at
    once are all 40), a bias as a fifth row of the taps, the tails ``[6,
    rows + 1, 3, 40, 128]`` in bf16 through in place, nothing gathered,
    scattered or looped over around it."""
    from vllm_tpu.ops import kda

    monkeypatch.setattr(kda, "_pallas_form", lambda state=None: (True, False))
    r = _falcon_rows()
    t = t or r

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    stack = s((FALCON_LAYERS, r + 1, 3, SSD_C // 128, 128), jnp.bfloat16)

    def fn(x, stack, taps, bias, slots, fresh, tok_req, qsl, n):
        return kda.kda_short_conv((x,), stack, (taps,), jnp.int32(3), slots, fresh, tok_req,
                                  qsl, n, decode_only=decode_only, bias=bias)

    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        s((t, SSD_C), jnp.bfloat16), stack, s((4, SSD_C), jnp.bfloat16),
        s((SSD_C,), jnp.bfloat16), s((r,), jnp.int32), s((r,), jnp.bool_),
        s((t,), jnp.int32), s((r + 1,), jnp.int32), s((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "kda_conv_kernel" in text
    assert not any(op in text for op in (" gather(", " scatter(", " while("))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= math.prod(stack.shape) * 2
    assert mem.temp_size_in_bytes < 64 << 20


def test_sampler_kernel_compiles_at_its_widest_row(one_chip):
    """A vocabulary of 261,120 rounds up to 64 noise tiles, 262,144 lanes:
    exactly ``MAX_KERNEL_WIDTH``, the widest row the fused sampler takes by
    its own estimate. The cell's request bucket, top-p, the probability
    returned, the live rows counted."""
    from vllm_tpu.ops import sampler_kernel as sk

    assert sk.compute_width(261120) == 262144 == sk.MAX_KERNEL_WIDTH
    r, v = _falcon_rows(), 261120

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for return_prob in (False, True):
        fn = functools.partial(
            sk.fused_sample, needs_penalties=False, needs_top_k=False,
            needs_top_p_min_p=True, return_prob=return_prob)
        jax.jit(fn).lower(
            s((r, v), jnp.float32), s((r, 128), jnp.float32), s((r, 128), jnp.int32),
            s((1, 128), jnp.int32), s((1, 128), jnp.int8), s((), jnp.int32)).compile()


def _falcon_attn_args(sharding, t, r, ragged):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    args = [
        s((t, 20, D), jnp.bfloat16),
        s((FALCON_LAYERS, FALCON_BLOCKS, PAGE, 2 * 4, D), jnp.bfloat16),
        s((1,), jnp.int32), s((r,), jnp.int32), s((r, FALCON_PAGES), jnp.int32),
    ]
    if ragged:
        args.append(s((r + 1,), jnp.int32))
    return args + [s((1,), jnp.int32)]


@pytest.mark.parametrize("kernel", ["ragged", "decode"])
def test_both_attention_kernels_compile_at_five_query_heads_a_kv_head(one_chip, kernel):
    """20 query heads over 4 KV heads of 128 (every accepted cell has 4, 6,
    8 or 16 a KV head), at the cell's rows and the whole page table."""
    from vllm_tpu.ops.rpa_decode_kernel import decode_paged_attention
    from vllm_tpu.ops.rpa_kernel import ragged_paged_attention, ragged_tiling

    r = _falcon_rows()
    if kernel == "ragged":
        tiling = ragged_tiling(20, 8, D, PAGE, jnp.bfloat16, jnp.bfloat16, 512, FALCON_PAGES)
        assert tiling.q_per_blk * 5 <= 512 and (tiling.vmem_limit_bytes or 0) <= 64 << 20
        fn = functools.partial(ragged_paged_attention, sm_scale=D**-0.5)
        args = _falcon_attn_args(one_chip, 512, r, True)
    else:
        fn = functools.partial(decode_paged_attention, sm_scale=D**-0.5)
        args = _falcon_attn_args(one_chip, r, r, False) + [
            jax.ShapeDtypeStruct((r,), jnp.int32, sharding=one_chip)]
    jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("decode_only", [True, False], ids=["decode_only", "mixed_T512"])
def test_falcon_h1_step_compiles_and_reads_each_weight_in_place(
        one_chip, monkeypatch, decode_only):
    """Both step programs of ``falconh1-34b.long-answers`` at the published
    widths, 6 layers, compiled for the v5e with the Pallas forms on: the
    one-token kernel of the rule, the shared convolution, the attention
    kernel of the step's kind; and no iteration of the layer scan slices,
    copies or transposes a WEIGHT out of its ``[L, ...]`` stack."""
    import json
    import pathlib

    from transformers import PretrainedConfig

    from vllm_tpu.layers.rotary import serving_positions
    from vllm_tpu.models.falcon_h1 import FalconH1ForCausalLM
    from vllm_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    root = pathlib.Path(__file__).resolve().parents[1]
    config = json.loads((root / FALCON_CONFIG).read_text())
    config.pop("bench")
    with serving_positions(FALCON_PAGES * PAGE):  # as the worker does
        model = FalconH1ForCausalLM(PretrainedConfig.from_dict(config), dtype=jnp.bfloat16)
    r = _falcon_rows()
    model.max_state_slots = r
    t = r if decode_only else 512
    assert (model.num_layers, model.conv_dim, model.in_dim) == (FALCON_LAYERS, SSD_C, 9248)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    on_chip = lambda tree: jax.tree.map(lambda a: s(a.shape, a.dtype), tree)
    params = on_chip(jax.eval_shape(model.init_dummy_params, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: model.alloc_kv_cache(FALCON_BLOCKS, PAGE, jnp.bfloat16)))
    assert cache["ssm"].shape == (FALCON_LAYERS, r + 1, SSD_H, SSD_P, SSD_N)
    assert cache["conv"].shape == (FALCON_LAYERS, r + 1, 3, 40, 128)
    assert cache["paged"].shape == (FALCON_LAYERS, FALCON_BLOCKS, PAGE, 8, D)
    md = attention.AttentionMetadata(
        positions=s((t,), jnp.int32), slot_mapping=s((t,), jnp.int32),
        block_tables=s((r, FALCON_PAGES), jnp.int32),
        seq_lens=s((r,), jnp.int32), query_start_loc=s((r + 1,), jnp.int32),
        token_req_idx=s((t,), jnp.int32), logits_indices=s((r,), jnp.int32),
        num_seqs=s((1,), jnp.int32), decode_only=decode_only,
        state_slots=s((r,), jnp.int32),
    )
    compiled = jax.jit(model.apply, donate_argnums=(1,)).lower(
        params, cache, s((t,), jnp.int32), md).compile()
    text = compiled.as_text()
    for kernel in ("ssd_decode_kernel", "kda_conv_kernel",
                   "rpa_decode_kernel" if decode_only else "rpa_kernel"):
        assert kernel in text
    # The slots and the pages go through in place.
    held = sum(math.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes >= held
    squeezed = lambda dims: tuple(d for d in dims if d != 1)
    shapes = {squeezed(a.shape[1:]) for a in jax.tree.leaves(params["layers"]) if a.ndim >= 3}
    shapes |= {sh[::-1] for sh in shapes}
    smallest = 5120 * 512 * 2  # wk, wv
    moved = [
        (name, opcode, dims, nbytes)
        for name, opcode, nbytes, dims in _while_body_instructions(text)
        if nbytes >= smallest
        and ("dynamic-slice" in name or opcode == "dynamic-slice"
             or (opcode in ("copy", "transpose") and squeezed(dims) in shapes))
    ]
    assert not moved, (
        f"the layer scan moves weight-sized temporaries (>= {smallest} bytes): {moved}")


# LFM2-8B-A1B (``lfm2-8b-a1b.chat``): a gated short convolution of THREE
# taps over one stream of 2048 channels whose two-token tail is all the
# state a request has (slots ``[12, rows + 1, 2, 16, 128]`` in bf16), GQA
# of 32 query heads over 8 KV heads of 64 lanes in the PACKED page layout
# (``[4, blocks, 16, 8, 128]``, k||v in one row: new to the chip in both
# attention kernels), 32 experts of 2048 x 1792 all held; layers 0-15, the
# cell's two step shapes at the whole page table of 12,288.
LFM2_CONFIG = "benchmark/configs/lfm2-8b-a1b.json"
LFM2_CELL = "benchmark/cells/lfm2-8b-a1b.chat.json"
LFM2_PAGES, LFM2_BLOCKS, LFM2_CONV_LAYERS, LFM2_ATTN_LAYERS = 768, 16384, 12, 4
LFM2_D, LFM2_HEADS, LFM2_KV_HEADS, LFM2_HEAD = 2048, 32, 8, 64


def _lfm2_rows():
    import json
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]
    return int(json.loads((root / LFM2_CELL).read_text())["engine_args"]["max_num_seqs"])


@pytest.mark.parametrize("t,decode_only", [(512, False), (0, True)],
                         ids=["mixed_512_tokens", "decode_only"])
def test_the_shared_convolution_compiles_at_three_taps_and_a_two_row_tail(
        one_chip, monkeypatch, t, decode_only):
    """``kda_conv_kernel`` as the LFM2 mixer calls it, its first caller at K
    = 3: one stream of 16 whole lane tiles, no bias, a slot of ``[2, 16,
    128]`` bf16 (two whole tiles: the copy of a two-row tail is what Mosaic
    had not been asked before), the stack through in place, nothing
    gathered, scattered or looped over around it."""
    from vllm_tpu.ops import kda

    monkeypatch.setattr(kda, "_pallas_form", lambda state=None: (True, False))
    r = _lfm2_rows()
    t = t or r

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    stack = s((LFM2_CONV_LAYERS, r + 1, 2, LFM2_D // 128, 128), jnp.bfloat16)

    def fn(x, stack, taps, slots, fresh, tok_req, qsl, n):
        return kda.kda_short_conv((x,), stack, (taps,), jnp.int32(7), slots, fresh, tok_req,
                                  qsl, n, decode_only=decode_only)

    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        s((t, LFM2_D), jnp.bfloat16), stack, s((3, LFM2_D), jnp.bfloat16),
        s((r,), jnp.int32), s((r,), jnp.bool_), s((t,), jnp.int32), s((r + 1,), jnp.int32),
        s((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "kda_conv_kernel" in text
    assert not any(op in text for op in (" gather(", " scatter(", " while("))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= math.prod(stack.shape) * 2
    assert mem.temp_size_in_bytes < 64 << 20


def _lfm2_attn_args(sharding, t, r, ragged):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    args = [
        s((t, LFM2_HEADS, LFM2_HEAD), jnp.bfloat16),
        s((LFM2_ATTN_LAYERS, LFM2_BLOCKS, PAGE, LFM2_KV_HEADS, 2 * LFM2_HEAD), jnp.bfloat16),
        s((1,), jnp.int32), s((r,), jnp.int32), s((r, LFM2_PAGES), jnp.int32),
    ]
    if ragged:
        args.append(s((r + 1,), jnp.int32))
    return args + [s((1,), jnp.int32)]


@pytest.mark.parametrize("kernel", ["ragged", "decode"])
def test_both_attention_kernels_compile_on_the_packed_page_of_64_lane_heads(one_chip, kernel):
    """32 query heads over 8 KV heads of 64 lanes, a KV head's key and value
    side by side in one 128-lane row of the page (every accepted cell has
    heads of 128 or 192, or a latent row), at the cell's rows and the whole
    page table."""
    from vllm_tpu.ops.attention import kv_cache_shape, packed_kv_layout
    from vllm_tpu.ops.rpa_decode_kernel import decode_paged_attention
    from vllm_tpu.ops.rpa_kernel import ragged_paged_attention

    assert packed_kv_layout(LFM2_HEAD) and kv_cache_shape(
        LFM2_ATTN_LAYERS, LFM2_BLOCKS, PAGE, LFM2_KV_HEADS, LFM2_HEAD) == (
        4, LFM2_BLOCKS, 16, 8, 128)
    r = _lfm2_rows()
    if kernel == "ragged":
        fn = functools.partial(ragged_paged_attention, sm_scale=LFM2_HEAD**-0.5)
        args = _lfm2_attn_args(one_chip, 512, r, True)
    else:
        fn = functools.partial(decode_paged_attention, sm_scale=LFM2_HEAD**-0.5)
        args = _lfm2_attn_args(one_chip, r, r, False) + [
            jax.ShapeDtypeStruct((r,), jnp.int32, sharding=one_chip)]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert ("rpa_kernel" if kernel == "ragged" else "rpa_decode_kernel") in text


@pytest.mark.parametrize("decode_only", [True, False], ids=["decode_only", "mixed_T512"])
def test_lfm2_moe_step_compiles_and_reads_each_weight_in_place(
        one_chip, monkeypatch, decode_only):
    """Both step programs of ``lfm2-8b-a1b.chat`` at the published widths,
    layers 0-15, compiled for the v5e with the Pallas forms on: the shared
    convolution at three taps, the attention kernel of the step's kind on
    the packed page, ``gmm`` over the 14 x 32 experts' stacks whole; the
    pages and the slots go through in place; and no iteration of the scan
    over the periods slices, copies or transposes a WEIGHT out of its
    stack (an expert stack is 3.3 GB: one copy would not fit)."""
    import json
    import pathlib

    from transformers import PretrainedConfig

    from vllm_tpu.layers.rotary import serving_positions
    from vllm_tpu.models.lfm2_moe import Lfm2MoeForCausalLM
    from vllm_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    root = pathlib.Path(__file__).resolve().parents[1]
    config = json.loads((root / LFM2_CONFIG).read_text())
    config.pop("bench")
    with serving_positions(LFM2_PAGES * PAGE):  # as the worker does
        model = Lfm2MoeForCausalLM(PretrainedConfig.from_dict(config), dtype=jnp.bfloat16)
    r = _lfm2_rows()
    model.max_state_slots = r
    t = r if decode_only else 512
    assert (model.head_layers, model.period, model.reps, model.tail_layers) == (
        [0, 1], 4, 3, [14, 15])
    assert model.state_bytes_per_slot() == 12 * 2 * 2048 * 2 == 98_304

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    on_chip = lambda tree: jax.tree.map(lambda a: s(a.shape, a.dtype), tree)
    params = on_chip(jax.eval_shape(model.init_dummy_params, jax.random.PRNGKey(0)))
    weights = sum(math.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert weights == pytest.approx(10.80e9, rel=2e-3)
    assert params["experts"]["we_gate"].shape == (14, 32, 2048, 1792)
    cache = on_chip(jax.eval_shape(
        lambda: model.alloc_kv_cache(LFM2_BLOCKS, PAGE, jnp.bfloat16)))
    assert cache["conv"].shape == (12, r + 1, 2, 16, 128)
    assert cache["paged"].shape == (4, LFM2_BLOCKS, PAGE, 8, 128)
    md = attention.AttentionMetadata(
        positions=s((t,), jnp.int32), slot_mapping=s((t,), jnp.int32),
        block_tables=s((r, LFM2_PAGES), jnp.int32),
        seq_lens=s((r,), jnp.int32), query_start_loc=s((r + 1,), jnp.int32),
        token_req_idx=s((t,), jnp.int32), logits_indices=s((r,), jnp.int32),
        num_seqs=s((1,), jnp.int32), decode_only=decode_only,
        state_slots=s((r,), jnp.int32),
    )
    compiled = jax.jit(model.apply, donate_argnums=(1,)).lower(
        params, cache, s((t,), jnp.int32), md).compile()
    text = compiled.as_text()
    for kernel in ("kda_conv_kernel", "gmm",
                   "rpa_decode_kernel" if decode_only else "rpa_kernel"):
        assert kernel in text
    held = sum(math.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves(cache))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 1 << 30  # no copy of an expert stack (3.3 GB)
    squeezed = lambda dims: tuple(d for d in dims if d != 1)
    shapes = {squeezed(a.shape[1:]) for kind in ("conv", "attn", "moe", "experts")
              for a in jax.tree.leaves(params[kind]) if a.ndim >= 3}
    shapes |= {sh[:-2] + sh[:-3:-1] for sh in shapes if len(sh) >= 2}
    smallest = 2048 * 512 * 2  # wk, wv
    # (a mixed step's [512 tokens, 2048] activations are wk's shape turned
    # round: rows of the step's tokens are no weight)
    moved = [
        (name, opcode, dims, nbytes)
        for name, opcode, nbytes, dims in _while_body_instructions(text)
        if nbytes >= smallest and squeezed(dims)[0] != t
        and ("dynamic-slice" in name or opcode == "dynamic-slice"
             or (opcode in ("copy", "transpose") and squeezed(dims) in shapes))
    ]
    assert not moved, (
        f"the scan over the periods moves weight-sized temporaries (>= {smallest} "
        f"bytes): {moved}")
