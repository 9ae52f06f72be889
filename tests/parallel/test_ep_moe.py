"""Expert-parallel MoE: ragged all_to_all dispatch + grouped GEMM.

Reference analog: the modular-kernel EP pipeline
(``vllm/model_executor/layers/fused_moe/modular_kernel.py:181`` prepare →
experts → finalize; ``csrc/moe/moe_align_sum_kernels.cu``) and
``tests/distributed/test_expert_parallel.py``. TPU realization: shard_map
manual region over the ep(=tp) mesh axis, offsets from an all_gathered
count matrix, megablox grouped GEMM over expert-sorted rows. The CPU mesh
exercises the identical offset/sort/group math through the all_gather
emulation of ``ragged_all_to_all`` (no XLA:CPU lowering for the primitive).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from vllm_tpu.layers.moe import _dense_moe, ep_moe, select_experts


def _rand_moe(rng, t, d, f, e, k):
    hidden = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(e, d, f)) * 0.1, jnp.float32)
    wu = jnp.asarray(rng.normal(size=(e, d, f)) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(e, f, d)) * 0.1, jnp.float32)
    logits = jnp.asarray(rng.normal(size=(t, e)), jnp.float32)
    weights, ids = select_experts(logits, k)
    return hidden, wg, wu, wd, weights, ids


@pytest.mark.parametrize("ep,t", [(2, 16), (4, 16), (8, 24), (4, 13)])
def test_ep_moe_matches_dense(cpu_devices, ep, t):
    """Ragged-dispatch EP == dense one-hot on an ep-only mesh.

    t=13 exercises the divisibility padding; skewed routing (top-k over
    random logits) exercises non-uniform per-device receive counts.
    """
    d, f, e, k = 8, 12, 8, 2
    rng = np.random.default_rng(ep * 100 + t)
    hidden, wg, wu, wd, weights, ids = _rand_moe(rng, t, d, f, e, k)
    ref = _dense_moe(hidden, wg, wu, wd, weights, ids)

    mesh = Mesh(np.asarray(cpu_devices[:ep]).reshape(ep), ("tp",))
    got = jax.jit(
        lambda *a: ep_moe(*a, mesh=mesh, axis="tp", interpret=True)
    )(hidden, wg, wu, wd, weights, ids)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


def test_ep_moe_extreme_skew(cpu_devices):
    """All tokens route to the experts of one device (worst-case counts)."""
    d, f, e, k, t, ep = 8, 12, 8, 2, 16, 4
    rng = np.random.default_rng(7)
    hidden, wg, wu, wd, _, _ = _rand_moe(rng, t, d, f, e, k)
    # Every pair lands on device 2's experts {4, 5}.
    ids = jnp.tile(jnp.asarray([[4, 5]], jnp.int32), (t, 1))
    weights = jnp.full((t, k), 0.5, jnp.float32)
    ref = _dense_moe(hidden, wg, wu, wd, weights, ids)
    mesh = Mesh(np.asarray(cpu_devices[:ep]).reshape(ep), ("tp",))
    got = jax.jit(
        lambda *a: ep_moe(*a, mesh=mesh, axis="tp", interpret=True)
    )(hidden, wg, wu, wd, weights, ids)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


def test_ep_moe_under_dp_mesh(cpu_devices):
    """Partial-manual shard_map composes with an outer dp axis: tokens
    arrive dp-sharded, the EP region is manual over tp only."""
    d, f, e, k, t = 8, 12, 8, 2, 16
    rng = np.random.default_rng(11)
    hidden, wg, wu, wd, weights, ids = _rand_moe(rng, t, d, f, e, k)
    ref = _dense_moe(hidden, wg, wu, wd, weights, ids)

    mesh = Mesh(np.asarray(cpu_devices[:8]).reshape(2, 4), ("dp", "tp"))
    hidden_s = jax.device_put(hidden, NamedSharding(mesh, P("dp", None)))
    wg_s = jax.device_put(wg, NamedSharding(mesh, P("tp", None, None)))
    wu_s = jax.device_put(wu, NamedSharding(mesh, P("tp", None, None)))
    wd_s = jax.device_put(wd, NamedSharding(mesh, P("tp", None, None)))
    got = jax.jit(
        lambda *a: ep_moe(*a, mesh=mesh, axis="tp", interpret=True)
    )(hidden_s, wg_s, wu_s, wd_s, weights, ids)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


def test_mixtral_ep_generate_parity(tmp_path_factory):
    """E2E: Mixtral-tiny with --enable-expert-parallel at tp=2 produces the
    same greedy tokens as tp=1 (reference protocol:
    tests/distributed/test_expert_parallel.py)."""
    from tests.models.test_mixtral import tiny_mixtral_config
    import torch
    from transformers import MixtralForCausalLM as HfMixtral

    from vllm_tpu import LLM, SamplingParams

    torch.manual_seed(0)
    hf = HfMixtral(tiny_mixtral_config()).to(torch.float32)
    path = str(tmp_path_factory.mktemp("tiny_mixtral_ep"))
    hf.save_pretrained(path, safe_serialization=True)

    rng = np.random.default_rng(5)
    prompts = [rng.integers(10, 120, size=n).tolist() for n in (9, 14)]

    def run(tp, ep):
        llm = LLM(
            model=path, dtype="float32", max_model_len=128, block_size=16,
            num_gpu_blocks_override=64, max_num_seqs=8,
            max_num_batched_tokens=128, tensor_parallel_size=tp,
            enable_expert_parallel=ep,
        )
        params = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)
        outs = llm.generate([{"prompt_token_ids": p} for p in prompts], params)
        return [o.outputs[0].token_ids for o in outs]

    ref = run(1, False)
    got = run(2, True)
    assert got == ref
