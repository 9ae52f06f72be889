"""DeepSeek-V2/V3 tests: MLA absorbed-math vs naive expansion, grouped
routing semantics, HF greedy parity, latent cache sizing.

Protocol of the reference's ``tests/models/language`` (tiny-config HF
parity) + kernel-vs-reference checks for the MLA path.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp



def tiny_deepseek_config(**overrides):
    from transformers import DeepseekV2Config

    kwargs = dict(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        moe_intermediate_size=48,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=4,
        n_routed_experts=8,
        n_shared_experts=1,
        num_experts_per_tok=2,
        first_k_dense_replace=1,
        n_group=2,
        topk_group=1,
        topk_method="group_limited_greedy",
        routed_scaling_factor=1.0,
        norm_topk_prob=False,
        kv_lora_rank=32,
        q_lora_rank=None,
        qk_rope_head_dim=16,
        qk_nope_head_dim=32,
        v_head_dim=32,
        max_position_embeddings=256,
        tie_word_embeddings=False,
        # HF's config defaults head_dim to 64 independent of the MLA dims;
        # the attention module only uses qk_*_head_dim, but set it anyway.
        head_dim=48,
    )
    kwargs.update(overrides)
    return DeepseekV2Config(**kwargs)


@pytest.fixture(scope="module")
def tiny_deepseek(tmp_path_factory):
    import torch
    from transformers import DeepseekV2ForCausalLM

    torch.manual_seed(0)
    model = DeepseekV2ForCausalLM(tiny_deepseek_config()).to(torch.float32)
    path = tmp_path_factory.mktemp("tiny_deepseek")
    model.save_pretrained(str(path), safe_serialization=True)
    return str(path)


def test_mla_absorbed_matches_naive_expansion():
    """Absorbed attention (latent-space scores, W_uv after the softmax)
    must equal the naive per-head K/V expansion."""
    rng = np.random.default_rng(0)
    t, h, dn, dr, dc, dv = 5, 3, 8, 4, 16, 8
    q_nope = jnp.asarray(rng.standard_normal((t, h, dn)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((t, h, dr)), jnp.float32)
    c_kv = jnp.asarray(rng.standard_normal((t, dc)), jnp.float32)
    k_pe = jnp.asarray(rng.standard_normal((t, dr)), jnp.float32)
    w_uk = jnp.asarray(rng.standard_normal((dc, h, dn)) * 0.2, jnp.float32)
    w_uv = jnp.asarray(rng.standard_normal((dc, h, dv)) * 0.2, jnp.float32)
    scale = (dn + dr) ** -0.5

    # Naive: expand K/V per head, causal softmax per query position.
    k = jnp.einsum("tc,chn->thn", c_kv, w_uk)  # [T, H, DN]
    v = jnp.einsum("tc,chv->thv", c_kv, w_uv)  # [T, H, DV]
    k_full = jnp.concatenate([k, jnp.broadcast_to(k_pe[:, None, :], (t, h, dr))], -1)
    q_full = jnp.concatenate([q_nope, q_pe], -1)
    scores = jnp.einsum("qhd,khd->hqk", q_full, k_full) * scale
    mask = np.tril(np.ones((t, t), bool))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    naive = jnp.einsum("hqk,khv->qhv", probs, v)

    # Absorbed path through the paged op.
    from vllm_tpu.ops.attention import AttentionMetadata
    from vllm_tpu.ops.mla_attention import (
        mla_kv_cache_shape,
        mla_paged_attention,
        write_latent,
    )

    bs = 4
    nb = 4
    kv = jnp.zeros(mla_kv_cache_shape(1, nb, bs, dc + dr), jnp.float32)
    latent = jnp.concatenate([c_kv, k_pe], -1)
    slot = jnp.arange(t, dtype=jnp.int32) + bs  # block 1 onward
    kv = write_latent(kv, jnp.int32(0), latent, slot)
    md = AttentionMetadata(
        positions=jnp.arange(t, dtype=jnp.int32),
        slot_mapping=slot,
        block_tables=jnp.asarray([[1, 2, 0, 0]], jnp.int32),
        seq_lens=jnp.asarray([t], jnp.int32),
        query_start_loc=jnp.asarray([0, t], jnp.int32),
        token_req_idx=jnp.zeros((t,), jnp.int32),
        logits_indices=jnp.asarray([t - 1], jnp.int32),
        num_seqs=jnp.asarray([1], jnp.int32),
    )
    q_lat = jnp.einsum("thn,chn->thc", q_nope, w_uk)
    q_abs = jnp.concatenate([q_lat, q_pe], -1)
    ctx = mla_paged_attention(q_abs, kv, jnp.int32(0), md, scale, value_dim=dc)
    absorbed = jnp.einsum("thc,chv->thv", ctx, w_uv)

    np.testing.assert_allclose(
        np.asarray(absorbed), np.asarray(naive), rtol=2e-5, atol=2e-5
    )


def test_deepseek_routing_group_limited():
    """Group-limited greedy: experts outside the winning groups are never
    selected; weights come from the softmax scores."""
    from vllm_tpu.models.deepseek import DeepseekV2ForCausalLM

    model = DeepseekV2ForCausalLM.__new__(DeepseekV2ForCausalLM)
    model.sigmoid_routing = False
    model.n_group = 4
    model.topk_group = 2
    model.top_k = 3
    model.topk_method = "group_limited_greedy"
    model.norm_topk_prob = False
    model.routed_scaling = 2.0

    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.standard_normal((6, 16)), jnp.float32)
    weights, ids = model._select_experts(logits, None)
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    for t in range(6):
        group_scores = probs[t].reshape(4, 4).max(-1)
        winners = set(np.argsort(group_scores)[::-1][:2])
        for eid, w in zip(np.asarray(ids[t]), np.asarray(weights[t])):
            assert eid // 4 in winners
            np.testing.assert_allclose(w, probs[t][eid] * 2.0, rtol=1e-5)


def test_deepseek_routing_noaux_tc_matches_hf_semantics():
    """V3 routing: sigmoid scores, bias only influences CHOICE, returned
    weights are the unbiased scores, normalized then scaled."""
    from vllm_tpu.models.deepseek import DeepseekV2ForCausalLM

    model = DeepseekV2ForCausalLM.__new__(DeepseekV2ForCausalLM)
    model.sigmoid_routing = True
    model.n_group = 2
    model.topk_group = 1
    model.top_k = 2
    model.topk_method = "noaux_tc"
    model.norm_topk_prob = True
    model.routed_scaling = 1.5

    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.standard_normal((4, 8)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal((8,)), jnp.float32)
    weights, ids = model._select_experts(logits, bias)

    scores = 1 / (1 + np.exp(-np.asarray(logits)))
    choice = scores + np.asarray(bias)[None]
    for t in range(4):
        g = choice[t].reshape(2, 4)
        gs = np.sort(g, axis=-1)[:, -2:].sum(-1)
        win = int(np.argmax(gs))
        masked = np.where(
            np.repeat(np.arange(2) == win, 4), choice[t], 0.0
        )
        top = set(np.argsort(masked)[::-1][:2])
        assert set(np.asarray(ids[t]).tolist()) == top
        sel = sorted(top, key=lambda e: -masked[e])
        raw = scores[t][np.asarray(sel)]
        want = raw / (raw.sum() + 1e-20) * 1.5
        got = {
            int(e): float(w)
            for e, w in zip(np.asarray(ids[t]), np.asarray(weights[t]))
        }
        for e, w in zip(sel, want):
            np.testing.assert_allclose(got[int(e)], w, rtol=1e-5)


@pytest.mark.parametrize("budget", [128, 16])  # 16 forces chunked prefill
def test_deepseek_e2e_greedy_matches_hf(tiny_deepseek, budget):
    import torch
    from transformers import AutoModelForCausalLM

    from vllm_tpu import LLM, SamplingParams

    llm = LLM(
        model=tiny_deepseek,
        dtype="float32",
        max_model_len=128,
        block_size=16,
        num_gpu_blocks_override=64,
        max_num_seqs=4,
        max_num_batched_tokens=budget,
    )
    rng = np.random.default_rng(3)
    prompts = [rng.integers(5, 120, size=n).tolist() for n in (9, 5)]
    outs = llm.generate(
        [{"prompt_token_ids": p} for p in prompts],
        SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True),
    )

    hf = AutoModelForCausalLM.from_pretrained(
        tiny_deepseek, torch_dtype=torch.float32
    )
    hf.eval()
    for out, prompt in zip(outs, prompts):
        with torch.no_grad():
            ref = hf.generate(
                torch.tensor([prompt]),
                max_new_tokens=6,
                do_sample=False,
            )[0][len(prompt):].tolist()
        assert out.outputs[0].token_ids == ref


def test_deepseek_latent_cache_geometry(tiny_deepseek):
    """The allocated cache is the latent layout (one shared row), and the
    spec's page bytes reflect it (no K/V doubling)."""
    import jax.numpy as jnp
    from transformers import AutoConfig

    from vllm_tpu.core.kv_cache_utils import MLAAttentionSpec
    from vllm_tpu.models.registry import get_model_class

    hf_config = AutoConfig.from_pretrained(tiny_deepseek)
    model = get_model_class(hf_config)(hf_config, dtype=jnp.float32)
    # A row of 48 numbers lies in one 128-lane tile, tokens on the sublanes.
    assert model.kv_cache_shape(10, 16) == (3, 10, 16, 128)
    spec = model.get_kv_cache_spec(16, 4)["layers.0"]
    assert isinstance(spec, MLAAttentionSpec)
    assert spec.page_size_bytes == 16 * 128 * 4


def test_deepseek_v3_e2e_greedy_matches_hf(tmp_path_factory):
    """V3: q-LoRA + sigmoid noaux_tc routing, tiny config."""
    import torch
    from transformers import AutoModelForCausalLM, DeepseekV3Config
    from transformers import DeepseekV3ForCausalLM as HFDeepseekV3

    from vllm_tpu import LLM, SamplingParams

    cfg = DeepseekV3Config(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        moe_intermediate_size=48,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=4,
        n_routed_experts=8,
        n_shared_experts=1,
        num_experts_per_tok=2,
        first_k_dense_replace=1,
        n_group=2,
        topk_group=1,
        routed_scaling_factor=1.2,
        norm_topk_prob=True,
        kv_lora_rank=32,
        q_lora_rank=24,
        qk_rope_head_dim=16,
        qk_nope_head_dim=32,
        v_head_dim=32,
        # HF V3 builds its rope table from head_dim: must be the rope dim.
        head_dim=16,
        max_position_embeddings=256,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    hf = HFDeepseekV3(cfg).to(torch.float32)
    path = str(tmp_path_factory.mktemp("tiny_deepseek_v3"))
    hf.save_pretrained(path, safe_serialization=True)

    llm = LLM(
        model=path, dtype="float32", max_model_len=128, block_size=16,
        num_gpu_blocks_override=64, max_num_seqs=4,
        max_num_batched_tokens=128,
    )
    rng = np.random.default_rng(5)
    prompt = rng.integers(5, 120, size=8).tolist()
    [out] = llm.generate(
        [{"prompt_token_ids": prompt}],
        SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True),
    )
    hf.eval()
    with torch.no_grad():
        ref = hf.generate(
            torch.tensor([prompt]), max_new_tokens=6, do_sample=False
        )[0][len(prompt):].tolist()
    assert out.outputs[0].token_ids == ref
