"""Falcon-H1 on the CPU at a tiny size (``tests/benchmark/tiny_falcon_h1.py``):
what the engine serves through pages AND state slots under every layer
against the plain reference's full forward pass, on the whole vocabulary's
logits; the published checkpoint layout against ``transformers``' own
implementation; the counters the runner keeps."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.manifest import BENCH_DIR, load_module
from tests.benchmark.tiny_falcon_h1 import TINY_FALCON_H1, model_dir

REF = load_module(BENCH_DIR / "reference" / "falcon_h1.py")
V = TINY_FALCON_H1["vocab_size"]


def _engine(tmp_path, seed, **kw):
    from vllm_tpu import LLM

    args = dict(model=model_dir(tmp_path), load_format="dummy", seed=seed, dtype="float32",
                max_model_len=128, block_size=16, num_gpu_blocks_override=64, max_num_seqs=4,
                max_num_batched_tokens=32)
    args.update(kw)
    return LLM(**args)


def _serve(llm, prompts, answers):
    """Greedy answers with the WHOLE vocabulary's logprobs a position:
    ``[(tokens, logprobs [n, V])]``, a prompt's own answer length."""
    from vllm_tpu import SamplingParams

    outs = llm.generate(
        [{"prompt_token_ids": p} for p in prompts],
        [SamplingParams(temperature=0.0, max_tokens=n, logprobs=V, ignore_eos=True)
         for n in answers])
    got = []
    for o in outs:
        rows = np.full((len(o.outputs[0].token_ids), V), np.nan, np.float32)
        for j, top in enumerate(o.outputs[0].logprobs):
            for tok, lp in top.items():
                rows[j, int(tok)] = lp.logprob
        got.append((list(o.outputs[0].token_ids), rows))
    return got


def _logits_agree(weights, prompts, served, atol):
    """The served log-softmax of every generated position against the
    reference's full forward pass over prompt + answer: the logits, up to
    the row's constant, which the softmax's own sum fixes. Float32 against
    float32, the two differ in the order of sums and in the chunked rule
    against the recurrence: 2e-4 on logits 2.5 wide; one bfloat16 product
    anywhere reads 1e-2 (``test_a_bfloat16_state_fails_the_tolerance``)."""
    worst = 0.0
    for prompt, (tokens, rows) in zip(prompts, served):
        assert not np.isnan(rows).any(), "the whole vocabulary was asked for"
        logits = np.asarray(REF.forward(TINY_FALCON_H1, weights, prompt + tokens[:-1]))
        want = np.asarray(jax.nn.log_softmax(logits[len(prompt) - 1:], axis=-1))
        assert want.shape == rows.shape
        worst = max(worst, float(np.abs(want - rows).max()))
        assert np.array_equal(np.argmax(want, -1), np.asarray(tokens))
    assert worst < atol, worst
    return worst


PROMPTS = (6, 9, 23, 70, 41, 3, 17)  # 70 and 41: chunks of 32 split the row
ANSWERS = (14, 5, 9, 12, 3, 16, 8)  # rows leave at unlike times and others join


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernels interpreted"])
def test_prefill_then_decode_through_pages_and_slots_is_the_reference_on_logits(
        tmp_path, interpret, monkeypatch):
    """Seven requests over four slots, 32 tokens a step: long prompts are
    prefilled in chunks beside the others' decode rows (the chunked rule
    continues a row from its slot), rows join as others leave (a slot is
    taken again by a request that must not read what it held), and every
    generated position's logits are the reference's."""
    from vllm_tpu import envs

    monkeypatch.setitem(envs._cache, "VLLM_TPU_DISABLE_PALLAS", not interpret)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 240, n).tolist() for n in PROMPTS]
    llm = _engine(tmp_path, 11)
    served = _serve(llm, prompts, ANSWERS)
    runner = llm.llm_engine.engine_core.engine_core.executor.worker.runner
    layers = TINY_FALCON_H1["num_hidden_layers"]
    tokens = dict(runner.linear_attn_tokens_by_kind)
    fed = sum(runner.step_tokens_by_kind.values())
    assert tokens["chunk"] + tokens["decode"] == fed * layers  # every layer has the mixer
    assert tokens["chunk"] >= (sum(PROMPTS) - 3 - len(PROMPTS)) * layers
    assert runner.linear_attn_state_rows >= sum(runner.steps_by_kind.values()) * layers
    assert set(runner.kv_cache) == {"paged", "conv", "ssm"}
    assert runner.kv_cache["paged"].shape[0] == layers == runner.kv_cache["ssm"].shape[0]
    assert runner.kv_cache["ssm"].shape[1] == 5 and runner.kv_cache["ssm"].dtype == jnp.float32
    llm.shutdown()
    _logits_agree(REF.seeded_weights(TINY_FALCON_H1, 11, jnp.float32), prompts, served, 2e-4)


def test_a_preempted_request_recomputes_to_the_same_logits(tmp_path):
    """A page pool too small for the batch: requests are preempted, give
    their slot back, and come again from their first token (slot state is
    recomputed, never resumed); what they then serve is still the
    reference's."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 240, 12).tolist() for _ in range(6)]
    llm = _engine(tmp_path, 7, block_size=4, num_gpu_blocks_override=14, max_model_len=64)
    served = _serve(llm, prompts, (20,) * 6)
    assert llm.llm_engine.engine_core.engine_core.scheduler._num_preempted_total > 0
    llm.shutdown()
    _logits_agree(REF.seeded_weights(TINY_FALCON_H1, 7, jnp.float32), prompts, served, 2e-4)


def test_a_bfloat16_state_fails_the_tolerance(tmp_path, monkeypatch):
    """The tolerance is float32's: the same engine with its state slots
    stored in bfloat16 misses it by more than tenfold."""
    from vllm_tpu.models.falcon_h1 import FalconH1ForCausalLM

    alloc = FalconH1ForCausalLM.alloc_kv_cache

    def rounded(self, *a):
        cache = alloc(self, *a)
        return {**cache, "ssm": cache["ssm"].astype(jnp.bfloat16)}

    monkeypatch.setattr(FalconH1ForCausalLM, "alloc_kv_cache", rounded)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 240, n).tolist() for n in (23, 41)]
    llm = _engine(tmp_path, 11)
    served = _serve(llm, prompts, (12, 12))
    llm.shutdown()
    weights = REF.seeded_weights(TINY_FALCON_H1, 11, jnp.float32)
    with pytest.raises(AssertionError):
        _logits_agree(weights, prompts, served, 2e-3)


@pytest.fixture(scope="module")
def saved_checkpoint(tmp_path_factory):
    """The family's own implementation at the tiny widths, every parameter
    drawn wide enough to matter (the library's initialisation leaves the
    decays and the skip at constants), saved in the published layout."""
    import torch
    from transformers import FalconH1Config, FalconH1ForCausalLM

    cfg = {k: v for k, v in TINY_FALCON_H1.items() if k not in ("architectures", "model_type")}
    torch.manual_seed(0)
    model = FalconH1ForCausalLM(FalconH1Config(**cfg)).to(torch.float32).eval()
    with torch.no_grad():
        for name, p in model.named_parameters():
            if name.endswith(("A_log", ".D", "dt_bias", "norm.weight", "layernorm.weight",
                              "conv1d.bias")):
                p.add_(torch.randn_like(p) * 0.3)
            else:
                p.mul_(8.0)  # the library draws at 0.02
    path = tmp_path_factory.mktemp("tiny_falcon_h1")
    model.save_pretrained(str(path), safe_serialization=True)
    return str(path), model


def test_hf_weight_map_loads_the_published_layout_and_serves_the_librarys_logits(
        saved_checkpoint):
    """``hf_weight_map`` over a tiny saved checkpoint: every leaf of the
    tree is filled at its dummy shape, the decays stay float32, and both
    the reference (on the loaded tree) and the engine give the logits of
    ``transformers``' implementation."""
    import torch
    from transformers import PretrainedConfig

    from vllm_tpu import LLM, SamplingParams
    from vllm_tpu.models.registry import get_model_class

    path, hf = saved_checkpoint
    cfg = PretrainedConfig.from_dict(TINY_FALCON_H1)
    model = get_model_class(cfg)(cfg, dtype=jnp.float32)
    params = model.load_params(path)
    dummy = jax.eval_shape(lambda: model.init_dummy_params(jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), params) == jax.tree.map(
        lambda x: (x.shape, x.dtype), dummy)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 240, 37).tolist()
    with torch.no_grad():
        want = hf(torch.tensor([prompt])).logits[0].numpy()
    got = np.asarray(REF.forward(TINY_FALCON_H1, params, prompt))
    assert np.abs(want).max() > 1.0  # the logits say something
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())

    llm = LLM(model=path, dtype="float32", max_model_len=128, block_size=16,
              num_gpu_blocks_override=64, max_num_seqs=4, max_num_batched_tokens=16)
    out = llm.generate([{"prompt_token_ids": prompt}],
                       SamplingParams(temperature=0.0, max_tokens=6, logprobs=V,
                                      ignore_eos=True))[0].outputs[0]
    llm.shutdown()
    tokens = list(out.token_ids)
    with torch.no_grad():
        full = hf(torch.tensor([prompt + tokens[:-1]])).logits[0, len(prompt) - 1:]
        want_lp = torch.log_softmax(full, -1).numpy()
    served = np.asarray([[top[t].logprob for t in range(V)] for top in out.logprobs])
    assert tokens == np.argmax(want_lp, -1).tolist()
    np.testing.assert_allclose(served, want_lp, atol=3e-4)


def test_the_layers_are_one_scan_over_stacked_leaves_each_with_one_consumer():
    """``apply`` holds one ``lax.scan`` whose ``xs`` are the ``[L, ...]``
    stacks and the layer's index, and every leaf of a layer is read by one
    operation of the body (PR 40: a slab with several readers is copied out
    of its stack)."""
    from transformers import PretrainedConfig

    from vllm_tpu.models.registry import get_model_class
    from vllm_tpu.ops.attention import AttentionMetadata

    cfg = PretrainedConfig.from_dict(TINY_FALCON_H1)
    model = get_model_class(cfg)(cfg, dtype=jnp.float32)
    model.max_state_slots = 4
    params = jax.eval_shape(lambda: model.init_dummy_params(jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: model.alloc_kv_cache(8, 16, jnp.float32))
    t, r = 16, 4
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    md = AttentionMetadata(
        positions=i32(t), slot_mapping=i32(t), block_tables=i32(r, 8), seq_lens=i32(r),
        query_start_loc=i32(r + 1), token_req_idx=i32(t), logits_indices=i32(r),
        num_seqs=i32(1), state_slots=i32(r))
    jaxpr = jax.make_jaxpr(model.apply)(params, cache, i32(t), md)
    n_leaves = len(jax.tree.leaves(params["layers"]))
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"
             and len(e.invars) > n_leaves]
    assert len(scans) == 1
    (scan,) = scans
    assert scan.params["length"] == TINY_FALCON_H1["num_hidden_layers"]
    assert scan.params["num_carry"] == 4  # the residual, pages, tails, states
    body = scan.params["jaxpr"].jaxpr
    xs = body.invars[scan.params["num_consts"] + scan.params["num_carry"]:]
    assert len(xs) == n_leaves + 1
    assert [v.aval.shape for v in xs].count(()) == 1  # the layer's index
    for var in (v for v in xs if v.aval.shape):
        readers = sum(var in e.invars for e in body.eqns)
        assert readers == 1, (var, var.aval, readers)


def test_the_decode_kernel_takes_five_query_heads_a_kv_head():
    """20 query heads over 4 KV heads: a row of the output is ``[heads,
    lanes]`` in tiles of 8 heads and the kernel copies whole rows, so each
    KV head's group of 5 is filled up to 6 with query heads of zeros whose
    outputs are dropped (the chip's compiler refuses 20 of a tile's 24:
    ``tests/test_tpu_aot_compile.py``). The answer is the reference's, and
    the same rows through the ragged kernel."""
    from tests.models.test_decode_attention import _decode_case, dispatched
    from vllm_tpu.ops.attention import ref_ragged_paged_attention

    rng = np.random.default_rng(9)
    kh, h, d, bs = 4, 20, 128, 16
    q, kv_cache, md = _decode_case(rng, [60, 9, 41, 130, 17], kh, h, d, bs, num_blocks=64)
    scale = d ** -0.5
    got, names = dispatched(q, kv_cache, md, scale)
    assert names == ["rpa_decode_kernel"]
    want = ref_ragged_paged_attention(q, kv_cache, jnp.int32(0), md, scale)
    assert got.shape == want.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_the_runner_counts_the_attention_kernels_work_under_a_cache_of_the_models_making(
        monkeypatch):
    """The cache is a pytree of the model's own (pages beside two slot
    arrays), but its pages are plain K/V under ``paged_attention``, and the
    model says so (``plain_kv_pages``): a mixed step's pairs and a
    decode-only step's tiles are counted by the kernels' own tilings, as a
    dense model's are, so ``ragged_attn_live_share`` reads in this cell."""
    import json
    from types import SimpleNamespace

    from vllm_tpu.models.falcon_h1 import FalconH1ForCausalLM
    from vllm_tpu.ops import attention
    from vllm_tpu.worker.model_runner import ModelRunner

    published = json.loads(
        (BENCH_DIR / "configs" / "falcon-h1-34b-instruct.json").read_text())
    model = FalconH1ForCausalLM(SimpleNamespace(**published), dtype=jnp.bfloat16)
    stub = SimpleNamespace(
        model=model, block_size=64, block_len=0, tp_size=1, _ragged_tilings={},
        _decode_tilings={}, attn_ragged_pairs={"live": 0, "computed": 0},
        attn_decode_tiles={"run": 0, "least": 0}, _kv_dtype=lambda: jnp.bfloat16)
    stub._attn_call_geometry = ModelRunner._attn_call_geometry.func(stub)
    assert stub._attn_call_geometry == ((20, 4, 128, 64), False)
    assert hasattr(model, "alloc_kv_cache")  # and yet counted

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    q_lens, kv_lens = np.array([1, 1, 300, 210]), np.array([900, 77, 300, 1500])
    ModelRunner._count_ragged_pairs(stub, q_lens, kv_lens, 512, 192)
    live = int((q_lens * kv_lens - q_lens * (q_lens - 1) // 2).sum())
    assert stub.attn_ragged_pairs["live"] == live
    assert stub.attn_ragged_pairs["computed"] >= live
    ModelRunner._count_decode_tiles(stub, np.array([900, 77, 1500]), 64, 192)
    assert 0 < stub.attn_decode_tiles["least"] <= stub.attn_decode_tiles["run"]
