"""LFM2-MoE on the CPU at a tiny size (``tests/benchmark/tiny_lfm2_moe.py``):
what the engine serves through pages in the attention layers and tail slots
in the convolution layers against the plain reference's full forward pass,
on the whole vocabulary's logits; the shared convolution at three taps; the
router's guard; the reference against ``transformers``' own dense sibling."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.manifest import BENCH_DIR, load_module
from tests.benchmark.tiny_lfm2_moe import PUBLISHED_PATTERN, TINY_LFM2_MOE, model_dir

REF = load_module(BENCH_DIR / "reference" / "lfm2_moe.py")
V = TINY_LFM2_MOE["vocab_size"]
PUBLISHED_DEPTH = {**TINY_LFM2_MOE, "num_hidden_layers": 24}


def _engine(tmp_path, seed, config=None, **kw):
    from vllm_tpu import LLM

    args = dict(model=model_dir(tmp_path, **(config or {})), load_format="dummy", seed=seed,
                dtype="float32", max_model_len=128, block_size=16, num_gpu_blocks_override=64,
                max_num_seqs=4, max_num_batched_tokens=32)
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


def _logits_agree(config, weights, prompts, served, atol):
    """The served log-softmax of every generated position against the
    reference's full forward pass over prompt + answer. Float32 against
    float32, the two differ in the order of sums only: 2e-4 on logits one
    wide; one bfloat16 product anywhere reads 1e-2."""
    worst = 0.0
    for prompt, (tokens, rows) in zip(prompts, served):
        assert not np.isnan(rows).any(), "the whole vocabulary was asked for"
        logits = np.asarray(REF.forward(config, weights, prompt + tokens[:-1]))
        want = np.asarray(jax.nn.log_softmax(logits[len(prompt) - 1:], axis=-1))
        assert want.shape == rows.shape
        worst = max(worst, float(np.abs(want - rows).max()))
        assert np.array_equal(np.argmax(want, -1), np.asarray(tokens))
    assert worst < atol, worst
    return worst


# 70 and 41: chunks of 32 split the row, and what the decode rows leave of a
# step's 32 tokens differs from step to step; 33: a chunk of ONE token, which
# keeps one value of its old tail.
PROMPTS = (6, 33, 23, 70, 41, 3, 17)
ANSWERS = (14, 5, 9, 12, 3, 16, 8)  # rows leave at unlike times and others join


@pytest.mark.parametrize("config", [TINY_LFM2_MOE, PUBLISHED_DEPTH],
                         ids=["the cell's pattern", "the published 24 layers"])
@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernels interpreted"])
def test_prefill_then_decode_through_pages_and_slots_is_the_reference_on_logits(
        tmp_path, interpret, config, monkeypatch):
    """Seven requests over four slots, 32 tokens a step: long prompts are
    prefilled in chunks of unlike length beside the others' decode rows (a
    row's tail is carried from chunk to chunk in its slot), rows join as
    others leave (a slot is taken again by a request that must start from
    zeros and not from what the slot held), and every generated position's
    logits are the reference's. Once with the cell's pattern (its scan
    covers layers 2-9 and two layers follow), once with the published 24
    entries, whose attention layers at 18 and 21 stand outside the period."""
    from vllm_tpu import envs

    monkeypatch.setitem(envs._cache, "VLLM_TPU_DISABLE_PALLAS", not interpret)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 240, n).tolist() for n in PROMPTS]
    llm = _engine(tmp_path, 11, config)
    served = _serve(llm, prompts, ANSWERS)
    worker = llm.llm_engine.engine_core.engine_core.executor.worker
    runner, model = worker.runner, worker.model
    conv_layers = config["layer_types"][: config["num_hidden_layers"]].count("conv")
    assert model.num_linear_attn_layers == conv_layers
    if config is PUBLISHED_DEPTH:
        assert (model.head_layers, model.period, model.reps, model.tail_layers) == (
            [0, 1], 4, 4, [18, 19, 20, 21, 22, 23])
        assert model.attn_layer_indices == [2, 6, 10, 14, 18, 21]
    else:
        assert (model.head_layers, model.period, model.reps, model.tail_layers) == (
            [0, 1], 4, 2, [10, 11])
    tokens = dict(runner.linear_attn_tokens_by_kind)
    fed = sum(runner.step_tokens_by_kind.values())
    assert tokens["chunk"] + tokens["decode"] == fed * conv_layers
    assert runner.linear_attn_state_rows >= sum(runner.steps_by_kind.values()) * conv_layers
    assert set(runner.kv_cache) == {"paged", "conv"}
    assert runner.kv_cache["paged"].shape[0] == model.num_attn_layers
    # Four slots and the scratch one, a tail of two tokens of the 64 channels.
    assert runner.kv_cache["conv"].shape == (conv_layers, 5, 2, 1, 64)
    assert runner.moe_assignments == fed * 2 * (config["num_hidden_layers"] - 2)
    llm.shutdown()
    _logits_agree(config, REF.seeded_weights(config, 11, jnp.float32), prompts, served, 2e-4)


def test_a_preempted_request_recomputes_to_the_same_logits(tmp_path):
    """A page pool too small for the batch: requests are preempted, give
    their slot back, and come again from their first token (the tail is
    recomputed, never resumed); what they then serve is still the
    reference's."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 240, 12).tolist() for _ in range(6)]
    llm = _engine(tmp_path, 7, block_size=4, num_gpu_blocks_override=14, max_model_len=64)
    served = _serve(llm, prompts, (20,) * 6)
    assert llm.llm_engine.engine_core.engine_core.scheduler._num_preempted_total > 0
    llm.shutdown()
    _logits_agree(TINY_LFM2_MOE, REF.seeded_weights(TINY_LFM2_MOE, 7, jnp.float32), prompts,
                  served, 2e-4)


def test_a_request_that_takes_a_used_slot_starts_from_zeros(tmp_path):
    """ONE slot: the second request runs where the first one did, after it,
    and its logits are those of a sequence that starts from nothing."""
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 240, n).tolist() for n in (19, 7)]
    llm = _engine(tmp_path, 5, max_num_seqs=1)
    served = _serve(llm, prompts, (6, 6))
    cache = llm.llm_engine.engine_core.engine_core.executor.worker.runner.kv_cache
    assert cache["conv"].shape[1] == 2 and np.asarray(cache["conv"][:, 0]).any()
    llm.shutdown()
    _logits_agree(TINY_LFM2_MOE, REF.seeded_weights(TINY_LFM2_MOE, 5, jnp.float32), prompts,
                  served, 2e-4)


# ----------------------------------------------------------------------
# The shared convolution at three taps
# ----------------------------------------------------------------------

CONV_STEPS = {
    "one row as steps of 2, 1, 9, 1, 1, 7": [
        ([(2, 1)], 4, 2), ([(1, 0)], 4, 2), ([(9, 0)], 12, 2), ([(1, 0)], 4, 2),
        ([(1, 0)], 4, 2), ([(7, 0)], 8, 2)],
    "a mixed step": [([(1, 0), (1, 0), (11, 0), (1, 1), (2, 1)], 24, 8)],
    "decode only, fewer live rows than the bucket": [
        ([(1, 0), (1, 1), (1, 0), (1, 0), (1, 0)], 8, 8)],
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(CONV_STEPS))
def test_the_shared_convolution_at_three_taps_is_the_sum_over_the_rows_history(
        case, dtype, monkeypatch):
    """``ops/kda.py::kda_short_conv`` at K = 3, one stream, no bias, a slot
    of ``[2, G, dk]`` and nothing else in it: ``kda_conv_kernel``
    interpreted against the XLA form, and both against the three-term sum
    over the row's whole history. A step of ONE token keeps one value of
    the row's old tail; a fresh row starts from zeros whatever its slot
    held."""
    from vllm_tpu import envs
    from vllm_tpu.ops import kda

    monkeypatch.setattr(kda, "CONV_TILE", 4)
    rng = np.random.default_rng(23)
    g, dk, k, slots_n, layers = 2, 16, 3, 9, 2
    taps = jnp.asarray(rng.normal(size=(k, g * dk)), dtype)
    # What a slot held before: a fresh row must not read it.
    stack = jnp.asarray(rng.normal(size=(layers, slots_n + 1, k - 1, g, dk)), dtype)
    decode_only = case.startswith("decode only")
    history: dict[int, list] = {}
    for rows, t_pad, r_pad in CONV_STEPS[case]:
        n = len(rows)
        qlens = [q for q, _ in rows]
        qsl = np.full(r_pad + 1, sum(qlens), np.int32)
        qsl[0] = 0
        qsl[1: n + 1] = np.cumsum(qlens)
        owner = np.zeros(t_pad, np.int32)
        for i in range(n):
            owner[qsl[i]: qsl[i + 1]] = i
        slots = np.full(r_pad, slots_n, np.int32)
        slots[:n] = (3 + 2 * np.arange(n)) % slots_n
        fresh = np.zeros(r_pad, bool)
        fresh[:n] = [f for _, f in rows]
        for i in range(n):
            if not fresh[i] and int(slots[i]) not in history:
                # The row came before this test's first step: its tail is
                # what the stack holds.
                history[int(slots[i])] = list(np.asarray(
                    stack[1, int(slots[i])], np.float32).reshape(k - 1, -1))
        x = jnp.asarray(rng.normal(size=(t_pad, g * dk)), dtype)
        if decode_only:
            qsl = np.arange(r_pad + 1, dtype=np.int32)
        args = ((x,), stack, (taps,), jnp.int32(1), jnp.asarray(slots), jnp.asarray(fresh),
                jnp.asarray(owner), jnp.asarray(qsl), jnp.int32(n))
        got = {}
        for form in ("xla", "kernel"):
            monkeypatch.setitem(envs._cache, "VLLM_TPU_DISABLE_PALLAS", form == "xla")
            got[form] = kda.kda_short_conv(*args, decode_only=decode_only)
        (want_y, want_stack), (y, new) = got["xla"], got["kernel"]
        np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(new, np.float32),
                                      np.asarray(want_stack, np.float32))
        xf, wf = np.asarray(x, np.float32), np.asarray(taps, np.float32)
        for i in range(n):
            seen = [] if fresh[i] else history[int(slots[i])]
            at = int(qsl[i]) if not decode_only else i
            for t in range(qlens[i]):
                seen = seen + [xf[at + t]]
                past = [np.zeros(g * dk, np.float32)] * (k - 1) + seen
                want = sum(past[-k + j] * wf[j] for j in range(k))
                np.testing.assert_allclose(
                    np.asarray(y)[at + t].reshape(-1), want, rtol=2e-6, atol=2e-6)
            history[int(slots[i])] = seen
            # The slot holds the row's two last inputs, the older first.
            np.testing.assert_array_equal(
                np.asarray(new[1, int(slots[i])], np.float32).reshape(k - 1, -1),
                np.stack(([np.zeros(g * dk, np.float32)] * (k - 1) + seen)[-(k - 1):]))
        assert not np.asarray(y)[sum(qlens):].any()
        # The other layer, the scratch slot's neighbours and every slot no
        # live row names are as they were.
        untouched = sorted(set(range(slots_n)) - set(int(s) for s in slots[:n]))
        np.testing.assert_array_equal(np.asarray(new[0], np.float32),
                                      np.asarray(stack[0], np.float32))
        np.testing.assert_array_equal(np.asarray(new[1, untouched], np.float32),
                                      np.asarray(stack[1, untouched], np.float32))
        stack = new


# ----------------------------------------------------------------------
# The router
# ----------------------------------------------------------------------

def test_the_routers_guard_is_an_argument_and_its_default_what_it_was():
    """``select_experts_grouped`` as this family calls it against the
    formula written out: sigmoid scores, the choice by ``s + b``, the
    weights ``s`` at the chosen over their sum + 1e-6. Scores near zero
    show the guard (sum 4e-6: a fifth goes to it); the default stays 1e-20,
    under float32's reach, for every caller that was there."""
    import inspect

    from vllm_tpu.layers.moe import select_experts_grouped

    rng = np.random.default_rng(1)
    logits = np.concatenate([rng.normal(size=(5, 8)), np.full((1, 8), -13.8)]).astype(np.float32)
    bias = (rng.normal(size=8) * 0.5).astype(np.float32)
    s = 1 / (1 + np.exp(-logits.astype(np.float64)))
    ids = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :3]
    top = np.take_along_axis(s, ids, -1)
    for guard in (1e-6, 1e-20):
        kw = {} if guard == 1e-20 else {"renorm_guard": guard}
        w, got_ids = select_experts_grouped(
            jnp.asarray(logits), jnp.asarray(bias), top_k=3, sigmoid=True, renormalize=True,
            scaling=1.0, **kw)
        assert np.array_equal(np.sort(np.asarray(got_ids), -1), np.sort(ids, -1))
        order = np.argsort(np.asarray(got_ids), -1)
        want = top / (top.sum(-1, keepdims=True) + guard)
        np.testing.assert_allclose(
            np.take_along_axis(np.asarray(w), order, -1),
            np.take_along_axis(want, np.argsort(ids, -1), -1), rtol=2e-5)
    # Not the choice's scores: with the bias in the weights the sums differ.
    assert np.abs((top + bias[ids]) / (top + bias[ids]).sum(-1, keepdims=True)
                  - top / top.sum(-1, keepdims=True)).max() > 0.05
    # The last row's scores are 1e-6 each: the guard takes a quarter of the sum.
    assert np.asarray(w)[-1].sum() == pytest.approx(1.0) and 0.7 < float(
        select_experts_grouped(jnp.asarray(logits), jnp.asarray(bias), top_k=3, sigmoid=True,
                               renormalize=True, renorm_guard=1e-6)[0][-1].sum()) < 0.8
    assert inspect.signature(select_experts_grouped).parameters["renorm_guard"].default == 1e-20


def test_the_reference_routes_as_the_program_does():
    """The reference's dense weights ``[T, E]`` hold the program's ``[T, k]``
    at the program's ids, on the same logits."""
    from vllm_tpu.layers.moe import select_experts_grouped

    s = REF.sizes(TINY_LFM2_MOE)
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(9, 64)), jnp.float32)
    lw = {"router": jnp.asarray(rng.normal(size=(64, 8)) / 8, jnp.float32),
          "router_bias": jnp.asarray(rng.normal(size=8) * 0.3, jnp.float32)}
    dense = np.asarray(REF.router_weights(h, lw, s))
    w, ids = select_experts_grouped(
        h @ lw["router"], lw["router_bias"], top_k=2, sigmoid=True, renormalize=True,
        scaling=1.0, renorm_guard=1e-6)
    assert (dense > 0).sum(-1).tolist() == [2] * 9
    np.testing.assert_allclose(np.take_along_axis(dense, np.asarray(ids), -1), np.asarray(w),
                               rtol=1e-6)


# ----------------------------------------------------------------------
# The layers as one scan and layers in line
# ----------------------------------------------------------------------

def test_the_periodic_stretch_is_one_scan_whose_body_slices_each_leaf_once():
    """``apply`` holds ONE ``lax.scan``, over the periods of the longest
    periodic stretch; the whole stacks are its constants (never ``xs``), and
    a layer of its body takes its row out of each by one slice. The layers
    before and after it are traced in line."""
    from transformers import PretrainedConfig

    from vllm_tpu.models.registry import get_model_class
    from vllm_tpu.ops.attention import AttentionMetadata

    cfg = PretrainedConfig.from_dict(PUBLISHED_DEPTH)
    model = get_model_class(cfg)(cfg, dtype=jnp.float32)
    model.max_state_slots = 4
    params = jax.eval_shape(lambda: model.init_dummy_params(jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda x: x.shape[0], params["conv"]) == {
        "w_in": 18, "conv_w": 18, "w_out": 18}
    assert params["experts"]["we_gate"].shape == (22, 8, 64, 32)
    assert params["attn"]["wq"].shape[0] == 6 and params["dense"]["w1"].shape[0] == 2
    cache = jax.eval_shape(lambda: model.alloc_kv_cache(8, 16, jnp.float32))
    t, r = 16, 4
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    md = AttentionMetadata(
        positions=i32(t), slot_mapping=i32(t), block_tables=i32(r, 8), seq_lens=i32(r),
        query_start_loc=i32(r + 1), token_req_idx=i32(t), logits_indices=i32(r),
        num_seqs=i32(1), state_slots=i32(r))
    jaxpr = jax.make_jaxpr(model.apply)(params, cache, i32(t), md)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"
             and e.params["length"] == model.reps]
    assert len(scans) == 1 and model.reps == 4
    (scan,) = scans
    assert scan.params["num_carry"] == 3  # the residual, the pages, the tails
    body = scan.params["jaxpr"].jaxpr
    xs = body.invars[scan.params["num_consts"] + scan.params["num_carry"]:]
    assert [v.aval.shape for v in xs] == [()]  # the period's index alone
    # A period's four layers: four slices of a norm stack, three of a
    # convolution leaf, one of an attention leaf, and the experts whole.
    stacks = {v: v.aval.shape for v in body.invars[: scan.params["num_consts"]]
              if len(v.aval.shape) >= 2 and v.aval.shape[0] in (24, 18, 6, 22)}
    readers = lambda var: sum(var in e.invars for e in body.eqns)
    by_rows = {}
    for var, shape in stacks.items():
        by_rows.setdefault(shape[0], set()).add(readers(var))
    assert by_rows[18] == {3} and by_rows[6] == {1} and by_rows[24] == {4}


# ----------------------------------------------------------------------
# The reference against the family's published code
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def dense_sibling(tmp_path_factory):
    """``transformers``' Lfm2ForCausalLM at the tiny widths (its MLP width
    ``intermediate_size`` as given: ``block_auto_adjust_ff_dim`` false),
    every parameter drawn wide enough to matter, saved in the published
    layout."""
    torch = pytest.importorskip("torch")
    from transformers import Lfm2Config, Lfm2ForCausalLM

    c = TINY_LFM2_MOE
    cfg = Lfm2Config(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"], num_hidden_layers=8,
        num_attention_heads=c["num_attention_heads"],
        num_key_value_heads=c["num_key_value_heads"], max_position_embeddings=4096,
        norm_eps=c["norm_eps"], rope_theta=c["rope_theta"], conv_bias=False,
        conv_L_cache=c["conv_L_cache"], block_auto_adjust_ff_dim=False,
        layer_types=PUBLISHED_PATTERN[:8], tie_word_embeddings=True)
    torch.manual_seed(0)
    model = Lfm2ForCausalLM(cfg).to(torch.float32).eval()
    with torch.no_grad():
        for name, p in model.named_parameters():
            if name.endswith(("norm.weight", "layernorm.weight")):
                p.add_(torch.randn_like(p) * 0.3)
            elif "conv.conv" in name:
                p.copy_(torch.randn_like(p) * 0.6)
            else:
                p.mul_(6.0)  # the library draws at 0.02
    path = tmp_path_factory.mktemp("tiny_lfm2")
    model.save_pretrained(str(path), safe_serialization=True)
    return str(path), model


def test_the_reference_is_the_familys_dense_sibling_at_all_dense_depth(dense_sibling):
    """With ``num_dense_layers`` the depth, this family's layer IS
    ``transformers.models.lfm2``'s: the reference on that checkpoint's
    weights (loaded through ``hf_weight_map``, whose tree is the
    reference's) gives the library's logits. That holds the order of ``B, C,
    x`` in ``in_proj``'s rows, the taps' order (the last on the current
    token), the norms a head BEFORE the rope and ``embedding_norm`` after
    the last layer to the family's own code. The sparse block has no such
    oracle here: ``lfm2_moe`` is not in ``transformers`` 4.57, and it rests
    on the catalog row's keys and the configuration's ``assumed``."""
    import torch
    from transformers import PretrainedConfig

    from vllm_tpu.models.registry import get_model_class

    path, hf = dense_sibling
    config = {**TINY_LFM2_MOE, "num_hidden_layers": 8, "num_dense_layers": 8}
    cfg = PretrainedConfig.from_dict(config)
    model = get_model_class(cfg)(cfg, dtype=jnp.float32)
    params = model.load_params(path)
    dummy = jax.eval_shape(lambda: model.init_dummy_params(jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), params) == jax.tree.map(
        lambda x: (x.shape, x.dtype), dummy)
    assert set(params) == {"embed", "final_norm", "norms", "conv", "attn", "dense"}
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 240, 37).tolist()
    with torch.no_grad():
        want = hf(torch.tensor([prompt])).logits[0].numpy()
    got = np.asarray(REF.forward(config, params, prompt))
    assert np.abs(want).max() > 1.0  # the logits say something
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())
    # And each of the four can be told: another order of B, C, x, the taps
    # turned round, the rope before the norm or the last norm left out reads
    # far outside that tolerance.
    swapped = {**params, "conv": {**params["conv"], "conv_w": params["conv"]["conv_w"][:, ::-1]}}
    assert np.abs(np.asarray(REF.forward(config, swapped, prompt)) - want).max() > 0.05


def test_hf_weight_map_names_every_leaf_of_the_sparse_model_by_the_familys_names():
    from transformers import PretrainedConfig

    from vllm_tpu.models.registry import get_model_class

    cfg = PretrainedConfig.from_dict(TINY_LFM2_MOE)
    model = get_model_class(cfg)(cfg, dtype=jnp.float32)
    m = model.hf_weight_map()
    assert m["model.embedding_norm.weight"] == ("final_norm", False)
    assert m["model.layers.2.self_attn.q_layernorm.weight"] == ("attn.q_norm.0", False)
    assert m["model.layers.6.self_attn.out_proj.weight"] == ("attn.wo.1", True)
    assert m["model.layers.3.conv.in_proj.weight"] == ("conv.w_in.2", True)
    assert m["model.layers.3.conv.conv.weight"] == ("conv.conv_w.2", False)
    assert m["model.layers.1.feed_forward.w2.weight"] == ("dense.w2.1", True)
    assert m["model.layers.2.feed_forward.gate.weight"] == ("moe.router.0", True)
    assert m["model.layers.2.feed_forward.expert_bias"] == ("moe.router_bias.0", False)
    assert m["model.layers.11.feed_forward.experts.7.w3.weight"] == ("experts.we_up.9.7", True)
    assert "lm_head.weight" not in m and "model.layers.0.feed_forward.gate.weight" not in m
    shapes = jax.eval_shape(lambda: model.init_dummy_params(jax.random.PRNGKey(0)))
    filled = {}
    for ours, _ in m.values():
        *path, last = ours.split(".")
        if last.isdigit():
            if path[-1].isdigit():  # experts.<leaf>.<row>.<expert>
                filled.setdefault(".".join(path[:-1]), set()).add((int(path[-1]), int(last)))
            else:
                filled.setdefault(".".join(path), set()).add(int(last))
        else:
            filled[ours] = None
    for kind, leaves in shapes.items():
        if not isinstance(leaves, dict):
            assert kind in filled
            continue
        for name, leaf in leaves.items():
            got = filled[f"{kind}.{name}"]
            want = leaf.shape[0] * (leaf.shape[1] if kind == "experts" else 1)
            assert len(got) == want, (kind, name)


# ----------------------------------------------------------------------
# The packed page of 64-lane heads
# ----------------------------------------------------------------------

def test_the_decode_kernel_serves_heads_of_64_lanes_from_the_packed_page():
    """32 query heads over 8 KV heads of 64 lanes, a KV head's key and value
    side by side in one 128-lane row of the page, decode-only rows (some of
    the bucket dead): the answer is the reference's. A row of the kernel's
    output is whole lane tiles, of which the wrapper returns the head's 64
    (the chip's compiler copies no row of 64 lanes out of the kernel:
    ``tests/test_tpu_aot_compile.py``)."""
    from tests.models.test_decode_attention import _decode_case, dispatched
    from vllm_tpu.ops.attention import packed_kv_layout, ref_ragged_paged_attention

    rng = np.random.default_rng(9)
    kh, h, d, bs = 8, 32, 64, 16
    assert packed_kv_layout(d)
    q, kv_cache, md = _decode_case(rng, [60, 9, 41, 130, 17], kh, h, d, bs, num_blocks=64,
                                   r_pad=8)
    assert kv_cache.shape == (1, 64, 16, 8, 128)
    scale = d ** -0.5
    got, names = dispatched(q, kv_cache, md, scale)
    assert names == ["rpa_decode_kernel"]
    want = ref_ragged_paged_attention(q, kv_cache, jnp.int32(0), md, scale)
    assert got.shape == want.shape == q.shape == (8, 32, 64)
    np.testing.assert_allclose(np.asarray(got)[:5], np.asarray(want)[:5], rtol=2e-4, atol=2e-4)
    assert not np.asarray(got)[5:].any()  # the dead rows come back as zeros
