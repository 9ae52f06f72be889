"""``benchmark/reference/mimo_v2.py`` against the program at a tiny size on
the CPU: the weights it draws, the shares' sum, and the comparison the
benchmark's checker makes of a served answer: prefill in chunks, then
decoding through the GROUPED cache of two page shapes (8 tokens of a
window group, 16 of the full group), with prompts longer than the tiny
window of 24 tokens and answers that cross several pages of both shapes,
so that the window groups HAVE given pages back and both kinds of table
have grown before the tokens compared are made."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.checker import compare
from benchmark.harness.manifest import BENCH_DIR, load_module

from .tiny_mimo_v2 import TINY_MIMO_V2, model_dir, share

REF = load_module(BENCH_DIR / "reference" / "mimo_v2.py")
CONFIGS = {"uncut": TINY_MIMO_V2, "one share of four": share(2)}


def flat(tree, prefix=""):
    for key, val in sorted(tree.items()):
        if isinstance(val, dict):
            yield from flat(val, f"{prefix}{key}.")
        else:
            yield prefix + key, val


def program(config, dtype=jnp.float32):
    from transformers import PretrainedConfig

    from vllm_tpu.models.registry import get_model_class

    cfg = PretrainedConfig.from_dict(config)
    return get_model_class(cfg)(cfg, dtype=jnp.dtype(dtype))


def test_the_reference_imports_nothing_of_the_program():
    source = (BENCH_DIR / "reference" / "mimo_v2.py").read_text()
    assert "import vllm_tpu" not in source and "from vllm_tpu" not in source


@pytest.mark.parametrize("which", list(CONFIGS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_seeded_weights_are_the_dummy_load_leaf_by_leaf(dtype, which):
    config = CONFIGS[which]
    served = dict(flat(program(config, dtype).init_dummy_params(jax.random.PRNGKey(21))))
    ours = dict(flat(REF.seeded_weights(config, 21, jnp.dtype(dtype))))
    assert sorted(served) == sorted(ours)
    for name, leaf in served.items():
        assert leaf.shape == ours[name].shape and leaf.dtype == ours[name].dtype, name
        np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                      np.asarray(ours[name], np.float32), err_msg=name)
    # The kinds differ in SHAPE: 4 KV heads in a window layer, 2 in a full
    # one; keys of 24 lanes, values of 16, 8 query heads in both.
    assert served["window.wk"].shape == (9, 64, 96) and served["full.wk"].shape == (3, 64, 48)
    assert served["window.wv"].shape == (9, 64, 64) and served["full.wv"].shape == (3, 64, 32)
    assert served["window.wq"].shape == (9, 64, 192) and served["window.wo"].shape == (9, 128, 64)
    # The two leaves that are no matrices stay float32, and only the window
    # layers have a sink.
    assert served["window.sinks"].shape == (9, 8) and "full.sinks" not in served
    assert served["window.sinks"].dtype == served["moe.router_bias"].dtype == jnp.float32
    held = config["n_routed_experts"]
    assert served["moe.router"].shape == (11, 64, 32)
    assert served["experts.we_down"].shape == (11, held, 32, 64)


def test_the_two_rope_tables_are_the_programs():
    """Plain rope over the first ``int(0.334 x 24)`` = 8 lanes, theta
    10,000,000 in a full layer and 10,000 in a window layer: the
    reference's own formula against ``layers/rotary.py``'s tables."""
    model = program(TINY_MIMO_V2)
    for kind in ("full", "window"):
        cos, sin, r = REF.rope_tables(TINY_MIMO_V2, kind, 300)
        rope = model.rope_of[kind]
        assert r == 8 == rope.rotary_dim
        np.testing.assert_allclose(np.asarray(rope.cos[:300]), np.asarray(cos), atol=2e-6)
        np.testing.assert_allclose(np.asarray(rope.sin[:300]), np.asarray(sin), atol=2e-6)
    assert not np.allclose(np.asarray(model.rope_of["full"].cos[:300]),
                           np.asarray(model.rope_of["window"].cos[:300]))


def test_the_dummy_draw_makes_the_sink_and_the_bias_matter():
    """What ``load_format=dummy`` must give the comparison to see: a sink
    that takes a tenth to a half of a window row's softmax over a whole
    window of keys, and a selection bias that changes some tenth of the
    choices (neither so large that it is all a row reads)."""
    s = REF.sizes(TINY_MIMO_V2)
    w = REF.seeded_weights(TINY_MIMO_V2, 3, jnp.float32)
    lw = REF.layer_weights(TINY_MIMO_V2, w, 2)
    h = jnp.asarray(np.random.default_rng(0).normal(size=(200, 64)), jnp.float32)
    # A row's scores are q k / sqrt(Dk) with q at width 2: deviation 2.
    q = (h @ lw["attn"]["wq"]).reshape(200, 8, 24)
    k = (h @ lw["attn"]["wk"]).reshape(200, 4, 24)
    scores = np.einsum("tad,sd->ats", np.asarray(q), np.asarray(k[:, 0])) / np.sqrt(24)
    assert 1.5 < scores.std() < 2.5
    # At the published window of 128 keys a row:
    lse = np.log(np.exp(scores[:, :, :128]).sum(-1))  # [heads, rows]
    share_of_sink = 1 / (1 + np.exp(lse - np.asarray(lw["attn"]["sinks"])[:, None]))
    assert 0.05 < np.median(share_of_sink) < 0.6, np.median(share_of_sink)
    # The bias: choices with it against choices without, at the published
    # 8 of 256 (the tiny router has 32 outputs; widen it for the count).
    rng = np.random.default_rng(1)
    sig = 1 / (1 + np.exp(-rng.normal(size=(400, 256))))
    bias = rng.normal(size=256) * REF.ROUTER_BIAS_WIDTH
    top = lambda x: np.argsort(-x, axis=-1)[:, :8]
    changed = np.mean([len(set(a) - set(b)) / 8 for a, b in zip(top(sig), top(sig + bias))])
    assert 0.03 < changed < 0.3, changed
    plain = REF.route(h, {**lw["ffn"], "router_bias": jnp.zeros(32)}, s)
    assert (np.asarray(REF.route(h, lw["ffn"], s)) > 0).sum(-1).tolist() == [4] * 200
    assert np.asarray(plain).shape == (200, 32)


def test_the_shares_of_the_reference_sum_to_its_uncut_layer():
    """The share test of the guide's section 4: 32 experts over ``ep_size``
    4, no shared expert. The four shares' routed parts add up to what the
    uncut reference gives for the whole layer."""
    s = REF.sizes(TINY_MIMO_V2)
    w = REF.seeded_weights(TINY_MIMO_V2, 9, jnp.float32)
    lw = REF.layer_weights(TINY_MIMO_V2, w, 3)
    h = jnp.asarray(np.random.default_rng(0).normal(size=(12, 64)), jnp.float32)
    full = REF.routed(h, lw["ffn"], lw["experts"], s)
    weight = np.asarray(REF.route(h, lw["ffn"], s))
    assert np.allclose(weight.sum(-1), 1.0, atol=1e-5)  # renormalised, scaled by 1
    assert (weight > 0).sum(-1).tolist() == [4] * 12
    parts = []
    for rank in range(4):
        sr = REF.sizes(share(rank))
        assert (sr["E"], sr["held"], sr["first"]) == (32, 8, 8 * rank)
        mine = jax.tree.map(lambda x: x[8 * rank: 8 * rank + 8], lw["experts"])
        parts.append(REF.routed(h, lw["ffn"], mine, sr))
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(full), atol=1e-5)


def test_the_programs_shares_sum_to_the_uncut_reference_layer():
    """The same sum with the PROGRAM's layer as the shares: ``layers/moe.py::
    share_of_sparse_layer`` under each of the four ``first_expert``, with
    the selection bias and no shared expert."""
    from vllm_tpu.layers.moe import share_of_sparse_layer

    s = REF.sizes(TINY_MIMO_V2)
    w = REF.seeded_weights(TINY_MIMO_V2, 9, jnp.float32)
    lw = REF.layer_weights(TINY_MIMO_V2, w, 6)
    h = jnp.asarray(np.random.default_rng(1).normal(size=(10, 64)), jnp.float32)
    full = REF.routed(h, lw["ffn"], lw["experts"], s)
    live = jnp.ones(10, bool)
    model = program(TINY_MIMO_V2)
    parts, hits = [], 0
    for rank in range(4):
        mine = jax.tree.map(lambda x: x[8 * rank: 8 * rank + 8], lw["experts"])
        out, counts = share_of_sparse_layer(
            h, lw["ffn"], mine, None, live, select=model.select,
            num_experts=8, first_expert=8 * rank, shared=False)
        parts.append(out)
        hits += int(counts.sum())
    assert hits == 10 * 4  # every pair lands in exactly one share
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(full), atol=2e-5)


def served_samples(tmp_path, seed, dtype="float32", lengths=(6, 23, 70, 130), config=None,
                   max_tokens=40, freed_at_least=1, **engine):
    """Greedy answers with logprobs in the shape the checker reads. The
    prompts of 70 and 130 tokens are prefilled in chunks of 32 beside the
    others' decode rows and pass the window of 24; forty generated tokens
    take five more pages of 8 in every window group and two or three of 16
    in the full group, and hand as many window pages back."""
    from vllm_tpu import LLM, SamplingParams

    llm = LLM(model=model_dir(tmp_path, **(config or TINY_MIMO_V2)), load_format="dummy",
              seed=seed, dtype=dtype, max_model_len=256, block_size=8,
              num_gpu_blocks_override=128, max_num_seqs=8, max_num_batched_tokens=32, **engine)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 240, n).tolist() for n in lengths]
    outs = llm.generate([{"prompt_token_ids": p} for p in prompts],
                        SamplingParams(temperature=0.0, max_tokens=max_tokens, logprobs=5,
                                       ignore_eos=True))
    manager = llm.llm_engine.engine_core.engine_core.scheduler.kv_cache_manager
    assert manager.num_groups == 4 and manager.window_pages_freed >= freed_at_least
    assert [m.block_size for m in manager.managers] == [16, 8, 8, 8]
    allocated = manager.pages_allocated_by_group()
    assert allocated[1] == allocated[2] == allocated[3] > allocated[0] > 0
    assert manager.get_num_free_blocks() == 127  # every group gave all back
    llm.shutdown()
    return [{"prompt": p, "tokens": list(o.outputs[0].token_ids),
             "top_logprobs": [{str(t): lp.logprob for t, lp in top.items()}
                              for top in o.outputs[0].logprobs]}
            for p, o in zip(prompts, outs)]


@pytest.mark.parametrize("which", list(CONFIGS))
def test_the_checker_finds_the_served_path_on_the_reference(tmp_path, which):
    """The unedited ``harness/checker.py`` over a served answer, float32
    against float32: the two differ in the order of sums only. Tolerance
    5e-4 on a logprob: float32 sums over 64 to 192 terms in another order
    differ by some 1e-6 of a logit of size 1, twelve layers and a softmax
    over 256 carry that to 1e-5..1e-4; an error of the mechanism (a page
    freed too early, a slot of the other page shape, the sink left out,
    the other rope) reads 0.01 to 1."""
    config = CONFIGS[which]
    samples = served_samples(tmp_path, 11, config=config, freed_at_least=20)
    got = compare(config, REF, 11, samples, "float32")
    assert got["compared"] == 4 * 40 * 5 and got["argmax_differs"] == 0
    assert got["max_abs"] < 5e-4, got


def test_the_served_path_through_the_kernels_interpreted(tmp_path, monkeypatch):
    """The same comparison with both Pallas kernels in interpret mode where
    the XLA path ran above (``VLLM_TPU_PALLAS_INTERPRET`` lets the dispatch
    take them off the TPU): the split page, the sink and the window inside
    the kernels, through the served path. Fewer tokens: interpreting is
    slow. Tolerance as above."""
    from vllm_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: False)
    real = attention.dispatch_ragged_attention
    traced = []
    monkeypatch.setattr(
        attention, "dispatch_ragged_attention",
        lambda *a, **kw: (traced.append(kw["kernel_suffix"]), real(
            *a, **{**kw, "allow_interpret": True}))[1])
    samples = served_samples(tmp_path, 12, lengths=(6, 41), max_tokens=10, freed_at_least=2)
    assert {"", "_window"} <= set(traced)
    got = compare(TINY_MIMO_V2, REF, 12, samples, "float32")
    assert got["argmax_differs"] == 0 and got["max_abs"] < 5e-4, got


def test_the_bfloat16_pass_is_the_same_formulae_rounded():
    """``compute`` (what ``harness/checker.py`` runs for a factor): in
    float32 every cast is the identity; in bfloat16 the logprobs stand a
    rounding's distance from the float32 pass (12 layers of width 64: a
    mean of some 0.1; an error of a formula reads 1 and more)."""
    weights = REF.seeded_weights(TINY_MIMO_V2, 5, jnp.bfloat16)
    tokens = np.random.default_rng(5).integers(0, 240, 90).tolist()
    plain = REF.forward(TINY_MIMO_V2, weights, tokens)
    again = REF.forward(TINY_MIMO_V2, weights, tokens, jnp.float32)
    np.testing.assert_array_equal(plain, again)
    logp = lambda logits: np.asarray(jax.nn.log_softmax(logits, axis=-1))
    low = REF.forward(TINY_MIMO_V2, weights, tokens, jnp.bfloat16)
    assert low.dtype == np.float32
    apart = float(np.abs(logp(low) - logp(plain)).mean())
    assert 0.02 < apart < 0.4, apart
    (many,) = REF.forward_many(TINY_MIMO_V2, weights, [tokens], compute=jnp.bfloat16)
    np.testing.assert_array_equal(many, low)
