"""``benchmark/reference/laguna.py`` against the program at a tiny size on
the CPU: the weights it draws, the shares' sum, and the comparison the
benchmark's checker makes of a served answer: prefill in chunks, then
decoding through the GROUPED cache, with prompts longer than the tiny
window of 32 tokens, so that the window groups HAVE given pages back before
the tokens compared are made."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.checker import compare
from benchmark.harness.manifest import BENCH_DIR, load_module

from .tiny_laguna import TINY_LAGUNA, model_dir, share

REF = load_module(BENCH_DIR / "reference" / "laguna.py")
CONFIGS = {"uncut": TINY_LAGUNA, "one share of four": share(2)}


def flat(tree, prefix=""):
    for key, val in sorted(tree.items()):
        if isinstance(val, dict):
            yield from flat(val, f"{prefix}{key}.")
        else:
            yield prefix + key, val


def test_the_reference_imports_nothing_of_the_program():
    source = (BENCH_DIR / "reference" / "laguna.py").read_text()
    assert "import vllm_tpu" not in source and "from vllm_tpu" not in source


@pytest.mark.parametrize("which", list(CONFIGS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_seeded_weights_are_the_dummy_load_leaf_by_leaf(dtype, which):
    from transformers import PretrainedConfig

    from vllm_tpu.models.registry import get_model_class

    config = CONFIGS[which]
    cfg = PretrainedConfig.from_dict(config)
    model = get_model_class(cfg)(cfg, dtype=jnp.dtype(dtype))
    served = dict(flat(model.init_dummy_params(jax.random.PRNGKey(21))))
    ours = dict(flat(REF.seeded_weights(config, 21, jnp.dtype(dtype))))
    assert sorted(served) == sorted(ours)
    for name, leaf in served.items():
        assert leaf.shape == ours[name].shape and leaf.dtype == ours[name].dtype, name
        np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                      np.asarray(ours[name], np.float32), err_msg=name)
    # The kinds differ in SHAPE: 8 query heads of 16 in a window layer, 6
    # in a full one, over 2 KV heads in both.
    assert served["periods.window.wq"].shape == (2, 3, 64, 128)
    assert served["periods.full.wq"].shape == (2, 64, 96)
    assert served["periods.full.wk"].shape == served["periods.window.wk"].shape[:1] + (64, 32)


def test_the_two_rope_tables_are_the_programs():
    """YaRN over half a head's lanes and plain rope over all of them, the
    reference's own formulae against ``layers/rotary.py``'s tables."""
    from transformers import PretrainedConfig

    from vllm_tpu.models.laguna import LagunaForCausalLM

    cfg = PretrainedConfig.from_dict(TINY_LAGUNA)
    model = LagunaForCausalLM(cfg, dtype=jnp.float32)
    for kind, lanes in (("full_attention", 8), ("sliding_attention", 16)):
        cos, sin, r = REF.rope_tables(TINY_LAGUNA, kind, 300)
        rope = model.rope_of[kind]
        assert r == lanes == rope.rotary_dim
        np.testing.assert_allclose(np.asarray(rope.cos[:300]), np.asarray(cos), atol=2e-6)
        np.testing.assert_allclose(np.asarray(rope.sin[:300]), np.asarray(sin), atol=2e-6)
    # YaRN's factor is on the full layers' table alone.
    assert np.isclose(float(model.rope_of["full_attention"].cos[0, 0]), 1.4158883, atol=1e-6)
    assert float(model.rope_of["sliding_attention"].cos[0, 0]) == 1.0


def test_the_shares_of_the_reference_sum_to_its_uncut_layer():
    """The share test of the guide's section 4: 16 experts over ``ep_size``
    4. The four shares' routed parts, with the shared expert counted once,
    add up to what the uncut reference gives for the whole layer."""
    s = REF.sizes(TINY_LAGUNA)
    w = REF.seeded_weights(TINY_LAGUNA, 9, jnp.float32)
    lw = REF.layer_weights(TINY_LAGUNA, w, 3)
    h = jnp.asarray(np.random.default_rng(0).normal(size=(12, 64)), jnp.float32)
    full, weight = REF.routed(h, lw["ffn"], lw["experts"], s)
    assert np.allclose(np.asarray(weight).sum(-1), 2.5, atol=1e-5)
    assert (np.asarray(weight) > 0).sum(-1).tolist() == [4] * 12
    only_shared = REF.swiglu(h, lw["ffn"]["ws_gate"], lw["ffn"]["ws_up"], lw["ffn"]["ws_down"])
    parts = []
    for rank in range(4):
        sr = REF.sizes(share(rank))
        assert (sr["E"], sr["held"], sr["first"]) == (16, 4, 4 * rank)
        mine = jax.tree.map(lambda x: x[4 * rank: 4 * rank + 4], lw["experts"])
        parts.append(REF.routed(h, lw["ffn"], mine, sr)[0])
    np.testing.assert_allclose(np.asarray(sum(parts) - 3 * only_shared),
                               np.asarray(full), atol=1e-5)


def test_the_programs_shares_sum_to_the_uncut_reference_layer():
    """The same sum with the PROGRAM's layer as the shares: ``layers/moe.py::
    share_of_sparse_layer`` under each of the four ``first_expert``."""
    from vllm_tpu.layers.moe import share_of_sparse_layer

    s = REF.sizes(TINY_LAGUNA)
    w = REF.seeded_weights(TINY_LAGUNA, 9, jnp.float32)
    lw = REF.layer_weights(TINY_LAGUNA, w, 6)
    h = jnp.asarray(np.random.default_rng(1).normal(size=(10, 64)), jnp.float32)
    full, _ = REF.routed(h, lw["ffn"], lw["experts"], s)
    only_shared = REF.swiglu(h, lw["ffn"]["ws_gate"], lw["ffn"]["ws_up"], lw["ffn"]["ws_down"])
    live = jnp.ones(10, bool)
    parts, hits = [], 0
    for rank in range(4):
        mine = jax.tree.map(lambda x: x[4 * rank: 4 * rank + 4], lw["experts"])
        out, counts = share_of_sparse_layer(
            h, lw["ffn"], mine, None, live,
            select=dict(top_k=4, sigmoid=True, method="noaux_tc", renormalize=True,
                        scaling=2.5),
            num_experts=4, first_expert=4 * rank, shared=True)
        parts.append(out)
        hits += int(counts.sum())
    assert hits == 10 * 4  # every pair lands in exactly one share
    np.testing.assert_allclose(np.asarray(sum(parts) - 3 * only_shared),
                               np.asarray(full), atol=2e-5)


def served_samples(tmp_path, seed, dtype="float32", lengths=(6, 23, 70, 130), config=None,
                   max_tokens=12, freed_at_least=1, **engine):
    """Greedy answers with logprobs in the shape the checker reads. The
    prompts of 70 and 130 tokens are prefilled in chunks of 32 beside the
    others' decode rows and pass the window of 32: their window groups'
    first pages are back in the pool before the first generated token."""
    from vllm_tpu import LLM, SamplingParams

    llm = LLM(model=model_dir(tmp_path, **(config or TINY_LAGUNA)), load_format="dummy",
              seed=seed, dtype=dtype, max_model_len=256, block_size=16,
              num_gpu_blocks_override=96, max_num_seqs=8, max_num_batched_tokens=32, **engine)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 240, n).tolist() for n in lengths]
    outs = llm.generate([{"prompt_token_ids": p} for p in prompts],
                        SamplingParams(temperature=0.0, max_tokens=max_tokens, logprobs=5,
                                       ignore_eos=True))
    manager = llm.llm_engine.engine_core.engine_core.scheduler.kv_cache_manager
    assert manager.num_groups == 4 and manager.window_pages_freed >= freed_at_least
    assert manager.get_num_free_blocks() == 95  # every group gave all back
    llm.shutdown()
    return [{"prompt": p, "tokens": list(o.outputs[0].token_ids),
             "top_logprobs": [{str(t): lp.logprob for t, lp in top.items()}
                              for top in o.outputs[0].logprobs]}
            for p, o in zip(prompts, outs)]


@pytest.mark.parametrize("which", list(CONFIGS))
def test_the_checker_finds_the_served_path_on_the_reference(tmp_path, which):
    """The unedited ``harness/checker.py`` over a served answer, float32
    against float32: the two differ in the order of sums only. Tolerance
    5e-4 on a logprob: float32 sums over 64 to 128 terms in another order
    differ by some 1e-6 of a logit of size 1, twelve layers and a softmax
    over 256 carry that to 1e-5..1e-4; an error of the mechanism (a page
    freed too early, a wrong table, the other rope) reads 0.01 to 1."""
    config = CONFIGS[which]
    samples = served_samples(tmp_path, 11, config=config)
    got = compare(config, REF, 11, samples, "float32")
    assert got["compared"] == 4 * 12 * 5 and got["argmax_differs"] == 0
    assert got["max_abs"] < 5e-4, got



def test_the_bfloat16_pass_is_the_same_formulae_rounded():
    """``compute`` (the witness of what rounding to the served type costs a
    seed's weights, and what ``harness/checker.py`` runs for a factor): in
    float32 every cast is the identity; in bfloat16 the logprobs stand a
    rounding's distance from the float32 pass (12 layers of width 64: a
    mean of 0.11-0.13 over two seeds at ``Wq``'s width 2, 0.07-0.08 at 1
    and 0.23-0.27 at 4; an error of a formula reads 1 and more), and more
    where ``Wq`` is drawn wider, which is why it is not drawn at
    Kimi-Linear's 4."""
    weights = REF.seeded_weights(TINY_LAGUNA, 5, jnp.bfloat16)
    tokens = np.random.default_rng(5).integers(0, 240, 90).tolist()
    plain = REF.forward(TINY_LAGUNA, weights, tokens)
    again = REF.forward(TINY_LAGUNA, weights, tokens, jnp.float32)
    np.testing.assert_array_equal(plain, again)
    logp = lambda logits: np.asarray(jax.nn.log_softmax(logits, axis=-1))
    low = REF.forward(TINY_LAGUNA, weights, tokens, jnp.bfloat16)
    assert low.dtype == np.float32
    apart = float(np.abs(logp(low) - logp(plain)).mean())
    assert 0.02 < apart < 0.3, apart
    (many,) = REF.forward_many(TINY_LAGUNA, weights, [tokens], compute=jnp.bfloat16)
    np.testing.assert_array_equal(many, low)
    wider = {**REF.DRAW_WIDTHS, "wq": 4.0}
    try:
        REF.DRAW_WIDTHS, narrow = wider, REF.DRAW_WIDTHS
        w4 = REF.seeded_weights(TINY_LAGUNA, 5, jnp.bfloat16)
    finally:
        REF.DRAW_WIDTHS = narrow
    apart4 = float(np.abs(logp(REF.forward(TINY_LAGUNA, w4, tokens, jnp.bfloat16))
                          - logp(REF.forward(TINY_LAGUNA, w4, tokens))).mean())
    assert apart4 > 1.4 * apart, (apart, apart4)
