"""``benchmark/reference/lfm2_moe.py`` against the program at a tiny size on
the CPU: the weights it draws, what its docstring says of them, and the
comparison the benchmark's checker makes of a served answer (chunked
prefill, then decoding through the attention layers' pages and the
convolution layers' tail slots)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.checker import compare
from benchmark.harness.manifest import BENCH_DIR, load_module

from .tiny_lfm2_moe import TINY_LFM2_MOE, model_dir

REF = load_module(BENCH_DIR / "reference" / "lfm2_moe.py")


def flat(tree, prefix=""):
    for key, val in sorted(tree.items()):
        if isinstance(val, dict):
            yield from flat(val, f"{prefix}{key}.")
        else:
            yield prefix + key, val


def test_the_reference_imports_nothing_of_the_program_and_sums_three_shifted_products():
    source = (BENCH_DIR / "reference" / "lfm2_moe.py").read_text()
    assert "import vllm_tpu" not in source and "from vllm_tpu" not in source
    code = source[source.index("from __future__"):]
    assert "vllm_tpu/ops" not in code and "pallas" not in code  # names no kernel
    assert "padded[j: j + t]" in code and 'default_matmul_precision("highest")' in code
    assert "jax.lax.scan(one" in code  # the experts one at a time over every row


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_seeded_weights_are_the_dummy_load_leaf_by_leaf(dtype):
    from transformers import PretrainedConfig

    from vllm_tpu.models import lfm2_moe
    from vllm_tpu.models.registry import get_model_class

    cfg = PretrainedConfig.from_dict(TINY_LFM2_MOE)
    model = get_model_class(cfg)(cfg, dtype=jnp.dtype(dtype))
    served = dict(flat(model.init_dummy_params(jax.random.PRNGKey(21))))
    ours = dict(flat(REF.seeded_weights(TINY_LFM2_MOE, 21, jnp.dtype(dtype))))
    assert sorted(served) == sorted(ours)
    assert served["moe.router_bias"].dtype == jnp.float32
    for name, leaf in served.items():
        assert leaf.shape == ours[name].shape and leaf.dtype == ours[name].dtype, name
        np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                      np.asarray(ours[name], np.float32), err_msg=name)
    # One table of what is not drawn plainly, the program's and the reference's.
    assert lfm2_moe.LEAF_IDS == REF.LEAF_IDS
    assert (lfm2_moe.Q_NORM_DRAW, lfm2_moe.ROUTER_BIAS_DRAW, lfm2_moe.RENORM_GUARD) == (
        REF.Q_NORM_DRAW, REF.ROUTER_BIAS_DRAW, REF.RENORM_GUARD)


def test_the_draw_has_a_spread_where_a_silent_zero_would_hide_a_fault():
    """The selection bias is not zeros (the choice by ``s + b`` is not the
    choice by ``s``), all three taps matter alike, the query's norm a head
    is the constant that makes a few keys carry a row."""
    w = REF.seeded_weights(TINY_LFM2_MOE, 4, jnp.float32)
    bias = np.asarray(w["moe"]["router_bias"], np.float64)
    assert bias.shape == (10, 8) and 0.05 < bias.std() < 0.15 and -0.65 < bias.mean() < -0.55
    taps = np.asarray(w["conv"]["conv_w"], np.float64)  # [Lc, K, D]
    assert taps.shape == (9, 3, 64)
    for j in range(3):
        assert 0.45 < taps[:, j].std() < 0.7, j  # 1 / sqrt(3)
    assert np.all(np.asarray(w["attn"]["q_norm"]) == 2.0)
    assert np.all(np.asarray(w["attn"]["k_norm"]) == 1.0)
    tokens = np.random.default_rng(0).integers(0, 240, 48).tolist()
    s = REF.sizes(TINY_LFM2_MOE)
    # With the bias the choice differs from the choice by the scores alone.
    h = jnp.asarray(np.random.default_rng(1).normal(size=(64, 64)), jnp.float32)
    lw = REF.layer_weights(w, s, 5)
    with_b = np.asarray(REF.router_weights(h, lw, s)) > 0
    without = np.asarray(REF.router_weights(h, {**lw, "router_bias": 0 * lw["router_bias"]}, s)) > 0
    assert 0.02 < (with_b != without).mean() < 0.5
    full = np.asarray(REF.forward(TINY_LFM2_MOE, w, tokens))
    assert 0.7 < full.std() < 1.5  # logits one wide: the head is the embedding


def test_every_mixer_and_both_feed_forwards_add_to_the_logits():
    w = REF.seeded_weights(TINY_LFM2_MOE, 2, jnp.float32)
    tokens = np.random.default_rng(0).integers(0, 240, 48).tolist()
    full = np.asarray(REF.forward(TINY_LFM2_MOE, w, tokens))
    for kind, leaf in (("conv", "w_out"), ("attn", "wo"), ("dense", "w2"),
                       ("experts", "we_down")):
        gone = {**w, kind: {**w[kind], leaf: 0 * w[kind][leaf]}}
        assert np.abs(np.asarray(REF.forward(TINY_LFM2_MOE, gone, tokens)) - full).mean() > 0.1, kind


def test_the_head_in_blocks_is_the_head(monkeypatch):
    w = REF.seeded_weights(TINY_LFM2_MOE, 2, jnp.float32)
    tokens = list(range(20))
    whole = np.asarray(REF.forward(TINY_LFM2_MOE, w, tokens))
    monkeypatch.setattr(REF, "HEAD_BLOCK", 96)  # 256 rows in 3 uneven blocks
    np.testing.assert_allclose(np.asarray(REF.forward(TINY_LFM2_MOE, w, tokens)), whole,
                               atol=1e-5)  # a product with the embedding's rows, not its columns
    many = REF.forward_many(TINY_LFM2_MOE, w, [tokens[:7], tokens])
    np.testing.assert_allclose(many[0], whole[:7], atol=1e-5)
    assert many[0].shape == (7, 256) and many[1].shape == (20, 256)


def served_samples(tmp_path, seed, dtype="float32", lengths=(6, 9, 23, 70), max_tokens=12,
                   **engine):
    """Greedy answers with logprobs in the shape the checker reads; the
    70-token prompt is prefilled over three steps beside the others' decode
    rows, so the carried tail and the pages are both read back."""
    from vllm_tpu import LLM, SamplingParams

    llm = LLM(model=model_dir(tmp_path), load_format="dummy", seed=seed, dtype=dtype,
              max_model_len=128, block_size=16, num_gpu_blocks_override=64, max_num_seqs=8,
              max_num_batched_tokens=32, **engine)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 240, n).tolist() for n in lengths]
    outs = llm.generate([{"prompt_token_ids": p} for p in prompts],
                        SamplingParams(temperature=0.0, max_tokens=max_tokens, logprobs=5,
                                       ignore_eos=True))
    llm.shutdown()
    return [{"prompt": p, "tokens": list(o.outputs[0].token_ids),
             "top_logprobs": [{str(t): lp.logprob for t, lp in top.items()}
                              for top in o.outputs[0].logprobs]}
            for p, o in zip(prompts, outs)]


def test_the_checker_finds_the_served_path_on_the_reference(tmp_path):
    """The unedited ``harness/checker.py`` over a served answer, float32
    against float32: the two differ in the order of sums only."""
    samples = served_samples(tmp_path, 11)
    got = compare(TINY_LFM2_MOE, REF, 11, samples, "float32")
    assert got["compared"] == 4 * 12 * 5 and got["argmax_differs"] == 0
    assert got["max_abs"] < 5e-4, got


def test_the_second_pass_in_the_served_type_reads_what_rounding_costs(tmp_path):
    """Served in bfloat16, with the checker's factors: the reference's own
    bfloat16 pass (``compute``) stands as far from the float32 pass as the
    served answer does, within a factor of two at this size."""
    samples = served_samples(tmp_path, 12, dtype="bfloat16")
    got = compare(TINY_LFM2_MOE, REF, 12, samples, "bfloat16", factors=True)
    assert got["lower_p50_abs"] > 0 and 0.5 < got["p50_factor"] < 2.0, got
    assert 0.5 < got["mean_factor"] < 2.0
