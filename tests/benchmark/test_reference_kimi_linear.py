"""``benchmark/reference/kimi_linear.py`` against the program at a tiny
size on the CPU: the weights it draws, and the comparison the benchmark's
checker makes of a served answer (chunked prefill, then decoding through
the latent pages and the state slots), uncut and as one expert share."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.checker import compare
from benchmark.harness.manifest import BENCH_DIR, load_module

from .tiny_kimi_linear import TINY_KIMI, model_dir, share

REF = load_module(BENCH_DIR / "reference" / "kimi_linear.py")
CONFIGS = {"uncut": TINY_KIMI, "one share of four": share(2)}


def flat(tree, prefix=""):
    for key, val in sorted(tree.items()):
        if isinstance(val, dict):
            yield from flat(val, f"{prefix}{key}.")
        else:
            yield prefix + key, val


def test_the_reference_imports_nothing_of_the_program():
    source = (BENCH_DIR / "reference" / "kimi_linear.py").read_text()
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
    # The decays stay float32 whatever is served, and are no random draw.
    for name in served:
        if name.endswith(("a_log", "dt_bias", "router_bias")):
            assert served[name].dtype == jnp.float32, name
    for name, leaf in served.items():
        assert leaf.shape == ours[name].shape and leaf.dtype == ours[name].dtype, name
        np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                      np.asarray(ours[name], np.float32), err_msg=name)


def test_the_dummy_decays_neither_forget_at_once_nor_hold_for_ever():
    """The range the reference's docstring states, read off the draw: the
    decay a token of a channel, at the projection's median input."""
    w = REF.seeded_weights(TINY_KIMI, 1, jnp.float32)["single"]["0"]["kda"]
    a = -np.exp(np.asarray(w["a_log"]))[:, None] * np.log1p(
        np.exp(np.asarray(w["dt_bias"]))).reshape(len(w["a_log"]), -1)
    assert np.isclose(a.max(), -1e-3, rtol=1e-3) and np.isclose(a.min(), -1.6, rtol=1e-3)


def test_the_shares_of_the_reference_sum_to_its_uncut_layer():
    """The reference is given the same share as the program: its four
    shares' parts, the shared expert counted once, are its uncut layer."""
    s = REF.sizes(TINY_KIMI)
    w = REF.seeded_weights(TINY_KIMI, 9, jnp.float32)
    lw = REF.layer_weights(TINY_KIMI, w, 3)
    h = jnp.asarray(np.random.default_rng(0).normal(size=(12, 64)), jnp.float32)
    full, weight = REF.routed(h, lw["ffn"], lw["experts"], s)
    assert np.allclose(np.asarray(weight).sum(-1), 2.446, atol=1e-5)
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


def served_samples(tmp_path, seed, dtype="float32", lengths=(6, 9, 23, 70), config=None,
                   max_tokens=12, **engine):
    """Greedy answers with logprobs in the shape the checker reads; the
    70-token prompt is prefilled over three steps beside the others'
    decode rows, so the carried state and the latent pages are both read
    back."""
    from vllm_tpu import LLM, SamplingParams

    llm = LLM(model=model_dir(tmp_path, **(config or TINY_KIMI)), load_format="dummy",
              seed=seed, dtype=dtype, max_model_len=128, block_size=16,
              num_gpu_blocks_override=64, max_num_seqs=8, max_num_batched_tokens=32, **engine)
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


@pytest.mark.parametrize("which", list(CONFIGS))
def test_the_checker_finds_the_served_path_on_the_reference(tmp_path, which):
    """The unedited ``harness/checker.py`` over a served answer, float32
    against float32: the two differ in the order of sums only."""
    config = CONFIGS[which]
    samples = served_samples(tmp_path, 11, config=config)
    got = compare(config, REF, 11, samples, "float32")
    assert got["compared"] == 4 * 12 * 5 and got["argmax_differs"] == 0
    assert got["max_abs"] < 5e-4, got
