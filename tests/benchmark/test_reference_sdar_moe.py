"""``benchmark/reference/sdar_moe.py`` against the program at a tiny size on
the CPU: the weights it draws, the row contract of ``forward_many``, and the
comparison the benchmark's checker makes of a served answer."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.checker import compare
from benchmark.harness.manifest import BENCH_DIR, load_module

from .tiny_sdar_moe import TINY_SDAR, model_dir

REF = load_module(BENCH_DIR / "reference" / "sdar_moe.py")
B = TINY_SDAR["block_length"]


def flat(tree, prefix=""):
    for key, val in sorted(tree.items()):
        if isinstance(val, dict):
            yield from flat(val, f"{prefix}{key}.")
        else:
            yield prefix + key, val


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_seeded_weights_are_the_dummy_load_leaf_by_leaf(dtype):
    from transformers import PretrainedConfig

    from vllm_tpu.models.registry import get_model_class

    cfg = PretrainedConfig.from_dict(TINY_SDAR)
    model = get_model_class(cfg)(cfg, dtype=jnp.dtype(dtype))
    served = dict(flat(model.init_dummy_params(jax.random.PRNGKey(21))))
    ours = dict(flat(REF.seeded_weights(TINY_SDAR, 21, jnp.dtype(dtype))))
    assert sorted(served) == sorted(ours)
    # No dense MLP stacks: every layer's MLP is the mixture of experts.
    assert not {"layers.wgate", "layers.wup", "layers.wdown"} & set(served)
    for name, leaf in served.items():
        assert leaf.shape == ours[name].shape and leaf.dtype == ours[name].dtype, name
        np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                      np.asarray(ours[name], np.float32), err_msg=name)


@pytest.mark.parametrize("length", [5, 8, 14])
def test_row_i_of_forward_many_is_the_pass_that_commits_position_i_plus_1(length):
    """Positions <= i clean, positions i + 1 .. to the end of that block
    masked, blocks to the left clean: worked out one pass at a time with
    ``block_forward``, against the (B + 1) streams of ``forward_many``."""
    weights = REF.seeded_weights(TINY_SDAR, 4, jnp.float32)
    seq = np.random.default_rng(length).integers(0, 240, length).tolist()
    rows = np.asarray(REF.forward_many(TINY_SDAR, weights, [seq, seq[:3]])[0])
    assert rows.shape == (length, TINY_SDAR["vocab_size"])
    for i in range(length):
        end = (i + 1) // B * B + B
        tokens = (seq + [0] * B)[:end]
        one = REF.block_forward(TINY_SDAR, weights, tokens, [p <= i for p in range(end)])
        np.testing.assert_allclose(rows[i], np.asarray(one)[i + 1], atol=2e-5, rtol=2e-5)


def served_samples(tmp_path, seed, dtype="float32", lengths=(6, 9, 23, 40), **engine):
    """Greedy answers with logprobs under the model's default rule
    (sequential, one token a pass), in the shape the checker reads."""
    from vllm_tpu import LLM, SamplingParams

    llm = LLM(model=model_dir(tmp_path), load_format="dummy", seed=seed, dtype=dtype,
              max_model_len=128, block_size=16, num_gpu_blocks_override=64, max_num_seqs=8,
              max_num_batched_tokens=32, **engine)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 240, n).tolist() for n in lengths]
    outs = llm.generate([{"prompt_token_ids": p} for p in prompts],
                        SamplingParams(temperature=0.0, max_tokens=12, logprobs=5, ignore_eos=True))
    llm.shutdown()
    return [{"prompt": p, "tokens": list(o.outputs[0].token_ids),
             "top_logprobs": [{str(t): lp.logprob for t, lp in top.items()}
                              for top in o.outputs[0].logprobs]}
            for p, o in zip(prompts, outs)]


def test_the_checker_finds_the_served_path_on_the_reference(tmp_path):
    """The unedited ``harness/checker.py`` over a served answer: prompts
    with ``P mod 4`` in 2, 1, 3, 0, the 40-token one prefilled in chunks;
    float32 against float32, so the two differ in the order of sums only."""
    samples = served_samples(tmp_path, 11)
    got = compare(TINY_SDAR, REF, 11, samples, "float32")
    assert got["compared"] == 4 * 12 * 5 and got["argmax_differs"] == 0
    assert got["max_abs"] < 5e-4, got
