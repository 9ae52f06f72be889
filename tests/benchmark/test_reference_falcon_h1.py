"""``benchmark/reference/falcon_h1.py`` against the program at a tiny size
on the CPU: the weights it draws, what its docstring says of them, and the
comparison the benchmark's checker makes of a served answer (chunked
prefill, then decoding through the pages and the state slots of every
layer)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.checker import compare
from benchmark.harness.manifest import BENCH_DIR, load_module

from .tiny_falcon_h1 import MULTIPLIERS, TINY_FALCON_H1, model_dir

REF = load_module(BENCH_DIR / "reference" / "falcon_h1.py")


def flat(tree, prefix=""):
    for key, val in sorted(tree.items()):
        if isinstance(val, dict):
            yield from flat(val, f"{prefix}{key}.")
        else:
            yield prefix + key, val


def test_the_reference_imports_nothing_of_the_program_and_is_a_recurrence_a_token():
    source = (BENCH_DIR / "reference" / "falcon_h1.py").read_text()
    assert "import vllm_tpu" not in source and "from vllm_tpu" not in source
    assert "vllm_tpu/ops" not in source.split('"""')[2]  # the code names no kernel
    assert "jax.lax.scan(token" in source and 'default_matmul_precision("highest")' in source
    assert "cumsum" not in source  # no chunked form: not the program's algebra


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_seeded_weights_are_the_dummy_load_leaf_by_leaf(dtype):
    from transformers import PretrainedConfig

    from vllm_tpu.models import falcon_h1
    from vllm_tpu.models.registry import get_model_class

    cfg = PretrainedConfig.from_dict(TINY_FALCON_H1)
    model = get_model_class(cfg)(cfg, dtype=jnp.dtype(dtype))
    served = dict(flat(model.init_dummy_params(jax.random.PRNGKey(21))))
    ours = dict(flat(REF.seeded_weights(TINY_FALCON_H1, 21, jnp.dtype(dtype))))
    assert sorted(served) == sorted(ours)
    for name in served:
        if name.endswith(("a_log", "dt_bias", "d_skip")):
            assert served[name].dtype == jnp.float32, name
    for name, leaf in served.items():
        assert leaf.shape == ours[name].shape and leaf.dtype == ours[name].dtype, name
        np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                      np.asarray(ours[name], np.float32), err_msg=name)
    # One table of widths, the program's and the reference's.
    assert falcon_h1.DRAW_WIDTHS == REF.DRAW_WIDTHS and falcon_h1.LEAF_IDS == REF.LEAF_IDS
    assert (falcon_h1.A_MAX, falcon_h1.DT_MIN, falcon_h1.DT_MAX, falcon_h1.CONV_BIAS_WIDTH) == (
        REF.A_MAX, REF.DT_MIN, REF.DT_MAX, REF.CONV_BIAS_WIDTH)


def test_every_multiplier_of_the_config_divides_some_leafs_width():
    """A leaf whose output a multiplier scales is drawn so much wider: what
    reaches the next operation is ``w`` wide whatever the multipliers."""
    named = {m for _, names in REF.DRAW_WIDTHS.values() for m in names}
    assert named == set(MULTIPLIERS)
    w = REF.seeded_weights(TINY_FALCON_H1, 4, jnp.float32)
    c = TINY_FALCON_H1
    std = lambda x: float(np.asarray(x, np.float64).std())
    assert std(w["embed"]) * c["embedding_multiplier"] * 8 == pytest.approx(1.0, rel=0.05)
    assert std(w["lm_head"]) * c["lm_head_multiplier"] * 8 == pytest.approx(2.5, rel=0.05)
    wk = w["layers"]["wk"]
    assert std(wk) * c["attention_in_multiplier"] * c["key_multiplier"] * 8 == pytest.approx(
        4.0, rel=0.05)
    w_in, at = np.asarray(w["layers"]["w_in"]), 0
    for (zone, width), m in zip(REF.sizes(c)["zones"], c["ssm_multipliers"]):
        got = w_in[:, :, at: at + width].std() * c["ssm_in_multiplier"] * m * 8
        assert got == pytest.approx(1.0, rel=0.15), zone
        at += width
    assert at == w_in.shape[-1]


def test_the_dummy_decays_neither_forget_at_once_nor_hold_for_ever():
    """The range the reference's docstring states, read off the draw: a
    head's memory 1 / (exp(A_log) softplus(dt_bias)) runs from a thousand
    tokens to a third of one."""
    w = REF.seeded_weights(TINY_FALCON_H1, 1, jnp.float32)["layers"]
    memory = 1 / (np.exp(np.asarray(w["a_log"][0])) * np.log1p(np.exp(np.asarray(w["dt_bias"][0]))))
    assert memory[0] == pytest.approx(1000, rel=1e-3) and memory[-1] == pytest.approx(
        1 / 3.2, rel=1e-3)
    assert np.all(np.diff(memory) < 0) and np.all(np.asarray(w["d_skip"]) == 1)
    assert 0.05 < np.asarray(w["conv_b"], np.float64).std() < 0.15


def test_the_logits_are_wide_enough_to_compare_and_every_mixer_adds_to_them():
    """At 1 / sqrt(fan_in) the published multipliers would leave every
    logprob at -log(V); here the logits are 2.5 wide, and taking either
    mixer's branch away moves them by much more than any tolerance."""
    w = REF.seeded_weights(TINY_FALCON_H1, 2, jnp.float32)
    tokens = np.random.default_rng(0).integers(0, 240, 48).tolist()
    full = np.asarray(REF.forward(TINY_FALCON_H1, w, tokens))
    assert 2.0 < full.std() < 3.2
    for key in ("ssm_out_multiplier", "attention_out_multiplier"):
        without = np.asarray(REF.forward({**TINY_FALCON_H1, key: 0.0}, w, tokens))
        assert np.abs(without - full).mean() > 0.3, key


def test_the_head_in_blocks_is_the_head(monkeypatch):
    w = REF.seeded_weights(TINY_FALCON_H1, 2, jnp.float32)
    tokens = list(range(20))
    whole = np.asarray(REF.forward(TINY_FALCON_H1, w, tokens))
    monkeypatch.setattr(REF, "HEAD_BLOCK", 96)  # 256 columns in 3 uneven blocks
    np.testing.assert_array_equal(np.asarray(REF.forward(TINY_FALCON_H1, w, tokens)), whole)
    many = REF.forward_many(TINY_FALCON_H1, w, [tokens[:7], tokens])
    np.testing.assert_allclose(many[0], whole[:7], atol=1e-5)
    assert many[0].shape == (7, 256) and many[1].shape == (20, 256)


def served_samples(tmp_path, seed, dtype="float32", lengths=(6, 9, 23, 70), max_tokens=12,
                   **engine):
    """Greedy answers with logprobs in the shape the checker reads; the
    70-token prompt is prefilled over three steps beside the others'
    decode rows, so the carried state and the pages are both read back."""
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
    against float32: the two differ in the order of sums and in the chunked
    rule against the recurrence only."""
    samples = served_samples(tmp_path, 11)
    got = compare(TINY_FALCON_H1, REF, 11, samples, "float32")
    assert got["compared"] == 4 * 12 * 5 and got["argmax_differs"] == 0
    assert got["max_abs"] < 5e-4, got


def test_the_second_pass_in_the_served_type_reads_what_rounding_costs(tmp_path):
    """Served in bfloat16, with the checker's factors: the reference's own
    bfloat16 pass (``compute``) stands as far from the float32 pass as the
    served answer does, within a factor of two at this size."""
    samples = served_samples(tmp_path, 12, dtype="bfloat16")
    got = compare(TINY_FALCON_H1, REF, 12, samples, "bfloat16", factors=True)
    assert got["lower_p50_abs"] > 0 and 0.5 < got["p50_factor"] < 2.0, got
    assert 0.5 < got["mean_factor"] < 2.0
