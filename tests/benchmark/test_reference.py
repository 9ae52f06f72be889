"""The program's logits against the benchmark's plain references, at a
tiny size on the CPU: prefill, then decoding through the cache."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.manifest import BENCH_DIR, load_module

from .tiny import TINY, write_model_dir

# float32 program against float32 reference on the CPU: the two differ in
# the order of float32 sums only (the program batches and pages), a few 1e-6 relative on logits of magnitude 1-3.
TOL = 2e-4


@pytest.mark.parametrize("family", ["llama_dense"])
def test_program_matches_reference(family, tmp_path):
    from vllm_tpu import LLM, SamplingParams

    seed = 11
    ref = load_module(BENCH_DIR / "reference" / f"{family}.py")
    cfg = TINY[family]
    llm = LLM(model=str(write_model_dir(tmp_path / family, family)), load_format="dummy",
              seed=seed, dtype="float32", max_model_len=256, block_size=16,
              num_gpu_blocks_override=64, max_num_seqs=8, max_num_batched_tokens=64)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg["vocab_size"], n).tolist() for n in (5, 40, 100)]
    outs = llm.generate(
        [{"prompt_token_ids": p} for p in prompts],
        SamplingParams(temperature=0.0, max_tokens=12, logprobs=5, ignore_eos=True),
    )
    llm.shutdown()
    weights = ref.seeded_weights(cfg, seed, jnp.float32)
    worst = 0.0
    for p, o in zip(prompts, outs):
        answer = list(o.outputs[0].token_ids)
        logits = ref.forward(cfg, weights, p + answer[:-1])
        logp = np.asarray(jax.nn.log_softmax(logits[len(p) - 1:], axis=-1))
        # 100 prompt tokens with 64 tokens a step: the prefill is chunked,
        # and every generated token is decoded through the paged cache.
        for j, top in enumerate(o.outputs[0].logprobs):
            assert int(np.argmax(logp[j])) == answer[j]
            for tok, lp in top.items():
                worst = max(worst, abs(float(logp[j, tok]) - lp.logprob))
    assert worst < TOL, worst
