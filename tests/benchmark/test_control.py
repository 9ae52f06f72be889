"""The control of ``correct``: the same comparison that passes the program
as configured must fail the program in the nearest lower precision (its own
fp8 KV cache, and its own int8 weights). Here at a
tiny size on the CPU; on the chip at the cells' own sizes the readings are
in PERF.md, and the limits in ``benchmark/cells/`` were set from those."""

from __future__ import annotations

import numpy as np
import pytest

from benchmark.harness.checker import compare
from benchmark.harness.manifest import BENCH_DIR, load_module

from .tiny import TINY, write_model_dir

SEEDS = (3, 4, 5)


def served(family, tmp_path, seed, **engine):
    """Greedy answers with logprobs from the program, in the shape the
    checker reads."""
    from vllm_tpu import LLM, SamplingParams

    cfg = TINY[family]
    llm = LLM(model=str(write_model_dir(tmp_path / family, family)), load_format="dummy",
              seed=seed, dtype="bfloat16", max_model_len=256, block_size=16,
              num_gpu_blocks_override=64, max_num_seqs=8, max_num_batched_tokens=64, **engine)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg["vocab_size"], n).tolist() for n in (24, 90)]
    outs = llm.generate([{"prompt_token_ids": p} for p in prompts],
                        SamplingParams(temperature=0.0, max_tokens=16, logprobs=5, ignore_eos=True))
    llm.shutdown()
    return [{"prompt": p, "tokens": list(o.outputs[0].token_ids),
             "top_logprobs": [{str(t): lp.logprob for t, lp in top.items()}
                              for top in o.outputs[0].logprobs]}
            for p, o in zip(prompts, outs)]


@pytest.mark.parametrize("family,control", [
    ("llama_dense", {"kv_cache_dtype": "fp8"}),
    ("llama_dense", {"quantization": "int8"}),
])
def test_the_lower_precision_is_told_from_the_configured_one(family, control, tmp_path):
    ref = load_module(BENCH_DIR / "reference" / f"{family}.py")
    sound, lower = [], []
    for seed in SEEDS:
        for engine, into in (({}, sound), (control, lower)):
            samples = served(family, tmp_path, seed, **engine)
            into.append(compare(TINY[family], ref, seed, samples, "bfloat16"))
    # The rule the limits on the chip were set by: the control's smallest
    # reading at least three times the sound runs' largest, for the median
    # and for the worst sequence's mean (the tail has too few numbers at
    # this size to be read).
    for key in ("p50_abs", "worst_sequence_mean_abs"):
        assert min(r[key] for r in lower) >= 3 * max(r[key] for r in sound), (key, sound, lower)
