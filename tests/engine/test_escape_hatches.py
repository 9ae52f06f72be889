"""The kernel escape hatches pick their path when the engine is built.

``enable_sampler_kernel`` and ``enable_decode_attention`` are read once,
at construction; nothing flips them afterwards. Each hatch must route
every step to the other path (the runner's own counters say which one ran)
and serve the tokens the default engine serves, greedy and seeded alike.
Tiny model, CPU, Pallas kernels in interpret mode.
"""

from __future__ import annotations

import numpy as np
import pytest

from tests.models.utils import tiny_llama_dir
from vllm_tpu import LLM, SamplingParams

GREEDY = SamplingParams(temperature=0.0, max_tokens=24, ignore_eos=True)
SEEDED = SamplingParams(temperature=0.8, top_k=16, top_p=0.95, seed=5,
                        max_tokens=24, ignore_eos=True)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    return tiny_llama_dir(tmp_path_factory.mktemp("tiny_llama_hatch"))


def _serve(ckpt, **hatch):
    """(greedy tokens, seeded tokens, which paths ran) of one engine."""
    llm = LLM(
        model=ckpt, dtype="float32", max_model_len=128, block_size=16,
        num_gpu_blocks_override=64, max_num_seqs=4,
        max_num_batched_tokens=128, **hatch,
    )
    rng = np.random.default_rng(7)
    prompts = [{"prompt_token_ids": rng.integers(5, 120, size=n).tolist()}
               for n in (7, 4)]
    tokens = [[o.outputs[0].token_ids for o in llm.generate(prompts, sp)]
              for sp in (GREEDY, SEEDED)]
    runner = llm.llm_engine.engine_core.engine_core.executor.worker.runner
    ran = {
        "sampler_kernel": runner.sampler_kernel_launches > 0,
        "sampler_fallback": runner.sampler_fallback_rows > 0,
        "decode_attention": runner.decode_only_launches > 0,
    }
    return tokens, ran


@pytest.fixture(scope="module")
def default(ckpt):
    tokens, ran = _serve(ckpt)
    # The default engine takes every fast path this file switches off.
    assert ran == {"sampler_kernel": True, "sampler_fallback": False,
                   "decode_attention": True}
    return tokens


@pytest.mark.parametrize("hatch, off, on", [
    ({"enable_sampler_kernel": False}, "sampler_kernel", "sampler_fallback"),
    ({"enable_decode_attention": False}, "decode_attention", None),
], ids=["sampler_kernel", "decode_attention"])
def test_hatch_selects_the_other_path_and_serves_the_same_tokens(
        ckpt, default, hatch, off, on):
    tokens, ran = _serve(ckpt, **hatch)
    assert not ran[off]
    if on is not None:
        assert ran[on]
    assert tokens == default


@pytest.mark.parametrize("argv", [
    ["--num-decode-steps", "4"],
    ["--max-decode-steps-per-launch", "16"],
    ["--disable-dynamic-decode"],
], ids=lambda argv: argv[0])
def test_a_removed_flag_is_refused_by_name(argv, capsys):
    """The flags of the decode drivers that left in PR 49 are errors of the
    `serve` command's parser, not options it accepts and ignores."""
    from vllm_tpu.entrypoints.cli.main import main

    with pytest.raises(SystemExit) as exc:
        main(["serve", "some-model", *argv])
    assert exc.value.code == 2
    assert f"unrecognized arguments: {argv[0]}" in capsys.readouterr().err
