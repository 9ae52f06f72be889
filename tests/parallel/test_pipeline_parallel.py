"""Pipeline-parallel tests: the in-jit collective-permute microbatch
pipeline produces the same greedy tokens as the unpipelined engine.

Protocol of the reference's ``tests/distributed/test_pipeline_parallel.py``
(multi-device PP output == single-device output), realized as real SPMD on
the 8-device virtual CPU mesh (SURVEY §4).
"""

from __future__ import annotations

import numpy as np
import pytest

from tests.models.utils import tiny_llama_dir
from vllm_tpu import LLM, SamplingParams


@pytest.fixture(scope="module")
def tiny_llama(tmp_path_factory):
    # 4 layers so pp in {2, 4} divides; 4 kv heads for tp in {1, 2}.
    return tiny_llama_dir(
        tmp_path_factory.mktemp("tiny_llama_pp"),
        num_hidden_layers=4,
        num_key_value_heads=4,
    )


def _generate(model_dir: str, prompts, max_tokens=8, **kw):
    kwargs = dict(
        model=model_dir,
        dtype="float32",
        max_model_len=128,
        block_size=16,
        num_gpu_blocks_override=64,
        max_num_seqs=8,
        max_num_batched_tokens=128,
    )
    kwargs.update(kw)
    llm = LLM(**kwargs)
    params = SamplingParams(
        temperature=0.0, max_tokens=max_tokens, ignore_eos=True
    )
    outs = llm.generate([{"prompt_token_ids": p} for p in prompts], params)
    return [o.outputs[0].token_ids for o in outs]


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(5, 120, size=n).tolist() for n in (9, 13, 3, 6)]


@pytest.fixture(scope="module")
def ref_tokens(tiny_llama, prompts):
    return _generate(tiny_llama, prompts)


@pytest.mark.parametrize("pp,tp", [
    (2, 1),
    (4, 1),
    (2, 2),
])
def test_pp_greedy_parity(tiny_llama, prompts, ref_tokens, pp, tp):
    got = _generate(
        tiny_llama, prompts,
        pipeline_parallel_size=pp, tensor_parallel_size=tp,
    )
    assert got == ref_tokens


def test_pp_microbatch_counts(tiny_llama, prompts, ref_tokens):
    """More microbatches than stages still exact."""
    got = _generate(
        tiny_llama, prompts,
        pipeline_parallel_size=2, pipeline_microbatches=4,
    )
    assert got == ref_tokens


def test_pp_chunked_prefill(tiny_llama, prompts, ref_tokens):
    """Chunked prefill across pipelined steps (budget forces chunks)."""
    got = _generate(
        tiny_llama, prompts,
        pipeline_parallel_size=2, max_num_batched_tokens=16,
    )
    assert got == ref_tokens


def test_pp_rejects_unsupported_model(tmp_path_factory):
    import torch
    from transformers import Mamba2Config, Mamba2ForCausalLM

    torch.manual_seed(0)
    cfg = Mamba2Config(
        vocab_size=128, hidden_size=32, state_size=16, num_hidden_layers=2,
        conv_kernel=4, expand=2, n_groups=1, num_heads=4, head_dim=16,
        tie_word_embeddings=False,
    )
    path = str(tmp_path_factory.mktemp("mamba_pp"))
    Mamba2ForCausalLM(cfg).to(torch.float32).save_pretrained(
        path, safe_serialization=True
    )
    with pytest.raises(Exception, match="pipeline"):
        LLM(
            model=path, dtype="float32", max_model_len=64,
            num_gpu_blocks_override=8, pipeline_parallel_size=2,
        )
