"""The async pipeline serves what the synchronous engine serves.

One launch decodes one position a row, and the async pipeline is what hides
the host between launches: the scheduler runs up to ``async_pipeline_depth``
steps ahead of the tokens it has seen, and a row whose last token is still
in flight is fed it on the device. Every scenario here runs at depth 1 and 3
against ``async_scheduling=False`` on one tiny checkpoint and must give the
same tokens, finish reasons and logprobs.
"""

from __future__ import annotations

import itertools

import numpy as np
import pytest

from tests.models.utils import tiny_llama_dir
from vllm_tpu import SamplingParams
from vllm_tpu.engine.arg_utils import EngineArgs
from vllm_tpu.engine.llm_engine import LLMEngine

SYNC = 0  # the "depth" that stands for async_scheduling=False


@pytest.fixture(scope="module")
def engines(tmp_path_factory):
    """One engine a pipeline depth, built on first use and kept: every
    scenario uses prompts and request ids of its own."""
    ckpt = tiny_llama_dir(tmp_path_factory.mktemp("tiny_llama_pipeline"))
    built: dict[int, LLMEngine] = {}

    def get(depth: int) -> LLMEngine:
        if depth not in built:
            config = EngineArgs(
                model=ckpt, dtype="float32", max_model_len=128, block_size=16,
                num_gpu_blocks_override=64, max_num_seqs=4,
                max_num_batched_tokens=128, async_scheduling=depth != SYNC,
            ).create_engine_config()
            if depth != SYNC:
                config.scheduler_config.async_pipeline_depth = depth
            built[depth] = LLMEngine(config)
        return built[depth]

    yield get
    for eng in built.values():
        eng.shutdown()


def _prompts(sizes, seed):
    rng = np.random.default_rng(seed)
    return [{"prompt_token_ids": rng.integers(5, 120, size=n).tolist()}
            for n in sizes]


def _run(eng: LLMEngine, name: str, requests, arrive_at=None):
    """Serve ``requests`` (prompt, params), request i added before step
    ``arrive_at[i]`` (all before the first by default); the finished
    outputs in request order."""
    arrive_at = arrive_at or [0] * len(requests)
    done = {}
    step = 0
    # Past the last finish the steps still in flight land and are dropped.
    while len(done) < len(requests) or eng.has_unfinished_requests():
        for i, (prompt, params) in enumerate(requests):
            if arrive_at[i] == step:
                eng.add_request(f"{name}-{i}", prompt, params)
        for out in eng.step():
            assert out.request_id not in done, out
            if out.finished:
                done[out.request_id] = out.outputs[0]
        step += 1
        assert step < 400, (name, sorted(done))
    return [done[f"{name}-{i}"] for i in range(len(requests))]


def _greedy(max_tokens, **kw):
    return SamplingParams(temperature=0.0, max_tokens=max_tokens,
                          ignore_eos=True, **kw)


def greedy(run):
    return run([(p, _greedy(21)) for p in _prompts((7, 13, 3), seed=0)])


def seeded(run):
    sp = SamplingParams(temperature=0.9, top_k=20, top_p=0.95, seed=7,
                        max_tokens=18, ignore_eos=True)
    return run([(p, sp) for p in _prompts((5, 9), seed=1)])


def max_tokens_met_exactly(run):
    """A length that no depth divides: the steps in flight past the last
    token are dropped, not served."""
    [out] = run([(p, _greedy(10)) for p in _prompts((6,), seed=2)])
    assert len(out.token_ids) == 10
    assert out.finish_reason == "length"
    return [out]


def logprobs_row_beside_a_plain_row(run):
    prompts = _prompts((4, 8), seed=3)
    outs = run([(prompts[0], _greedy(8, logprobs=2)),
                (prompts[1], _greedy(8))])
    assert len(outs[0].logprobs) == 8 and outs[1].logprobs is None
    return outs


def staggered_arrivals(run):
    """Prefills of late arrivals ride in steps where the early rows decode
    with their last tokens still in flight."""
    prompts = _prompts((9, 14, 5, 11), seed=4)
    return run([(p, _greedy(12)) for p in prompts], arrive_at=[0, 0, 3, 5])


def stop_token_with_steps_in_flight(run):
    """Row 0 meets a stop token while the scheduler is steps ahead of it
    and row 1 decodes on: nothing of row 0 after the stop is served."""
    prompts = _prompts((8, 6), seed=5)
    [free] = run([(prompts[0], _greedy(16))])
    # A token whose first appearance is a few steps in.
    at = next(i for i in range(3, 16) if free.token_ids[i]
              not in free.token_ids[:i])
    stop = free.token_ids[at]
    outs = run([
        (prompts[0], SamplingParams(temperature=0.0, max_tokens=16,
                                    stop_token_ids=[stop])),
        (prompts[1], _greedy(16)),
    ])
    assert outs[0].finish_reason == "stop" and outs[0].stop_reason == stop
    assert outs[0].token_ids[:at] == free.token_ids[:at]
    assert len(outs[0].token_ids) <= at + 1
    assert len(outs[1].token_ids) == 16
    return outs


SCENARIOS = [greedy, seeded, max_tokens_met_exactly,
             logprobs_row_beside_a_plain_row, staggered_arrivals,
             stop_token_with_steps_in_flight]


def _served(engines, scenario, depth):
    calls = itertools.count()

    def run(requests, arrive_at=None):
        name = f"{scenario.__name__}-{next(calls)}"
        return _run(engines(depth), name, requests, arrive_at)

    return scenario(run)


@pytest.fixture(scope="module")
def sync_served(engines):
    """What the synchronous engine serves, a scenario once."""
    served = {}

    def get(scenario):
        if scenario not in served:
            served[scenario] = _served(engines, scenario, SYNC)
        return served[scenario]

    return get


@pytest.mark.parametrize("depth", [1, 3])
@pytest.mark.parametrize("scenario", SCENARIOS, ids=lambda s: s.__name__)
def test_async_pipeline_serves_what_sync_serves(
        engines, sync_served, scenario, depth):
    want = sync_served(scenario)
    assert engines(depth).engine_core.engine_core._max_inflight == depth
    got = _served(engines, scenario, depth)
    assert [o.token_ids for o in got] == [o.token_ids for o in want]
    assert ([o.finish_reason for o in got]
            == [o.finish_reason for o in want])
    for g, w in zip(got, want):
        assert (g.logprobs is None) == (w.logprobs is None)
        for gl, wl in zip(g.logprobs or (), w.logprobs or ()):
            assert sorted(gl) == sorted(wl)
            for tok in gl:
                assert gl[tok].logprob == pytest.approx(
                    wl[tok].logprob, abs=1e-5)
