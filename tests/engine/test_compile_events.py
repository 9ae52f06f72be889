"""What compiling costs, from JAX's own events (``tracing.CompileEvents``),
and a first use of a step program taken apart by them. CPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from vllm_tpu import tracing
from vllm_tpu.core.sched_output import SchedulerStats
from vllm_tpu.metrics.prometheus import PrometheusRegistry
from vllm_tpu.tracing import CompileEvents

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def test_a_fresh_jit_raises_all_three_stages_and_a_second_call_none():
    tracing.compiles.listen()
    tracing.compiles.listen()  # once a process, however often it is asked

    @jax.jit
    def fresh(x):
        return jnp.tanh(x) * 3.0 + 1.0

    x = jnp.arange(8.0)
    x.block_until_ready()
    before, heard = tracing.compiles.reading(), tracing.compiles.heard
    fresh(x).block_until_ready()
    first, heard_first = tracing.compiles.reading(), tracing.compiles.heard
    for stage, a, b in zip(("trace", "lower", "backend"), first, before):
        assert a > b, stage
    assert heard_first > heard
    fresh(x).block_until_ready()
    assert tracing.compiles.reading() == first
    assert tracing.compiles.heard == heard_first


def test_a_jitted_function_traced_inside_another_counts_once():
    """JAX reports the inner trace in full and then the outer one, which
    holds it: the sum of what it reports would count the inner twice."""
    tracing.compiles.listen()

    @jax.jit
    def inner(x):
        time.sleep(0.15)
        return x * 2.0

    @jax.jit
    def outer(x):
        time.sleep(0.05)
        return inner(x) + 1.0

    reported: dict[str, float] = {}

    def hear(event, duration, fun_name="", **kwargs):
        if event.endswith("jaxpr_trace_duration") and fun_name in ("inner", "outer"):
            reported[fun_name] = reported.get(fun_name, 0.0) + duration

    jax.monitoring.register_event_duration_secs_listener(hear)
    try:
        before = tracing.compiles.reading()[0]
        outer(jnp.arange(4.0)).block_until_ready()
        traced = tracing.compiles.reading()[0] - before
    finally:
        jax.monitoring.unregister_event_duration_listener(hear)
    assert reported["inner"] >= 0.15 and reported["outer"] >= 0.2
    # The outer trace and the small ones that follow it (the arange is
    # made before): not the 0.15 s of the inner one a second time.
    assert reported["outer"] <= traced < reported["outer"] + 0.1


def test_nested_and_following_traces_by_hand():
    """The same on a listener of its own, fed by hand: a trace that ends
    takes back the ones that began inside it, and only those."""
    ev = CompileEvents()
    name = "/jax/core/compile/jaxpr_trace_duration"
    ev.on_duration(name, 0.001, fun_name="before")  # ended long before
    time.sleep(0.05)
    began = time.perf_counter()
    ev.on_duration(name, 0.004, fun_name="child_a")
    ev.on_duration(name, 0.003, fun_name="child_b")
    # The parent began before both children and after "before" ended.
    held = time.perf_counter() - began + 0.02
    ev.on_duration(name, held, fun_name="parent")
    assert ev.seconds["trace"] == pytest.approx(0.001 + held)
    ev.on_duration("/jax/core/compile/backend_compile_duration", 2.0)
    ev.on_duration("/jax/some/other_duration", 5.0)
    ev.on_event("/jax/compilation_cache/cache_hits")
    ev.on_event("/jax/compilation_cache/compile_requests_use_cache")
    assert ev.reading() == (pytest.approx(0.001 + held), 0.0, 2.0, 1, 0)
    assert ev.heard == 6


SCRIPT = """
import json, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from vllm_tpu import tracing
from vllm_tpu.utils.compile_cache import enable_compilation_cache
tracing.compiles.listen()
enable_compilation_cache()
f = jax.jit(lambda x: jnp.sin(x) @ jnp.cos(x).T + 41.0)
f(jnp.ones((16, 16))).block_until_ready()
print("RESULT " + json.dumps(tracing.compiles.cache))
"""


def test_a_persistent_cache_gives_a_miss_and_then_a_hit_from_a_second_process(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    results = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", SCRIPT.format(root=REPO_ROOT)], env=env,
            capture_output=True, text=True, timeout=240)
        assert out.returncode == 0, out.stderr[-2000:]
        [line] = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")]
        results.append(json.loads(line[len("RESULT "):]))
    cold, warm = results
    assert cold["miss"] >= 1 and cold["hit"] == 0
    assert warm["hit"] >= 1 and warm["miss"] == 0
    assert warm["hit"] == cold["miss"]  # the same programs, now all there


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A tiny model that has served one batch, with the registry that saw
    every stats snapshot, and a way to serve the same batch again."""
    from tests.models.utils import tiny_llama_dir
    from vllm_tpu import LLM, SamplingParams

    llm = LLM(model=tiny_llama_dir(tmp_path_factory.mktemp("tiny_first_use")),
              dtype="float32", max_model_len=128, block_size=16,
              max_num_batched_tokens=128, max_num_seqs=4,
              num_gpu_blocks_override=64, async_scheduling=False)
    engine = llm.llm_engine
    reg = PrometheusRegistry()
    params = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)
    attached = []

    def run_batch(tag):
        engine.add_request(f"{tag}-a", {"prompt_token_ids": [11, 24, 9]}, params)
        engine.add_request(f"{tag}-b", {"prompt_token_ids": [1, 2, 3, 4, 5]}, params)
        while engine.has_unfinished_requests():
            outputs = engine.engine_core.get_output()
            processed = engine.output_processor.process_outputs(outputs.outputs)
            attached.append(outputs.scheduler_stats.compile_seconds is not None)
            reg.record(outputs.scheduler_stats, processed.iteration_stats)

    run_batch("one")
    runner = engine.engine_core.engine_core.executor.worker.runner
    return runner, reg, run_batch, attached


def _samples(text: str, family: str) -> dict[str, float]:
    return {line.split()[0]: float(line.split()[1]) for line in text.splitlines()
            if line.startswith(family)}


def test_the_four_stages_of_a_first_use_sum_to_its_seconds(served):
    runner, reg, _, _ = served
    assert runner.bucket_compiles == 2 and runner.first_use_s > 0
    stages = runner.first_use_stage_s
    assert set(stages) == {"trace", "lower", "backend", "run"}
    assert sum(stages.values()) == pytest.approx(runner.first_use_s, rel=0.01)
    assert all(stages[s] > 0 for s in ("trace", "lower", "backend"))
    got = _samples(reg.render(), "vllm:step_first_use_stage_seconds_total{")
    assert len(got) == 4
    total = _samples(reg.render(), "vllm:step_first_use_seconds_total ")
    assert sum(got.values()) == pytest.approx(
        total["vllm:step_first_use_seconds_total"], rel=0.01)


def test_the_process_counters_hold_at_least_the_first_uses(served):
    runner, reg, _, _ = served
    whole = _samples(reg.render(), "vllm:compile_seconds_total{")
    for stage in ("trace", "lower", "backend"):
        key = f'vllm:compile_seconds_total{{stage="{stage}"}}'
        assert whole[key] >= runner.first_use_stage_s[stage] * 0.999, stage
    cache = _samples(reg.render(), "vllm:compile_cache_total{")
    assert set(cache) == {'vllm:compile_cache_total{result="hit"}',
                          'vllm:compile_cache_total{result="miss"}'}


def test_a_later_step_of_the_same_bucket_adds_nothing_and_carries_nothing(served):
    runner, reg, run_batch, attached = served
    stages, seconds = dict(runner.first_use_stage_s), runner.first_use_s
    rendered = _samples(reg.render(), "vllm:step_first_use_stage_seconds_total{")
    steps_before = len(attached)
    run_batch("two")
    assert runner.bucket_compiles == 2
    assert runner.first_use_stage_s == stages and runner.first_use_s == seconds
    assert _samples(reg.render(),
                    "vllm:step_first_use_stage_seconds_total{") == rendered
    # The compile counters ride a snapshot only after they moved: the
    # first of all, and the one after each of the two first uses.
    assert len(attached) > steps_before and not any(attached[steps_before:])
    assert 1 <= sum(attached[:steps_before]) <= 3


def test_a_respawned_engines_counters_never_lower_the_series():
    reg = PrometheusRegistry()
    reg.record(SchedulerStats(
        step_first_use_stage_s={"trace": 4.0, "lower": 2.0, "backend": 1.0, "run": 0.5},
        compile_seconds={"trace": 5.0, "lower": 2.5, "backend": 9.0},
        compile_cache={"hit": 7, "miss": 0}))
    reg.record(SchedulerStats(compile_seconds={"trace": 1.0, "lower": 1.0, "backend": 1.0},
                              compile_cache={"hit": 1, "miss": 1}))
    reg.record(SchedulerStats())  # nothing moved: nothing attached
    text = reg.render()
    assert 'vllm:compile_seconds_total{stage="trace"} 5.0' in text
    assert 'vllm:compile_cache_total{result="hit"} 7.0' in text
    assert 'vllm:compile_cache_total{result="miss"} 1.0' in text
    assert 'vllm:step_first_use_stage_seconds_total{stage="run"} 0.5' in text
