"""Laguna on the normal path, the parts that are not the benchmark's
comparison (``tests/benchmark/test_reference_laguna.py`` holds the served
answers to the plain reference): the two attention kernels at 6 and at 8
query heads a KV head with a window and with NULL pages under the window's
floor, the tiling a group of 6 gets, the runner's counters and its block
table a KV group, the host span, the weight map."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from transformers import PretrainedConfig

from tests.benchmark.tiny_laguna import TINY_LAGUNA, model_dir
from tests.models.test_decode_attention import _decode_case, dispatched
from tests.models.test_ragged_paged_attention import (
    _bundled_ref,
    _random_case,
    _run_kernel,
)

WINDOW, BS = 16, 8


def null_under_the_floor(md, window: int, bs: int):
    """The block tables with every page wholly under the window's floor
    replaced by the null page 0, as a window group's manager leaves them:
    the floor of a row is ``context before this step - window + 1``."""
    tables = np.asarray(md.block_tables).copy()
    kv_lens = np.asarray(md.seq_lens)
    q_lens = np.diff(np.asarray(md.query_start_loc))
    nulled = 0
    for i in range(int(md.num_seqs[0])):
        first_needed = max(0, int(kv_lens[i] - q_lens[i]) - window + 1) // bs
        nulled += first_needed
        tables[i, :first_needed] = 0
    assert nulled > 0, "the case frees nothing: lengthen it"
    return dataclasses.replace(md, block_tables=jnp.asarray(tables))


@pytest.mark.parametrize("kh,h", [(2, 12), (2, 16)], ids=["6_a_kv_head", "8_a_kv_head"])
def test_ragged_kernel_with_a_window_over_null_pages(kh, h):
    """Decode rows and chunks past the window, their pages under the floor
    gone to the null page: the kernel starts its tile loop at the floor and
    never lets a key under it through, so the answer is the intact table's."""
    rng = np.random.default_rng(5)
    q_lens, kv_lens = [1, 5, 1, 9], [70, 45, 12, 64]
    q, kv_cache, md = _random_case(rng, 4, q_lens, kv_lens, kh, h, 128, BS, num_blocks=64)
    scale = 128 ** -0.5
    want = _bundled_ref(q, kv_cache[0], md, 4, sm_scale=scale, sliding_window=WINDOW)
    got = _run_kernel(q, kv_cache, 0, null_under_the_floor(md, WINDOW, BS), scale,
                      sliding_window=jnp.int32(WINDOW), name="rpa_kernel_window")
    np.testing.assert_allclose(np.asarray(got)[:16], np.asarray(want), rtol=2e-4, atol=2e-4)
    # And as a full layer's call (window 0) on the intact table.
    full = _run_kernel(q, kv_cache, 0, md, scale, sliding_window=jnp.int32(0))
    np.testing.assert_allclose(
        np.asarray(full)[:16],
        np.asarray(_bundled_ref(q, kv_cache[0], md, 4, sm_scale=scale)), rtol=2e-4, atol=2e-4)
    assert not np.allclose(np.asarray(full)[:16], np.asarray(want), atol=1e-2)


@pytest.mark.parametrize("kh,h", [(2, 12), (2, 16)], ids=["6_a_kv_head", "8_a_kv_head"])
def test_a_decode_only_step_with_a_window_over_null_pages(kh, h):
    """The decode-only step of a window layer, as the dispatcher lowers it:
    the ragged kernel under the window layers' name (the decode kernel has
    no window), every row one visit that starts at its floor."""
    from vllm_tpu.ops.attention import ref_ragged_paged_attention

    rng = np.random.default_rng(6)
    q, kv_cache, md = _decode_case(rng, [70, 45, 12, 33], kh, h, 128, BS, num_blocks=64)
    scale = 128 ** -0.5
    want = ref_ragged_paged_attention(q, kv_cache, jnp.int32(0), md, scale, sliding_window=WINDOW)
    got, names = dispatched(q, kv_cache, null_under_the_floor(md, WINDOW, BS), scale,
                            sliding_window=jnp.int32(WINDOW), kernel_suffix="_window")
    assert names == ["rpa_kernel_window"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_a_group_of_six_query_heads_keeps_whole_tiles_and_the_narrow_rung():
    from vllm_tpu.ops.rpa_kernel import ragged_tiling

    six = ragged_tiling(48, 16, 128, 16, jnp.bfloat16, jnp.bfloat16, 512, 768)
    assert (six.q_per_blk, six.narrow_q, six.kv_pages_per_blk) == (80, 8, 32)
    # The groups that divide 512 rows are as they were.
    assert ragged_tiling(64, 16, 128, 16, jnp.bfloat16, jnp.bfloat16, 512, 768).q_per_blk == 64
    assert ragged_tiling(32, 16, 128, 16, jnp.bfloat16, jnp.bfloat16, 512, 512).q_per_blk == 128


def test_the_dispatch_names_a_window_layers_kernel(monkeypatch):
    """``paged_attention(kernel_suffix=...)`` reaches the kernel as its
    ``pallas_call``'s name; without it the names are what they were. A
    window layer's call is the ragged kernel's in a decode-only step too."""
    from vllm_tpu.ops import attention, rpa_decode_kernel, rpa_kernel

    seen = []
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    for module, fn in ((rpa_kernel, "ragged_paged_attention"),
                       (rpa_decode_kernel, "decode_paged_attention")):
        monkeypatch.setattr(
            module, fn, lambda q, *a, name, **kw: (seen.append(name), q)[1])
    rng = np.random.default_rng(7)
    q, kv_cache, md = _random_case(rng, 2, [1, 3], [9, 12], 2, 4, 128, BS, num_blocks=16)
    attention.paged_attention(q, kv_cache, 0, md, 1.0, sliding_window=4, kernel_suffix="_window")
    attention.paged_attention(q, kv_cache, 0, md, 1.0)
    dq, dkv, dmd = _decode_case(rng, [9, 12], 2, 4, 128, BS, num_blocks=16)
    attention.paged_attention(dq, dkv, 0, dmd, 1.0, sliding_window=4, kernel_suffix="_window")
    attention.paged_attention(dq, dkv, 0, dmd, 1.0)
    assert seen == ["rpa_kernel_window", "rpa_kernel", "rpa_kernel_window",
                    "rpa_decode_kernel"]


def test_a_rope_table_past_the_inline_limit_holds_the_positions_served():
    """``layers/rotary.py``, for every model: under a worker a table that
    the published positions take past 8 MB holds ``max_model_len`` rows;
    one at or under the limit (Mistral's 32,768 x 64 floats) keeps its
    length, so the accepted cells' step programs are what they were."""
    from vllm_tpu.layers.rotary import RotaryEmbedding, serving_positions
    from vllm_tpu.models.laguna import LagunaForCausalLM

    whole = RotaryEmbedding(128, 65600, theta=1e4)
    with serving_positions(12288):
        kept = RotaryEmbedding(128, 32768, theta=1e6)
        cut = RotaryEmbedding(128, 65600, theta=1e4)
        tiny = LagunaForCausalLM(PretrainedConfig.from_dict(TINY_LAGUNA), dtype=jnp.float32)
    assert kept.cos.shape == (32768, 64) and whole.cos.shape == (65600, 64)
    assert cut.cos.shape == cut.sin.shape == (12288, 64)
    np.testing.assert_array_equal(cut._sin_np, whole._sin_np[:12288])
    assert tiny.rope_of["full_attention"].cos.shape == (4096, 4)
    assert tiny.rope_of["sliding_attention"].sin.shape == (4096, 8)
    assert RotaryEmbedding(128, 65600).cos.shape[0] == 65600  # the rule ends with the worker's block


def test_a_rope_table_over_the_limit_is_no_tracer_of_the_first_trace():
    """What stopped Laguna's first step on the chip: a table over the
    limit was made a device array INSIDE the first trace that asked for
    it, and the next step program met that trace's tracer."""
    from vllm_tpu.layers.rotary import RotaryEmbedding

    rope = RotaryEmbedding(128, 40000)  # 10 MB a table
    pos = jnp.arange(4)
    first = jax.jit(lambda p: rope.cos[p])(pos)
    assert not isinstance(rope._cos_dev, jax.core.Tracer)
    second = jax.jit(lambda p: rope.cos[p] + rope.sin[p])(pos)
    np.testing.assert_allclose(np.asarray(second - first), rope._sin_np[:4], atol=1e-6)


def test_the_weight_map_names_every_leaf_once():
    from vllm_tpu.models.laguna import LagunaForCausalLM

    model = LagunaForCausalLM(PretrainedConfig.from_dict(TINY_LAGUNA), dtype=jnp.float32)
    shapes = jax.eval_shape(model.init_dummy_params, jax.random.PRNGKey(0))
    slots = set()
    for hf_name, (path, transposed) in model.hf_weight_map().items():
        node, idx = shapes, []
        for part in path.split("."):
            if isinstance(node, dict):
                node = node[part]
            else:
                idx.append(int(part))
        assert len(idx) <= node.ndim - 1 and all(i < n for i, n in zip(idx, node.shape)), hf_name
        assert (path not in slots), path
        slots.add(path)
        if transposed:
            assert node.ndim - len(idx) == 2, hf_name
    leaves = {".".join(str(k.key) for k in path): leaf
              for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    covered = {name: 0 for name in leaves}
    for path in slots:
        parts = path.split(".")
        name = ".".join(p for p in parts if not p.isdigit())
        covered[name] += 1
    for name, leaf in leaves.items():
        stacked = leaf.ndim - (1 if name.endswith("norm") else 2)
        per_slot = int(np.prod(leaf.shape[:stacked])) if stacked > 0 else 1
        assert covered[name] == per_slot, (name, covered[name], per_slot)
    assert "model.layers.5.mlp.experts.15.down_proj.weight" in model.hf_weight_map()
    assert model.hf_weight_map()["model.layers.4.self_attn.g_proj.weight"] == (
        "periods.full.wg.0", True)


def test_a_layer_types_pattern_this_file_cannot_run_is_refused():
    from vllm_tpu.models.laguna import LagunaForCausalLM

    odd = {**TINY_LAGUNA, "layer_types": ["sliding_attention"] + TINY_LAGUNA["layer_types"][1:]}
    with pytest.raises(NotImplementedError, match="dense full-attention layer 0"):
        LagunaForCausalLM(PretrainedConfig.from_dict(odd))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One tiny server run: five requests, two of them past the window."""
    from vllm_tpu import LLM, SamplingParams

    llm = LLM(model=model_dir(tmp_path_factory.mktemp("laguna")), load_format="dummy",
              seed=3, dtype="float32", max_model_len=256, block_size=16,
              num_gpu_blocks_override=96, max_num_seqs=8, max_num_batched_tokens=32)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 240, n).tolist() for n in (6, 9, 23, 70, 130)]
    llm.generate([{"prompt_token_ids": p} for p in prompts],
                 SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True))
    core = llm.llm_engine.engine_core.engine_core
    yield core, prompts
    llm.shutdown()


def test_the_worker_groups_the_layers_and_sizes_a_page_by_one_group(served):
    core, _ = served
    cache = core.config.cache_config
    assert [len(g.layer_names) for g in cache.kv_cache_groups] == [3, 3, 3, 3]
    assert cache.sliding_window is None
    runner = core.executor.worker.runner
    assert runner.num_kv_groups == 4 and len(runner.input_batch.block_tables) == 4
    # ONE array, a layer's place in its group first: 3, not the 12 layers.
    assert runner.kv_cache.shape == (3, 96, 16, 2, 32)
    # A tiny table is far under the inline limit: its published length.
    assert runner.model.rope_of["full_attention"].cos.shape[0] == 4096


def test_the_runner_counts_the_window_layers_work(served):
    core, prompts = served
    runner = core.executor.worker.runner
    steps = sum(runner.steps_by_kind.values())
    assert runner.window_attn_rows >= 9 * steps  # 9 window layers, a row a step at least
    # Every token of every request once, and under the window a key more a
    # query; past it 32 keys a query.
    total = [len(p) + 11 for p in prompts]
    want = sum(sum(min(pos + 1, 32) for pos in range(n)) for n in total)
    # (Async scheduling runs a request up to two positions past its end
    # before it learns of it.)
    assert want <= runner.window_attn_pairs <= want + 2 * 5 * 32
    keys = runner.attn_context_tokens_by_kind["window"]
    full = runner.attn_context_tokens_by_kind["decode"] + runner.attn_context_tokens_by_kind["mixed"]
    assert 0 < keys < full


def test_the_stats_carry_the_groups_to_the_metrics(served):
    from vllm_tpu.metrics.prometheus import PrometheusRegistry

    core, _ = served
    stats = core.scheduler.make_stats()
    assert stats.kv_pages_allocated == [22] * 4 and stats.kv_group_pages_in_use == [0] * 4
    assert stats.kv_window_pages_freed == 27
    assert stats.kv_page_steps["pool"] % 95 == 0 and 0 < stats.kv_page_steps["held"]
    stats.window_attn_rows = core.executor.worker.runner.window_attn_rows
    reg = PrometheusRegistry()
    reg.record(stats, None)
    text = reg.render()
    for line in ('vllm:kv_pages_allocated_total{group="3"} 22.0',
                 'vllm:kv_group_pages_in_use{group="0"} 0',
                 "vllm:kv_window_pages_freed_total 27.0",
                 'vllm:kv_page_steps_total{kind="held"}',
                 "vllm:window_attn_rows_total"):
        assert line in text, line


def test_giving_pages_back_is_a_host_span_of_the_scheduler(monkeypatch):
    """``vllm_tpu.scheduler.free_window_pages`` on the profiler's host
    plane, once an allocation of a model with window groups."""
    from vllm_tpu import tracing
    from vllm_tpu.core.kv_cache_manager import KVCacheManager
    from vllm_tpu.core.kv_cache_utils import group_kv_cache_specs
    from vllm_tpu.models.laguna import LagunaForCausalLM
    from vllm_tpu.request import Request
    from vllm_tpu.sampling_params import SamplingParams

    seen = []

    class Span:
        def __init__(self, name, **kw):
            seen.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    monkeypatch.setattr(tracing, "_annotation", Span)
    model = LagunaForCausalLM(PretrainedConfig.from_dict(TINY_LAGUNA), dtype=jnp.float32)
    groups = group_kv_cache_specs(model.get_kv_cache_spec(16, 4))
    m = KVCacheManager(num_blocks=64, block_size=16, kv_cache_groups=groups)
    req = Request("a", list(range(100)), SamplingParams(max_tokens=8))
    assert m.allocate_slots(req, 100) is not None
    assert seen == ["vllm_tpu.scheduler.free_window_pages"]
    one = KVCacheManager(num_blocks=64, block_size=16)
    assert one.allocate_slots(Request("b", list(range(40)), SamplingParams(max_tokens=8)), 40)
    assert len(seen) == 1  # a uniform model pays for no span
