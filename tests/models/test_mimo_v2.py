"""MiMo-V2 on the normal path, the parts that are not the benchmark's
comparison (``tests/benchmark/test_reference_mimo_v2.py`` holds the served
answers to the plain reference, ``tests/models/test_split_kv_attention.py``
both kernels): the layers' runs and places, the worker's groups and its one
cache array, the runner's counters, the new series on ``/metrics``, the
named scopes, the weight map with the fused q, k and v split at load."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from transformers import PretrainedConfig

from tests.benchmark.tiny_mimo_v2 import TINY_MIMO_V2, model_dir, share
from vllm_tpu.models.mimo_v2 import MiMoV2ForCausalLM


def tiny(config=TINY_MIMO_V2, dtype=jnp.float32):
    return MiMoV2ForCausalLM(PretrainedConfig.from_dict(config), dtype=dtype)


def test_the_layers_runs_and_their_places_in_the_cache():
    m = tiny()
    assert m.kinds == ["full"] + ["window"] * 4 + ["full"] + ["window"] * 5 + ["full"]
    # Three layers in line and two scans over runs of window layers.
    assert m.runs == [(0, 1), (1, 4), (5, 1), (6, 5), (11, 1)]
    assert m.geometry == {"full": (8, 2, 24, 16), "window": (8, 4, 24, 16)}
    assert m.has_sinks == {"full": False, "window": True}
    assert (m.num_sink_attn_layers, m.num_window_attn_layers, m.num_moe_layers) == (9, 9, 11)
    assert m.attn_index == [0, 0, 1, 2, 3, 1, 4, 5, 6, 7, 8, 2]
    assert m.ffn_index == [0] + list(range(11))
    # The full layers are group 0's places 0, 1, 2; the window kind's i-th
    # layer is place i // 3 of group 1 + i % 3.
    assert [m.kv_place[i] for i in (0, 5, 11)] == [(0, 0), (0, 1), (0, 2)]
    window = [i for i, k in enumerate(m.kinds) if k == "window"]
    assert [m.kv_place[i] for i in window] == [(1 + j % 3, j // 3) for j in range(9)]
    assert (m.num_kv_groups, m.kv_group_places) == (4, 3)
    assert m.kv_cache_shape(50, 8) == (3, 50, 32, 128 + 16)
    assert m.rope_of["full"].rotary_dim == m.rope_of["window"].rotary_dim == 8
    assert m.ragged_kernel_geometry(8) == (8, 2, 128, 16)


def test_the_published_depth_has_a_short_last_window_group():
    pattern = [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0]
    m = tiny({**TINY_MIMO_V2, "num_hidden_layers": 48, "hybrid_layer_pattern": pattern,
              "moe_layer_freq": [0] + [1] * 47})
    assert (m.num_kv_groups, m.kv_group_places) == (6, 9)
    assert len(m.runs) == 17 and m.runs[:3] == [(0, 1), (1, 4), (5, 1)]
    assert m.kv_place[46] == (5, 2) and m.kv_place[47] == (0, 8)
    assert m.kv_cache_shape(10, 16)[0] == 9


def test_a_config_the_family_does_not_cover_is_refused():
    with pytest.raises(NotImplementedError, match="one key width"):
        tiny({**TINY_MIMO_V2, "swa_v_head_dim": 24})
    with pytest.raises(NotImplementedError, match="sigmoid"):
        tiny({**TINY_MIMO_V2, "scoring_func": "softmax"})
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        tiny({**TINY_MIMO_V2, "rope_scaling": {"rope_type": "yarn", "factor": 4}})
    with pytest.raises(ValueError, match="ep_rank"):
        tiny({**share(0), "ep_rank": 4})
    with pytest.raises(ValueError, match="every"):
        tiny({**TINY_MIMO_V2, "num_hidden_layers": 14})


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from vllm_tpu import LLM, SamplingParams

    llm = LLM(model=model_dir(tmp_path_factory.mktemp("mimo")), load_format="dummy", seed=2,
              dtype="float32", max_model_len=256, block_size=8, num_gpu_blocks_override=128,
              max_num_seqs=8, max_num_batched_tokens=32)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 240, n).tolist() for n in (6, 23, 70)]
    llm.generate([{"prompt_token_ids": p} for p in prompts],
                 SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True))
    core = llm.llm_engine.engine_core.engine_core
    yield core, prompts
    llm.shutdown()


def test_the_worker_groups_the_layers_by_bytes_a_token(served):
    core, _ = served
    cache = core.config.cache_config
    assert [len(g.layer_names) for g in cache.kv_cache_groups] == [3, 3, 3, 3]
    assert [g.block_size for g in cache.kv_cache_groups] == [16, 8, 8, 8]
    assert len({g.page_size_bytes for g in cache.kv_cache_groups}) == 1
    assert cache.sliding_window is None and cache.block_size == 8
    runner = core.executor.worker.runner
    assert runner.num_kv_groups == 4 and len(runner.input_batch.block_tables) == 4
    # ONE array for both page shapes: 2 heads x 16 tokens = 4 x 8 = 32 rows.
    assert runner.kv_cache.shape == (3, 128, 32, 144)
    manager = core.scheduler.kv_cache_manager
    assert [m.block_size for m in manager.managers] == [16, 8, 8, 8]
    assert manager.get_num_free_blocks() == 127


def test_the_runner_counts_the_rows_that_ran_with_a_sink(served):
    core, prompts = served
    runner = core.executor.worker.runner
    # Nine window layers, each with a sink: rows x 9, as the window rows.
    assert runner.attn_sink_rows == runner.window_attn_rows > 0
    assert runner.attn_sink_rows % 9 == 0
    total = [len(p) + 11 for p in prompts]
    want = sum(sum(min(pos + 1, 24) for pos in range(n)) for n in total)
    assert want <= runner.window_attn_pairs <= want + 2 * 3 * 24


def test_the_new_series_are_on_metrics(served):
    from vllm_tpu.metrics.prometheus import PrometheusRegistry

    core, _ = served
    stats = core.scheduler.make_stats()
    stats.attn_sink_rows = core.executor.worker.runner.attn_sink_rows
    reg = PrometheusRegistry()
    reg.record(stats, None)
    text = reg.render()
    for line in ('vllm:kv_group_page_tokens{group="0"} 16',
                 'vllm:kv_group_page_tokens{group="3"} 8',
                 'vllm:kv_token_bytes{group="0",what="published"} 960.0',
                 'vllm:kv_token_bytes{group="0",what="held"} 3456.0',
                 'vllm:kv_token_bytes{group="1",what="published"} 1920.0',
                 'vllm:kv_token_bytes{group="1",what="held"} 6912.0',
                 "# TYPE vllm:kv_token_bytes gauge",
                 f"vllm:attn_sink_rows_total {float(stats.attn_sink_rows)}"):
        assert line in text, line
    # A uniform model has neither gauge (no sample), and counts no row.
    from vllm_tpu.core.sched_output import SchedulerStats

    plain = PrometheusRegistry()
    plain.record(SchedulerStats(), None)
    assert 'vllm:kv_group_page_tokens{' not in plain.render()
    assert "vllm:attn_sink_rows_total 0.0" in plain.render()


def test_the_layers_parts_run_under_named_scopes():
    """``attn_full``, ``attn_window``, ``moe`` as Laguna's, and ``dense_mlp``
    for layer 0: what a trace's operations are found by."""
    from vllm_tpu.ops.attention import AttentionMetadata

    m = tiny()
    params = jax.eval_shape(m.init_dummy_params, jax.random.PRNGKey(0))
    t, r, b, g = 8, 2, 4, m.num_kv_groups
    s = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype)
    md = AttentionMetadata(
        positions=s((t,)), slot_mapping=s((g, t)), block_tables=s((g, r, b)),
        seq_lens=s((r,)), query_start_loc=s((r + 1,)), token_req_idx=s((t,)),
        logits_indices=s((r,)), num_seqs=s((1,)))
    text = jax.jit(m.apply).lower(
        params, s(m.kv_cache_shape(16, 8), jnp.float32), s((t,)), md).as_text(debug_info=True)
    for scope in ("attn_full", "attn_window", "dense_mlp", "moe"):
        assert scope in text, scope


def hf_tensors(model, params):
    """The checkpoint a release of this family would hold, made from
    ``params``: every name of ``hf_weight_map`` as the published layout has
    it (``[out, in]`` matrices), then q, k and v fused a layer."""
    out = {}
    for name, (dest, transpose) in model.hf_weight_map().items():
        parts = dest.split(".")
        idx = [int(p) for p in parts if p.isdigit()]
        leaf = params
        for p in parts:
            if not p.isdigit():
                leaf = leaf[p]
        arr = np.asarray(leaf[tuple(idx)] if idx else leaf)
        out[name] = np.ascontiguousarray(arr.T if transpose else arr)
    for i in range(model.num_layers):
        stem = f"model.layers.{i}.self_attn."
        out[stem + "qkv_proj.weight"] = np.concatenate(
            [out.pop(stem + f"{x}_proj.weight") for x in "qkv"], axis=0)
    return out


@pytest.mark.parametrize("which", ["uncut", "share"])
def test_a_checkpoint_with_fused_qkv_loads_leaf_by_leaf(tmp_path, which):
    """A tiny seeded checkpoint under the published names (written here:
    no real one is needed), ``attention_projection_layout`` ``fused_qkv``:
    ``split_hf_tensor`` cuts each layer's matrix at its kind's own head
    counts, and every leaf comes back where the dummy draw had it. A share
    maps only the experts it holds, by their numbers in the router."""
    from safetensors.numpy import save_file

    config = TINY_MIMO_V2 if which == "uncut" else share(1)
    model = tiny(config)
    params = model.init_dummy_params(jax.random.PRNGKey(7))
    tensors = hf_tensors(model, params)
    assert "model.layers.3.self_attn.qkv_proj.weight" in tensors
    assert tensors["model.layers.3.self_attn.qkv_proj.weight"].shape == (
        8 * 24 + 4 * 24 + 4 * 16, 64)  # a window layer: 4 KV heads
    assert tensors["model.layers.5.self_attn.qkv_proj.weight"].shape == (
        8 * 24 + 2 * 24 + 2 * 16, 64)  # a full layer: 2
    assert "model.layers.0.self_attn.attention_sink_bias" not in tensors
    assert tensors["model.layers.1.self_attn.attention_sink_bias"].shape == (8,)
    first = 8 if which == "share" else 0
    assert f"model.layers.1.mlp.experts.{first}.down_proj.weight" in tensors
    assert f"model.layers.1.mlp.experts.{first + (8 if which == 'share' else 32)}.down_proj.weight" \
        not in tensors
    path = tmp_path / "ckpt"
    path.mkdir()
    save_file(tensors, str(path / "model.safetensors"))
    loaded = model.load_params(str(path), dtype=jnp.float32)
    want = jax.tree_util.tree_leaves_with_path(params)
    got = dict(jax.tree_util.tree_leaves_with_path(loaded))
    assert len(got) == len(want)
    for key, leaf in want:
        assert got[key].shape == leaf.shape and got[key].dtype == leaf.dtype, key
        np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(leaf), err_msg=str(key))
    with pytest.raises(ValueError, match="rows"):
        model.split_hf_tensor("model.layers.3.self_attn.qkv_proj.weight",
                              tensors["model.layers.5.self_attn.qkv_proj.weight"])
