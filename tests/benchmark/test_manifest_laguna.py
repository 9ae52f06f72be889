"""What ``lagunaxs2.code-mixed`` adds to ``BENCHMARK.json``: the
configuration against the catalog's, the arithmetic of its cut, the ladder
of step programs the cell really meets, the traffic, and that appending
still passes the form checks (with the counts read off the manifest)."""

from __future__ import annotations

import json
import shutil

import pytest

from benchmark.harness import manifest as M

from . import test_manifest as forms
from . import test_manifest_appends as appends

MANIFEST = M.load_manifest()
NAME = "lagunaxs2.code-mixed"
CELL = M.Cell(MANIFEST, NAME)

KINDS = ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention"] * 10
# The catalog's ``config`` for Laguna-XS.2, key for key.
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048, "intermediate_size": 8192,
    "num_hidden_layers": 40, "num_attention_heads": 48, "num_key_value_heads": 8,
    "head_dim": 128, "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                           "original_max_position_embeddings": 4096, "beta_slow": 1,
                           "beta_fast": 64, "attention_factor": 1.4158883083359672,
                           "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": KINDS, "moe_apply_router_weight_on_input": False,
    "partial_rotary_factor": 0.5, "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
}


def test_the_configuration_holds_every_published_key_and_cuts_one():
    cfg, bench = CELL.hf_config, CELL.config["bench"]
    assert {k: cfg[k] for k in PUBLISHED if k != "num_experts"} == {
        k: v for k, v in PUBLISHED.items() if k != "num_experts"}
    assert list(bench["reduced"]) == ["num_experts"]
    assert bench["reduced"]["num_experts"]["published"] == 256 and cfg["num_experts"] == 32
    assert set(cfg) - set(PUBLISHED) == {"architectures", "torch_dtype", "ep_size", "ep_rank"}
    assert (cfg["architectures"], cfg["torch_dtype"], cfg["ep_size"], cfg["ep_rank"]) == (
        ["LagunaForCausalLM"], "bfloat16", 8, 0)
    assert CELL.family == "laguna" and CELL.engine_args["max_model_len"] == 12288
    assert "v5e-8" in bench["stands_for"] and len(bench["assumed"]) >= 6
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "laguna-xs.2")
    assert entry["reduced"] == ["num_experts"] and entry["source"] == bench["source"]
    # After what the benchmark had accepted, at whatever place: the next
    # appended configuration or cell must not fail this test.
    forms.check_accepted_entries_are_a_prefix(MANIFEST)
    assert "laguna-xs.2" not in forms.ACCEPTED["configs"] and NAME not in forms.ACCEPTED["workloads"]


def test_the_catalogs_row_is_what_the_file_was_written_from():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(line) for line in open(path)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in rows if r["name"] == "Laguna-XS.2")
    assert row["config"] == PUBLISHED and row["source_url"] == CELL.config["bench"]["source"]


def test_the_arithmetic_of_the_cut():
    cfg = CELL.hf_config
    d, v, dh = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * dh
    attn = lambda heads: 2 * d * heads * dh + 2 * d * kv + d * heads  # q, o, k, v, gate
    assert attn(48) == 29_458_432 and attn(64) == 37_879_808
    expert = 3 * d * cfg["moe_intermediate_size"]
    assert expert == 3_145_728 and 256 * expert == 805_306_368
    shared, router = 3 * d * cfg["shared_expert_intermediate_size"], d * 256
    dense = 3 * d * cfg["intermediate_size"]
    assert dense == 50_331_648 and 2 * v * d == 411_041_792
    norms = 2 * d
    sparse = lambda held: held * expert + shared + router + norms
    whole = (attn(48) + dense + norms + 9 * (attn(48) + sparse(256))
             + 30 * (attn(64) + sparse(256)) + 2 * v * d + d)
    assert 33.40e9 < whole < 33.48e9  # the published 33.4B: the shapes are right
    held = (attn(48) + dense + norms + 9 * (attn(48) + sparse(32))
            + 30 * (attn(64) + sparse(32)) + 2 * v * d + d)
    assert 5.955e9 < held < 5.970e9 and 11.91e9 < 2 * held < 11.94e9  # 74% of 16 GB
    # A token of a layer is 4,096 bytes in BOTH kinds: one page shape.
    assert 2 * kv * 2 == 4096
    # A request of 4,096 tokens: 256 pages in the full group, about 34 in
    # each window group once its window has passed; one group managing all
    # four as full would hold 1,024.
    assert 256 + 3 * (512 // 16 + 2) == 358 and 4 * 256 == 1024


def test_the_program_sees_the_same_sizes():
    import jax.numpy as jnp
    from transformers import PretrainedConfig

    from vllm_tpu.core.kv_cache_utils import group_kv_cache_specs
    from vllm_tpu.models.registry import get_model_class

    cfg = PretrainedConfig.from_dict(CELL.hf_config)
    model = get_model_class(cfg)(cfg, dtype=jnp.bfloat16)
    assert (model.router_width, model.num_experts, model.first_expert) == (256, 32, 0)
    assert (model.window_per_period, model.periods, model.tail) == (3, 9, 3)
    assert model.heads_of == {"full_attention": 48, "sliding_attention": 64}
    assert model.num_window_attn_layers == 30
    groups = group_kv_cache_specs(model.get_kv_cache_spec(16, 2))
    assert [len(g.layer_names) for g in groups] == [10] * 4
    assert [g.sliding_window for g in groups] == [None, 512, 512, 512]
    assert groups[0].page_size_bytes == 655_360 and model.kv_group_layers == 10
    # Layer l lives in group l % 4 at place l // 4 of the one cache array.
    assert all(model.kv_place[layer] == (layer % 4, layer // 4) for layer in range(40))
    assert model.rope_of["full_attention"].rotary_dim == 64
    assert model.rope_of["sliding_attention"].rotary_dim == 128


def test_the_traffic_is_what_the_issue_names():
    t = CELL.traffic
    assert t["generator"] == "open_poisson"
    assert t["prompt_len"] == {"dist": "lognormal", "median": 1800, "sigma": 1.0,
                               "min": 128, "max": 8192}
    assert t["output_len"] == {"dist": "lognormal", "median": 192, "sigma": 0.7,
                               "min": 16, "max": 768}
    assert t["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert (t["ramp_s"], t["drain_limit_s"]) == (30, 90)
    assert t["check"] == {"sequences": 4, "prompt_len": [96, 3072], "output_len": 64,
                          "logprobs": 5}
    assert CELL.numbers["rate_per_s"] == pytest.approx(0.8 * CELL.numbers["knee_per_s"], rel=0.02)
    assert (CELL.chips, CELL.traffic_name, CELL.config_name) == (1, "code-mixed", "laguna-xs.2")


def test_the_check_requests_lie_under_across_and_past_the_window():
    """96, 305, 968 and 3,072 tokens, 64 generated each: two stay under the
    window of 512 (the full and the window layers read the same keys there)
    and two have had their window groups' first pages given back before
    the first generated token."""
    from benchmark.harness import traffic as T

    reqs, together = T.check_requests(CELL.traffic, 7, CELL.hf_config["vocab_size"], 12288)
    lengths = [len(r.prompt) for r in reqs]
    assert together and lengths == [96, 305, 968, 3072]
    assert all(r.max_tokens == 64 and r.logprobs == 5 for r in reqs)
    window = CELL.hf_config["sliding_window"]
    # Two stay under the window to their last generated token, two are past
    # it by 28 pages and more before their first.
    assert lengths[1] + 64 < window and (lengths[2] - window) // 16 >= 28


def test_the_warm_up_walks_the_ladder_this_cell_really_meets():
    """The cell pins the step to one mixed and one decode-only shape:
    token bucket 512, one request bucket (all the rows), the whole page
    table of 768 pages. Worked out with the program's own bucket rule."""
    ladder, reached = forms.check_the_warm_up_walks_the_cells_own_ladder(MANIFEST, NAME)
    tokens_b, rows_b, pages_b, budget = ladder
    rows = CELL.engine_args["max_num_seqs"]
    assert (tokens_b, rows_b, pages_b, budget) == ([512], [rows], [768], 512)
    assert reached == {(512, rows, 768), (rows, rows, 768)}
    assert "2 programs" in CELL.numbers["warmup_waves_why"]
    t = CELL.traffic
    assert t["prompt_len"]["max"] + t["output_len"]["max"] <= 768 * 16


def test_the_limits_of_correct_are_three_and_explained():
    limits = CELL.numbers["correct_limits"]
    assert set(limits) == {"p50_abs", "p90_abs", "worst_sequence_mean_abs"}
    why = CELL.numbers["correct_limits_why"]
    assert "float8" in why and "window" in why and "expert" in why and len(why) > 300
    assert json.dumps(CELL.numbers)  # plain data
    # The chip's readings (PERF.md section 4): every limit lies between the
    # largest of twenty sound seeds and the smallest reading of the nearest
    # control (the window layers served as full for the median, three
    # seeds; a fault in the expert layer for the other two, two seeds;
    # float8 KV reads further off), with half as much again on both sides.
    sound = {"p50_abs": 0.1265, "p90_abs": 0.3229, "worst_sequence_mean_abs": 0.1638}
    control = {"p50_abs": 0.3474, "p90_abs": 1.5070, "worst_sequence_mean_abs": 0.8476}
    for key, most in sound.items():
        assert 1.5 * most < limits[key] < control[key] / 1.5


def test_the_new_entries_are_appended_and_list_the_one_cell():
    new = ["window_pages_freed_share", "kv_pages_in_use_share", "attn_window_roofline",
           "attn_full_roofline"]
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    # Appended: none of them is among the names the list begins with (what
    # may follow them, and in which order, is the next PR's business).
    forms.check_accepted_entries_are_a_prefix(MANIFEST)
    assert not set(new) & set(forms.ACCEPTED["per_layer"])
    for name in new:
        m = by_name[name]
        assert NAME in m["workloads"]
        assert m["layer"] == ("kernels" if name.endswith("roofline") else "KV cache manager")
    assert by_name["window_pages_freed_share"]["moves"] == "output_tokens_per_s"
    assert {by_name[n]["moves"] for n in new[1:]} == {"itl_p95_ms"}
    reported = {m["name"] for m in CELL.per_layer}
    assert set(new) | {"ragged_attn_live_share", "sampler_fallback_rows_per_step",
                       "preemptions", "device_idle_share", "schedule_ms_per_step"} <= reported
    for other in ("mistral7b.chat", "sdar30b.chat", "kimilinear48b.long-answers"):
        assert not set(new) & {m["name"] for m in M.Cell(MANIFEST, other).per_layer}


# ---- appending, with the counts read off the manifest. The accepted
# ``test_manifest_appends.py`` asserts the counts of the manifest PR 42
# accepted as literals (``[4, 4, 36]`` and ``36``) over a copy of the REAL
# manifest plus its synthetic entries: its two cases fail from the first real
# appended entry on, and only a `benchmark` PR may replace the literals
# (PERF.md section 7). Everything else they check is run here.


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("appended-after-laguna")
    shutil.copytree(M.BENCH_DIR, root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(M.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    appends.append_to(root)
    return root


def test_appending_still_passes_the_form_checks(tree, monkeypatch):
    """``test_manifest_appends.py::test_the_form_checks_pass_over_appended_
    entries`` line for line, but for its literal ``[4, 4, 36]``."""
    monkeypatch.setattr(M, "ROOT", tree)
    monkeypatch.setattr(M, "BENCH_DIR", tree / "benchmark")
    manifest = M.load_manifest()
    assert [len(manifest[k]) for k in ("configs", "workloads", "per_layer")] == [
        len(MANIFEST[k]) + 1 for k in ("configs", "workloads", "per_layer")]
    forms.check_accepted_entries_are_a_prefix(manifest)
    forms.check_top_level_keys_and_limits(manifest)
    forms.check_names_and_units_use_only_the_allowed_characters(manifest)
    for cfg in manifest["configs"]:
        forms.check_a_configuration_states_its_source_its_cuts_and_what_it_stands_for(manifest, cfg)
    for w in manifest["workloads"]:
        forms.check_every_file_a_cell_names_exists_and_loads(manifest, w["name"])
        forms.check_the_warm_up_walks_the_cells_own_ladder(manifest, w["name"])
    cell = M.Cell(manifest, appends.CELL)
    assert appends.METRIC in {m["name"] for m in cell.per_layer}
    _, reached = forms.check_the_warm_up_walks_the_cells_own_ladder(manifest, appends.CELL)
    assert len(reached) == 12 and len(cell.numbers["warmup_waves"]) == 6


def test_the_two_place_holding_tests_pass_over_the_appended_tree(tree, monkeypatch):
    from . import test_kda_chunk_roofline as chunk
    from . import test_setup_timeline_metrics as timeline

    monkeypatch.setattr(M, "ROOT", tree)
    monkeypatch.setattr(M, "BENCH_DIR", tree / "benchmark")
    manifest = M.load_manifest()
    assert len(manifest["per_layer"]) == len(MANIFEST["per_layer"]) + 1
    monkeypatch.setattr(chunk, "MANIFEST", manifest)
    monkeypatch.setattr(timeline, "MANIFEST", manifest)
    monkeypatch.setattr(timeline, "CELLS", [w["name"] for w in manifest["workloads"]])
    chunk.test_the_entry_lists_the_one_cell_under_the_kernels()
    timeline.test_what_the_benchmark_had_is_as_it_was()
    for name in timeline.READERS:
        timeline.test_an_entry_moves_setup_s_in_every_cell(name)
