"""What ``mimov25.long-answers`` adds to ``BENCHMARK.json``: the
configuration against the catalog's row, the arithmetic of its cut, the
sizes the program sees, the ladder of step programs the cell really meets
(worked out with the program's own bucket rule), the traffic it shares
with ``kimilinear48b.long-answers``, and that appending still passes the
form checks, with every count read off the manifest and no literal."""

from __future__ import annotations

import json
import shutil

import pytest

from benchmark.harness import manifest as M

from . import test_manifest as forms
from . import test_manifest_appends as appends

MANIFEST = M.load_manifest()
NAME = "mimov25.long-answers"
CELL = M.Cell(MANIFEST, NAME)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": (48, 10), "n_routed_experts": (256, 16)}
ADDED = {"architectures", "torch_dtype", "ep_size", "ep_rank"}


def test_the_configuration_holds_the_published_widths_and_cuts_two_keys():
    cfg, bench = CELL.hf_config, CELL.config["bench"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"],
            cfg["v_head_dim"]) == (4096, 64, 192, 128)
    assert (cfg["num_key_value_heads"], cfg["swa_num_key_value_heads"]) == (4, 8)
    assert (cfg["sliding_window"], cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["intermediate_size"], cfg["vocab_size"]) == (128, 2048, 8, 16_384, 152_576)
    assert cfg["add_swa_attention_sink_bias"] and not cfg["add_full_attention_sink_bias"]
    assert list(bench["reduced"]) == list(REDUCED)
    for key, (published, run) in REDUCED.items():
        assert bench["reduced"][key]["published"] == published
        assert bench["reduced"][key]["run"] == cfg[key] == run
        assert len(bench["reduced"][key]["why"]) > 60
    assert (cfg["architectures"], cfg["torch_dtype"], cfg["ep_size"], cfg["ep_rank"]) == (
        ["MiMoV2ForCausalLM"], "bfloat16", 16, 0)
    assert CELL.family == "mimo_v2" and CELL.engine_args["max_model_len"] == 12288
    assert "the head WHOLE" in bench["stands_for"] and "a sixteenth" in bench["stands_for"]
    assert len(bench["assumed"]) >= 10 and "drew" in bench["drawn"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "mimo-v2.5")
    assert entry["reduced"] == list(REDUCED) and entry["source"] == bench["source"]
    assert len(entry["why"]) <= 200
    forms.check_accepted_entries_are_a_prefix(MANIFEST)
    assert "mimo-v2.5" not in forms.ACCEPTED["configs"] and NAME not in forms.ACCEPTED["workloads"]


def test_the_catalogs_row_is_what_the_file_was_written_from():
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in rows if r["name"] == "MiMo-V2.5")
    cfg = CELL.hf_config
    assert row["source_url"] == CELL.config["bench"]["source"]
    assert set(cfg) - set(row["config"]) == ADDED
    differs = {k for k, v in row["config"].items() if cfg[k] != v}
    assert differs == set(REDUCED)
    assert {k: row["config"][k] for k in REDUCED} == {k: v[0] for k, v in REDUCED.items()}


def test_the_arithmetic_of_the_cut():
    cfg = CELL.hf_config
    d, dk, dv, h = cfg["hidden_size"], cfg["head_dim"], cfg["v_head_dim"], 64
    attn = lambda kh: d * h * dk + d * kh * dk + d * kh * dv + h * dv * d
    assert attn(4) == 89_128_960 and attn(8) == 94_371_840
    expert = 3 * d * cfg["moe_intermediate_size"]
    assert expert == 25_165_824 and 256 * expert * 2 == 12_884_901_888  # one layer: 12.9 GB
    router, dense = d * 256, 3 * d * cfg["intermediate_size"]
    assert dense == 201_326_592 and 2 * 152_576 * d == 1_249_902_592
    whole = (9 * attn(4) + 39 * attn(8) + dense + 47 * (router + 256 * expert)
             + 2 * 152_576 * d)
    assert 308e9 < whole < 310e9  # the published 309B: the shapes are right
    held = (2 * attn(4) + 8 * attn(8) + dense + 9 * (router + 16 * expert)
            + 2 * cfg["vocab_size"] * d)
    assert held == 6_017_777_664 and 12.03e9 < 2 * held < 12.04e9  # 75% of 16 GB
    # An eleventh layer (the second period's last window layer) is 13.0 GB.
    assert 13.0e9 < 2 * (held + attn(8) + router + 16 * expert) < 13.1e9
    # A token of a layer: 2,560 bytes in a full layer and 5,120 in a window
    # layer as published; 3,072 and 6,144 as held (a key of 192 in 256).
    assert (4 * (dk + dv) * 2, 8 * (dk + dv) * 2) == (2560, 5120)
    assert (4 * 384 * 2, 8 * 384 * 2) == (3072, 6144)
    # One page: 2 layers x 32 tokens x 3,072 = 2 x 16 x 6,144 bytes.
    assert 2 * 32 * 3072 == 2 * 16 * 6144 == 196_608
    # A request of 700 + 640 tokens: 42 full pages and 4 x 10 window pages,
    # where window groups kept whole would hold 4 x 84.
    assert -(-1340 // 32) == 42 and 128 // 16 + 2 == 10 and -(-1340 // 16) == 84


def test_the_program_sees_the_same_sizes():
    import jax.numpy as jnp
    from transformers import PretrainedConfig

    from vllm_tpu.core.kv_cache_utils import group_kv_cache_specs
    from vllm_tpu.models.registry import get_model_class

    cfg = PretrainedConfig.from_dict(CELL.hf_config)
    model = get_model_class(cfg)(cfg, dtype=jnp.bfloat16)
    assert (model.router_width, model.num_experts, model.first_expert) == (256, 16, 0)
    assert model.kinds == (["full"] + ["window"] * 4) * 2
    assert model.geometry == {"full": (64, 4, 192, 128), "window": (64, 8, 192, 128)}
    assert (model.num_window_attn_layers, model.num_sink_attn_layers) == (8, 8)
    assert model.rope_of["full"].rotary_dim == model.rope_of["window"].rotary_dim == 64
    groups = group_kv_cache_specs(model.get_kv_cache_spec(16, 2))
    assert [len(g.layer_names) for g in groups] == [2] * 5
    assert [g.block_size for g in groups] == [32, 16, 16, 16, 16]
    assert [g.sliding_window for g in groups] == [None, 128, 128, 128, 128]
    assert {g.page_size_bytes for g in groups} == {196_608}
    assert model.kv_cache_shape(1000, 16) == (2, 1000, 128, 384)
    import jax

    shapes = jax.eval_shape(model.init_dummy_params, jax.random.PRNGKey(0))
    held = sum(int(a.size) for a in jax.tree.leaves(shapes))
    assert 6.017e9 < held < 6.019e9  # the norms, sinks and biases beside the matrices


def test_the_traffic_is_the_accepted_file_it_shares():
    t = CELL.traffic
    assert t == M.Cell(MANIFEST, "kimilinear48b.long-answers").traffic
    assert t["generator"] == "open_poisson"
    assert t["prompt_len"] == {"dist": "lognormal", "median": 700, "sigma": 1.0,
                               "min": 64, "max": 8192}
    assert t["output_len"] == {"dist": "lognormal", "median": 640, "sigma": 0.6,
                               "min": 128, "max": 2048}
    assert t["check"] == {"sequences": 4, "prompt_len": [96, 1024], "output_len": 1024,
                          "logprobs": 5}
    assert CELL.numbers["rate_per_s"] == pytest.approx(0.8 * CELL.numbers["knee_per_s"], rel=0.02)
    assert (CELL.chips, CELL.traffic_name, CELL.config_name) == (1, "long-answers", "mimo-v2.5")
    assert len(CELL.entry["why"]) <= 200 and "sixteenth" in CELL.entry["why"]


def test_the_check_requests_pass_the_window_and_turn_both_page_shapes_over():
    """Four prompts of 96 to 1,024 tokens, 1,024 generated each: every one
    is past the window of 128 long before its last generated token, takes
    64 window pages of 16 and 32 full pages of 32 while it decodes, and
    gives 60 and more window pages back a group."""
    from benchmark.harness import traffic as T

    reqs, _ = T.check_requests(CELL.traffic, 7, CELL.hf_config["vocab_size"], 12288)
    lengths = [len(r.prompt) for r in reqs]
    assert len(reqs) == 4 and min(lengths) >= 96 and max(lengths) <= 1024
    assert all(r.max_tokens == 1024 and r.logprobs == 5 for r in reqs)
    assert all(max(r.prompt) < CELL.hf_config["vocab_size"] for r in reqs)
    window = CELL.hf_config["sliding_window"]
    assert all((n + 1024 - window) // 16 >= 60 for n in lengths)


def test_the_warm_up_walks_the_ladder_this_cell_really_meets():
    """The cell pins the step to one mixed and one decode-only shape:
    token bucket 512, one request bucket (all the rows), the whole page
    table of 768 pages (of a window group's 16 tokens: the longest table).
    Worked out with the program's own bucket rule."""
    ladder, reached = forms.check_the_warm_up_walks_the_cells_own_ladder(MANIFEST, NAME)
    tokens_b, rows_b, pages_b, budget = ladder
    rows = CELL.engine_args["max_num_seqs"]
    assert (tokens_b, rows_b, pages_b, budget) == ([512], [rows], [768], 512)
    assert reached == {(512, rows, 768), (rows, rows, 768)}
    assert "2 programs" in CELL.numbers["warmup_waves_why"]
    t = CELL.traffic
    assert t["prompt_len"]["max"] + t["output_len"]["max"] <= 768 * 16
    for key in ("knee_why", "engine_args_why", "warmup_waves_why", "correct_limits_why"):
        assert "PROVISIONAL" not in CELL.numbers[key] and len(CELL.numbers[key]) > 200, key


def test_the_limits_of_correct_are_three_and_lie_between_their_readings():
    limits = CELL.numbers["correct_limits"]
    assert set(limits) == {"p50_abs", "p90_abs", "worst_sequence_mean_abs"}
    why = CELL.numbers["correct_limits_why"]
    assert "float8" in why and "sink" in why and "full attention" in why
    assert json.dumps(CELL.numbers)  # plain data
    readings = CELL.numbers["correct_readings"]
    # Read at the cut the file runs (the first session's cut, fifteen sound
    # seeds, stands beside it under ``first_cut``).
    for row in ("sound", "fp8", "no_sink"):
        assert readings[row]["cut"] == "run"
        assert readings[row]["seeds"] == len(readings[row]["seed_list"]) >= 1
    assert readings["sound"]["seeds"] >= 5 and readings["fp8"]["seeds"] >= 2
    for key, limit in limits.items():
        assert readings["sound"]["largest"][key] < limit, key
        for control in ("fp8", "no_sink", "window_as_full"):
            assert limit < readings[control]["smallest"][key], (key, control)


def test_the_new_entries_are_appended_and_list_the_one_cell():
    new = ["attn_kv_window_roofline", "attn_kv_full_roofline", "kv_held_bytes_share"]
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    forms.check_accepted_entries_are_a_prefix(MANIFEST)
    assert not set(new) & set(forms.ACCEPTED["per_layer"])
    for name in new:
        assert by_name[name]["workloads"] == [NAME]
    reported = {m["name"] for m in CELL.per_layer}
    # Every accepted metric that lists no cells is reported here too.
    assert {m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m} <= reported
    assert set(new) | {"ragged_attn_live_share", "sampler_fallback_rows_per_step",
                       "preemptions", "device_idle_share"} <= reported
    # PR 46 (a `benchmark` PR) put the cell on the lists of three accepted
    # entries whose readers find their counters here; ``moe_experts_hit_share``
    # asks the configuration for ``num_experts`` (MiMo's file says
    # ``n_routed_experts``) and the prefix cache is off under KV groups.
    for joined in ("window_pages_freed_share", "kv_pages_in_use_share",
                   "moe_share_gmm_roofline"):
        assert by_name[joined]["workloads"][-1] == NAME and joined in reported
    for other in ("moe_experts_hit_share", "prefix_hit_share"):
        assert NAME not in by_name[other]["workloads"]
    for w in MANIFEST["workloads"]:
        if w["name"] != NAME:
            assert not set(new) & {m["name"] for m in M.Cell(MANIFEST, w["name"]).per_layer}


# ---- appending, with the counts read off the manifest (the accepted
# ``test_manifest_appends.py`` asserts the counts PR 42 accepted as literals
# and fails in two cases since PR 43; PERF.md section 7).


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("appended-after-mimo")
    shutil.copytree(M.BENCH_DIR, root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(M.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    appends.append_to(root)
    return root


def test_this_configuration_states_its_source_its_cuts_and_what_it_stands_for():
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == "mimo-v2.5")
    forms.check_a_configuration_states_its_source_its_cuts_and_what_it_stands_for(MANIFEST, cfg)


def test_appending_still_passes_the_form_checks(tree, monkeypatch):
    monkeypatch.setattr(M, "ROOT", tree)
    monkeypatch.setattr(M, "BENCH_DIR", tree / "benchmark")
    manifest = M.load_manifest()
    assert [len(manifest[k]) for k in ("configs", "workloads", "per_layer")] == [
        len(MANIFEST[k]) + 1 for k in ("configs", "workloads", "per_layer")]
    forms.check_accepted_entries_are_a_prefix(manifest)
    forms.check_top_level_keys_and_limits(manifest)
    forms.check_names_and_units_use_only_the_allowed_characters(manifest)
    for cfg in manifest["configs"]:
        forms.check_a_configuration_states_its_source_its_cuts_and_what_it_stands_for(
            manifest, cfg)
    for w in manifest["workloads"]:
        forms.check_every_file_a_cell_names_exists_and_loads(manifest, w["name"])
        forms.check_the_warm_up_walks_the_cells_own_ladder(manifest, w["name"])
    cell = M.Cell(manifest, appends.CELL)
    assert appends.METRIC in {m["name"] for m in cell.per_layer}
