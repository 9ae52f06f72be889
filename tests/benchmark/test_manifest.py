"""``BENCHMARK.json`` against the files it names, and the rule that a run
without a TPU fails and prints no metrics."""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import manifest as M
from benchmark.harness import traffic as T

MANIFEST = M.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

# What the benchmark has accepted, in its order: each list of BENCHMARK.json
# BEGINS with these names. A PR appends; whatever follows them is free, and
# no test here or elsewhere names a place counted from a list's end or says
# what may come after (PERF.md section 6, PR 42). The form checks below take
# the manifest as an argument, so that ``test_manifest_appends.py`` can run
# them over a manifest with entries this file has never heard of.
ACCEPTED = {
    "configs": ["mistral-7b-v0.3", "sdar-30b-a3b-chat", "kimi-linear-48b-a3b-instruct"],
    "workloads": ["mistral7b.chat", "sdar30b.chat", "kimilinear48b.long-answers"],
    "end_to_end": ["itl_p95_ms", "output_tokens_per_s", "setup_s", "itl_p50_ms"],
    "per_layer": [
        "gen_late_p95_ms", "frontend_ttft_gap_ms", "client_ttft_p50_ms", "queue_wait_mean_ms",
        "gen_tokens_per_step", "prefix_hit_share", "preemptions", "step_interval_ms_mean",
        "compiles_in_window", "decode_attn_roofline", "device_idle_share",
        "schedule_ms_per_step", "prepare_ms_per_step", "output_ms_per_step",
        "device_wait_share", "setup_first_use_s", "decode_attn_roofline_counted",
        "idle_unnamed_share", "diffusion_tokens_per_pass", "moe_experts_hit_share",
        "moe_gmm_roofline", "block_attn_roofline", "sampler_fallback_rows_per_step",
        "ragged_attn_live_share", "kda_roofline", "mla_attn_roofline",
        "linear_state_rows_per_step", "moe_share_gmm_roofline", "kda_chunk_roofline",
        # PR 42: the six readers of PR 38 and PR 39 that had shipped without entries.
        "kda_conv_roofline", "setup_ready_s", "setup_load_model_s", "setup_profile_s",
        "setup_first_use_python_s", "setup_compile_cache_hit_share",
    ],
}


def check_accepted_entries_are_a_prefix(manifest):
    for key, names in ACCEPTED.items():
        got = [e["name"] for e in manifest[key]]
        assert got[:len(names)] == names, f"{key}: an accepted entry moved, changed its name or left"
        assert len(set(got)) == len(got), f"{key}: a name twice"
    # An entry for every reader and a reader for every entry: PR 38 and
    # PR 39 shipped six readers that printed on no line of the ledger.
    readers = {p.stem for p in (M.BENCH_DIR / "layer_metrics").glob("*.py")}
    assert readers == {m["name"] for m in manifest["per_layer"]}


def test_what_the_benchmark_accepted_is_an_ordered_prefix_of_every_list():
    check_accepted_entries_are_a_prefix(MANIFEST)
    assert len(ACCEPTED["per_layer"]) == 35


def check_top_level_keys_and_limits(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer", "trace_in_run"}
    # Per-layer metrics come from the run that measures: `--trace 2`.
    assert manifest["trace_in_run"] is True
    assert 1 <= manifest["run_seconds"] <= 51 and isinstance(manifest["run_seconds"], int)
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert manifest["command"][:2] == ["python3", "benchmark/run_cell.py"]
    assert os.path.getsize(M.ROOT / "BENCHMARK.json") <= 64 * 1024
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) <= max(1, len(cells) // 4)
    assert 1 <= len(manifest["configs"]) <= 24 and 1 <= len(cells) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16 and 1 <= len(manifest["per_layer"]) <= 128
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs), "a pair of configuration and traffic appears once"
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files), "a configuration's file is no other's"


def test_top_level_keys_and_limits():
    check_top_level_keys_and_limits(MANIFEST)


def check_every_file_a_cell_names_exists_and_loads(manifest, cell):
    c = M.Cell(manifest, cell)
    assert c.config_file.is_file() and c.chips in (1, 4)
    assert c.traffic["generator"] in T.GENERATORS
    assert (M.BENCH_DIR / "reference" / f"{c.family}.py").is_file()
    assert c.numbers["correct_limits"] and "rate_per_s" in c.numbers
    assert len(c.entry["why"]) <= 200 and "\n" not in c.entry["why"]
    assert set(c.entry) == {"name", "config", "traffic", "chips", "why"}
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(c.layer_metric(m["name"]).read)
        # The end-to-end metric it should move is one this cell reports.
        assert m["moves"] in reported


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_a_cell_names_exists_and_loads(cell):
    check_every_file_a_cell_names_exists_and_loads(MANIFEST, cell)


def check_names_and_units_use_only_the_allowed_characters(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"] for m in manifest["end_to_end"]}
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    names += cells + [c["name"] for c in manifest["configs"]]
    names += [w["traffic"] for w in manifest["workloads"]]
    names += [k for c in manifest["configs"] for k in c["reduced"]]
    for n in names:
        assert M.NAME_RE.match(n), n
    assert len(set(names[: len(manifest["end_to_end"]) + len(manifest["per_layer"])])) == len(
        manifest["end_to_end"]) + len(manifest["per_layer"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert M.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
        assert set(m.get("workloads", [])) <= set(cells)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_names_and_units_use_only_the_allowed_characters():
    check_names_and_units_use_only_the_allowed_characters(MANIFEST)


def check_a_configuration_states_its_source_its_cuts_and_what_it_stands_for(manifest, cfg):
    data = M.load_json(M.ROOT / cfg["file"])
    bench = data["bench"]
    assert bench["source"] == cfg["source"] and cfg["source"].startswith("https://")
    assert sorted(bench["reduced"]) == sorted(cfg["reduced"])
    for key, cut in bench["reduced"].items():
        assert data[key] == cut["run"] != cut["published"] and cut["why"]
        assert not key.endswith(("_dim", "_rank", "_size")), "a width may never be cut"
    assert isinstance(bench["assumed"], list) and bench["stands_for"]
    assert isinstance(bench["engine_args"], dict) and bench["engine_args_why"]
    assert set(bench["engine_args"]) <= {"max_model_len"}, "a cell's own arguments go in cells/"
    assert any(w["config"] == cfg["name"] for w in manifest["workloads"])
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert cfg["file"].startswith("benchmark/configs/") and len(cfg["reduced"]) <= 16


@pytest.mark.parametrize("cfg", MANIFEST["configs"], ids=lambda c: c["name"])
def test_every_configuration_states_its_source_its_cuts_and_what_it_stands_for(cfg):
    check_a_configuration_states_its_source_its_cuts_and_what_it_stands_for(MANIFEST, cfg)


def test_a_cells_engine_arguments_win_over_its_configurations():
    c = M.Cell(MANIFEST, "mistral7b.chat")
    assert c.config["bench"]["engine_args"] == {"max_model_len": 12288}
    assert c.engine_args == {"max_model_len": 12288, "max_num_batched_tokens": 512,
                             "max_step_compilations": 8}
    c.numbers = {**c.numbers, "engine_args": {"max_model_len": 8192}}
    assert c.engine_args == {"max_model_len": 8192}
    assert c.numbers["engine_args_why"] if "engine_args" in M.load_json(
        M.BENCH_DIR / "cells" / "mistral7b.chat.json") else True


def test_what_a_chat_user_feels_is_end_to_end_and_bounded():
    """Of the four the issue asked for, two are bounded. ``ttft_p95_ms``
    spreads by a fifth from seed to seed, and the driver's check read
    ``ttft_p50_ms`` spreading by more than half of the widest bound there is
    (PERF.md section 2): the first is in every run's log, the second is read
    per layer as ``client_ttft_p50_ms``. A time to first token is so many
    512-token steps, and ``itl_p95_ms`` is what one of them takes. Since
    PR 46 the median of the same gaps stands beside it: the decode-only step,
    four steps in five, which the percentile never falls on."""
    assert list(E2E) == ["itl_p95_ms", "output_tokens_per_s", "setup_s", "itl_p50_ms"]
    assert E2E["output_tokens_per_s"]["better"] == "higher"
    assert E2E["itl_p95_ms"]["bound"] == 0.05
    moved = {m["name"]: m["moves"] for m in MANIFEST["per_layer"]}
    assert moved["client_ttft_p50_ms"] == moved["queue_wait_mean_ms"] == "itl_p95_ms"
    assert moved["gen_tokens_per_step"] == moved["device_idle_share"] == "output_tokens_per_s"
    assert moved["step_interval_ms_mean"] == moved["mixed_step_share"] == "itl_p95_ms"


# The cells whose median gap says something and repeats. ``sdar30b.chat`` commits
# two tokens in a pass, so half of its gaps are 0 by ``score.token_gaps``' rule and
# its median gap read 0.0 in every run there is. ``lagunaxs2.code-mixed`` has TWO
# states: 22.0-22.5 ms in 13 of its 18 logged runs and 24.7-24.9 in 5, one seed
# reading both, 12% apart, which no bound the contract allows can hold (PERF.md
# section 2 has the runs).
MEDIAN_GAP_CELLS = ["mistral7b.chat", "kimilinear48b.long-answers", "mimov25.long-answers"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_median_gap_is_reported_where_it_says_something_and_repeats(cell):
    assert E2E["itl_p50_ms"]["workloads"] == MEDIAN_GAP_CELLS
    assert E2E["itl_p50_ms"]["unit"] == "ms" and E2E["itl_p50_ms"]["better"] == "lower"
    assert E2E["itl_p50_ms"]["source"] == "host_clock"
    reported = [m["name"] for m in M.Cell(MANIFEST, cell).end_to_end]
    assert ("itl_p50_ms" in reported) == (cell in MEDIAN_GAP_CELLS)
    assert reported[:3] == ["itl_p95_ms", "output_tokens_per_s", "setup_s"]


def test_the_median_gaps_bound_lies_between_what_the_runs_spread_and_the_most_allowed():
    """Over twice the widest spread a set of this PR's runs read (2.93%: a
    bound whose half the runs out-spread is refused as too tight), at most
    8 times the widest spread of all the runs of a cell (2.59%), and never
    over the 0.1 the contract allows."""
    assert 2 * 0.0293 < E2E["itl_p50_ms"]["bound"] <= min(8 * 0.0259, 0.1)


@pytest.mark.parametrize("name,cell", [
    ("decode_attn_roofline", "mistral7b.chat"),
    ("decode_attn_roofline_counted", "mistral7b.chat"),
    ("attn_kv_window_roofline", "mimov25.long-answers"),
    ("attn_kv_full_roofline", "mimov25.long-answers")])
def test_a_share_of_the_decode_kernel_bounds_a_claim_on_the_median_gap(name, cell):
    """``moves`` says which step a share bounds. The decode kernel runs only
    in a decode-only step (``ops/attention.py::dispatch_ragged_attention``), and
    the median gap is that step: these four read nothing else. Each lists the
    one cell it is read in, which reports the metric (the form check asks
    that). Whatever a mixed step dominates, and every entry without a list
    (``sdar30b.chat`` prints those too), stays on ``itl_p95_ms``."""
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["moves"] == "itl_p50_ms" and entry["workloads"] == [cell]
    assert cell in MEDIAN_GAP_CELLS


def test_nothing_else_moves_the_median_gap_and_no_entry_without_a_list_does():
    onto_the_median = [m for m in MANIFEST["per_layer"] if m["moves"] == "itl_p50_ms"]
    assert {m["name"] for m in onto_the_median} == {
        "decode_attn_roofline", "decode_attn_roofline_counted",
        "attn_kv_window_roofline", "attn_kv_full_roofline"}
    assert all("workloads" in m for m in onto_the_median)


@pytest.mark.parametrize("name,bound", [
    ("itl_p95_ms", 0.05), ("output_tokens_per_s", 0.015), ("setup_s", 0.1),
    ("itl_p50_ms", 0.08)])
def test_the_bounds_are_what_the_spreads_set(name, bound):
    """``output_tokens_per_s`` stood on 10% until PR 42. Once every seed
    sends one order of work an open loop under its knee completes what was
    offered, and the driver refuses a bound over the larger of 1% and eight
    times the widest spread (it refused 10% in PR 41). It is 1.5% and not
    1%: in ``kimilinear48b.long-answers`` one set of three runs spread by
    0.56% with no stalled run and one run of twelve read 1.3% over the
    others, which a bound of 1% would refuse (PERF.md section 2 has the
    runs). The other two are as they were. ``itl_p50_ms`` (PR 46) is 8% and
    not the 5% its issue capped it at: two sets of three runs a cell of one
    program spread by 2.2-2.9% of the median in ``mimov25.long-answers``
    (two runs of ONE seed differ by up to 2.6% there) and in
    ``kimilinear48b.long-answers`` (the seed's router), and the driver
    refuses a bound whose half its own runs out-spread (PERF.md section 2
    has the runs and the reckoning)."""
    assert E2E[name]["bound"] == bound


def ladder_of(cell: M.Cell) -> tuple[list[int], list[int], list[int], int]:
    """(token buckets, request buckets, page buckets, tokens a step) that
    the server has under the CELL'S OWN engine arguments, through the serve
    command's flags and the program's own bucket rule: the explicit ladders
    where the cell pins them, the thinned default ones where it does not."""
    from benchmark.harness.server import engine_cli_args
    from vllm_tpu.engine.arg_utils import EngineArgs

    parser = EngineArgs.add_cli_args(argparse.ArgumentParser())
    cli = parser.parse_args(["--model", "none", *engine_cli_args(cell.engine_args)])
    config = EngineArgs.from_cli_args(cli).create_engine_config()
    comp, sched = config.compilation_config, config.scheduler_config
    return (comp.token_buckets, comp.request_buckets,
            comp.page_buckets(sched.max_model_len // 16), sched.max_num_batched_tokens)


def bucket(n, ladder: list[int]) -> int:
    return next(b for b in ladder if b >= n)


def programs_of(wave: list, ladder, block: int = 1) -> set[tuple]:
    """The step programs (token, request, page bucket) one warm-up wave is
    there to reach, by the arithmetic of the test this one replaced, on the
    CELL'S ladder: the request bucket from the wave's count, the page
    bucket from its longest context, the token bucket from the step's
    budget. A decoding row takes ``block`` tokens of the budget (1, or a
    diffusion block); where rows are single tokens a step without a
    prompt's chunk runs the decode-only program, keyed (request bucket,
    request bucket, pages), and a row of several tokens has none."""
    tokens_b, rows_b, pages_b, budget = ladder

    def pages(tokens):
        return bucket(-(-tokens // 16), pages_b)

    r = bucket(sum(count for count, *_ in wave), rows_b)
    decode_only = [r] if block == 1 else []
    if len(wave[-1]) == 4:
        # A prompt that arrives while the others decode: a step leaves it
        # the budget less their tokens, and its last few tokens go with them.
        (others, _, _), (_, prompt, answer, _) = wave
        room = budget - block * others
        out = {(tokens_b[-1], r, pages(i * room)) for i in range(1, prompt // room + 1)}
        out |= {(bucket(prompt % room + block * others, tokens_b), r, pages(prompt))}
        return out | {(t, r, pages(ctx)) for t in decode_only
                      for ctx in range(prompt + 1, prompt + answer + 1)}
    top = max(prompt + answer for _, prompt, answer in wave)
    chunk = bucket(min(budget, max(prompt for _, prompt, _ in wave)), tokens_b)
    return {(t, r, pages(top)) for t in [chunk, *decode_only]}


def check_the_warm_up_walks_the_cells_own_ladder(manifest, cell):
    """The ladder is the CELL'S: worked out from its own engine arguments.
    The waves stay on it (no wave is there for a program the cell's server
    never builds), and they walk it: at every request bucket under the last
    one (the head-room over the knee; the only one where the cell pins one)
    and every page bucket a request of the traffic can reach, the mixed
    program of the widest token bucket and, where rows are single tokens,
    the decode-only program."""
    c = M.Cell(manifest, cell)
    ladder = tokens_b, rows_b, pages_b, _ = ladder_of(c)
    block = int(c.hf_config.get("block_length", 1))
    reached = set().union(*(programs_of(w, ladder, block) for w in c.numbers["warmup_waves"]))
    for t, r, b in reached:
        assert r in rows_b and b in pages_b and (t in tokens_b or (t == r and block == 1))
    t = c.traffic
    longest = min(t["prompt_len"]["max"] + t["output_len"]["max"], c.engine_args["max_model_len"])
    walked = {(tb, r, b) for r in (rows_b[:-1] or rows_b)
              for tb in ([tokens_b[-1]] + ([r] if block == 1 else []))
              for b in pages_b if b <= bucket(-(-longest // 16), pages_b)}
    assert walked <= reached, sorted(walked - reached)
    assert len(c.numbers["warmup_waves_why"]) > 100
    return ladder, reached


@pytest.mark.parametrize("cell", CELLS)
def test_the_warm_up_walks_the_cells_ladder_of_step_programs(cell):
    check_the_warm_up_walks_the_cells_own_ladder(MANIFEST, cell)


def test_mistrals_twelve_waves_reach_the_29_programs_of_its_thinned_ladder():
    """As before PR 42, for the cell whose ladder this is: thinned by
    ``max_step_compilations`` 8 to token buckets 16, 512 and request
    buckets 8, 128, 256, 29 programs under 129 running requests, each
    reached by a wave and none beside them."""
    ladder, reached = check_the_warm_up_walks_the_cells_own_ladder(MANIFEST, "mistral7b.chat")
    tokens_b, rows_b, pages_b, budget = ladder
    assert (tokens_b, rows_b, budget) == ([16, 512], [8, 128, 256], 512)
    c = M.Cell(MANIFEST, "mistral7b.chat")
    longest = c.traffic["prompt_len"]["max"] + c.traffic["output_len"]["max"]
    want = {(t, r, b) for r in (8, 128) for t in (r, 512)
            for b in pages_b if b * 16 < 2 * longest and b <= 512}
    # The end of a prompt beside 9 to 15 decoding requests (round 3).
    want |= {(16, 128, b) for b in pages_b if 16 < b <= 512}
    assert reached == want and len(want) == 29
    for wave in c.numbers["warmup_waves"]:
        if len(wave[-1]) == 4:
            (others, _, outlast), (_, prompt, _, after_s) = wave
            room = 512 - others
            assert 0 < prompt % room and prompt % room + others <= 16
            assert outlast > after_s / 0.017 + prompt // room + 20, "the others are still decoding"
        else:
            assert all(answer > -(-prompt // 512) + 8 for _, prompt, answer in wave[:-1]), \
                "the short requests outlast the long one's prefill"


def test_a_cell_that_pins_its_ladder_needs_no_wave_beyond_it():
    """What the two pinned cells carry waves 6-11 for was the old form of
    the test above, which held every cell to Mistral's ladder. Without
    them each still walks its own (the files keep them until the cells are
    re-based: taking them out moves ``setup_s``)."""
    for cell, programs in (("sdar30b.chat", 6), ("kimilinear48b.long-answers", 2)):
        c = M.Cell(MANIFEST, cell)
        ladder = ladder_of(c)
        block = int(c.hf_config.get("block_length", 1))
        waves = c.numbers["warmup_waves"]
        first = set().union(*(programs_of(w, ladder, block) for w in waves[:6]))
        rest = set().union(*(programs_of(w, ladder, block) for w in waves[6:]))
        assert rest <= first and len(first) == programs


def test_mistral_holds_the_published_widths():
    data = M.load_json(M.BENCH_DIR / "configs" / "mistral-7b-v0.3.json")
    published = {"hidden_size": 4096, "intermediate_size": 14336, "num_attention_heads": 32,
                 "num_key_value_heads": 8, "vocab_size": 32768, "max_position_embeddings": 32768,
                 "rope_theta": 1000000.0, "rms_norm_eps": 1e-05, "sliding_window": None}
    assert {k: data[k] for k in published} == published


def test_a_missing_file_is_named():
    with pytest.raises(FileNotFoundError, match="layer_metrics"):
        M.load_module(M.BENCH_DIR / "layer_metrics" / "no_such_metric.py")
    with pytest.raises(KeyError, match="no workload"):
        M.Cell(MANIFEST, "no.such-cell")


def test_without_a_tpu_a_run_exits_non_zero_and_prints_no_metrics():
    """Here JAX is held to the CPU; the server child sees that and refuses,
    and the parent prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(M.BENCH_DIR / "run_cell.py"), "--workload", CELLS[0],
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=str(M.ROOT), env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "no TPU" in p.stdout
    for line in p.stdout.splitlines():
        if line.startswith("{"):
            assert "metrics" not in json.loads(line)
