"""``BENCHMARK.json`` against the files it names, and the rule that a run
without a TPU fails and prints no metrics."""

from __future__ import annotations

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


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer", "trace_in_run"}
    # Per-layer metrics come from the run that measures: `--trace 2`.
    assert MANIFEST["trace_in_run"] is True
    assert 1 <= MANIFEST["run_seconds"] <= 51 and isinstance(MANIFEST["run_seconds"], int)
    assert MANIFEST["paths"] == ["benchmark", "tests/benchmark"]
    assert MANIFEST["command"][:2] == ["python3", "benchmark/run_cell.py"]
    assert os.path.getsize(M.ROOT / "BENCHMARK.json") <= 64 * 1024
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_a_cell_names_exists_and_loads(cell):
    c = M.Cell(MANIFEST, cell)
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


def test_names_and_units_use_only_the_allowed_characters():
    names = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    names += CELLS + [c["name"] for c in MANIFEST["configs"]]
    names += [w["traffic"] for w in MANIFEST["workloads"]]
    names += [k for c in MANIFEST["configs"] for k in c["reduced"]]
    for n in names:
        assert M.NAME_RE.match(n), n
    assert len(set(names[: len(MANIFEST["end_to_end"]) + len(MANIFEST["per_layer"])])) == len(
        MANIFEST["end_to_end"]) + len(MANIFEST["per_layer"])
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert M.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in E2E and 1 <= len(m["layer"]) <= 200
        assert set(m.get("workloads", [])) <= set(CELLS)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


@pytest.mark.parametrize("cfg", MANIFEST["configs"], ids=lambda c: c["name"])
def test_every_configuration_states_its_source_its_cuts_and_what_it_stands_for(cfg):
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
    assert any(w["config"] == cfg["name"] for w in MANIFEST["workloads"])


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
    512-token steps, and ``itl_p95_ms`` is what one of them takes."""
    assert list(E2E) == ["itl_p95_ms", "output_tokens_per_s", "setup_s"]
    assert E2E["output_tokens_per_s"]["better"] == "higher"
    assert E2E["itl_p95_ms"]["bound"] == 0.05
    moved = {m["name"]: m["moves"] for m in MANIFEST["per_layer"]}
    assert moved["client_ttft_p50_ms"] == moved["queue_wait_mean_ms"] == "itl_p95_ms"
    assert moved["gen_tokens_per_step"] == moved["device_idle_share"] == "output_tokens_per_s"
    assert moved["step_interval_ms_mean"] == moved["decode_attn_roofline"] == "itl_p95_ms"


@pytest.mark.parametrize("cell", CELLS)
def test_the_warm_up_walks_the_cells_ladder_of_step_programs(cell):
    """The step programs a chat server can meet under the cell's engine
    arguments, worked out with the program's own bucket rule, against the
    ones the waves are there to reach: (token, request, page) buckets."""
    from vllm_tpu.config import CompilationConfig, SchedulerConfig

    c = M.Cell(MANIFEST, cell)
    comp = CompilationConfig(max_step_compilations=c.engine_args["max_step_compilations"])
    comp.finalize(SchedulerConfig(max_num_batched_tokens=c.engine_args["max_num_batched_tokens"]))
    assert comp.token_buckets == [16, 512] and comp.request_buckets == [8, 128, 256]
    pages = comp._pow2_buckets(16, c.engine_args["max_model_len"] // 16)
    longest = c.traffic["prompt_len"]["max"] + c.traffic["output_len"]["max"]
    def pages_of(tokens):
        return next(x for x in pages if x * 16 >= tokens)

    reached = set()
    for wave in c.numbers["warmup_waves"]:
        n = sum(count for count, *_ in wave)
        r = next(b for b in comp.request_buckets if b >= n)
        if len(wave[-1]) == 4:
            # A prompt that arrives while the n - 1 others decode: a step
            # leaves it 512 - (n - 1) tokens, and its last few tokens with
            # their decode tokens fit the smallest token bucket.
            (others, short, outlast), (_, prompt, answer, after_s) = wave
            room = 512 - others
            assert r == 128 and 0 < prompt % room and prompt % room + others <= 16
            assert outlast > after_s / 0.017 + prompt // room + 20, "the others are still decoding"
            reached |= {(512, r, pages_of(i * room)) for i in range(1, prompt // room + 1)}
            reached |= {(16, r, pages_of(prompt))}
            reached |= {(r, r, pages_of(ctx)) for ctx in range(prompt + 1, prompt + answer + 1)}
            continue
        top = max(prompt + answer for _, prompt, answer in wave)
        b = pages_of(top)
        assert all(answer > -(-prompt // 512) + 8 for _, prompt, answer in wave[:-1]), \
            "the short requests outlast the long one's prefill"
        reached |= {(512, r, b), (r, r, b)}
    want = {(t, r, b) for r in (8, 128) for t in (r, 512)
            for b in pages if b * 16 < 2 * longest and b <= 512}
    # The end of a prompt beside 9 to 15 decoding requests (round 3).
    want |= {(16, 128, b) for b in pages if 16 < b <= 512}
    assert reached == want and len(want) == 29
    assert len(c.numbers["warmup_waves_why"]) > 100


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
