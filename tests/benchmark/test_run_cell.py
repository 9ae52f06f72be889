"""Pieces of ``run_cell.py`` that need no server."""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from benchmark import run_cell as rc
from benchmark.harness.manifest import Cell, load_manifest
from benchmark.harness.server import end_sessions, engine_cli_args, session_members

from .xplane_writer import xspace


def test_a_trace_without_a_device_plane_is_told_from_one_with(tmp_path):
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [(0, 10, "f")]}]}
    dev = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [(0, 10, "%fusion.1 = x")]}]}
    a = tmp_path / "a" / "plugins" / "profile" / "t"
    b = tmp_path / "b" / "plugins" / "profile" / "t"
    a.mkdir(parents=True)
    b.mkdir(parents=True)
    (a / "h.xplane.pb").write_bytes(xspace([host]))
    (b / "h.xplane.pb").write_bytes(xspace([host, dev]))
    assert not rc.has_device_plane(tmp_path / "a")
    assert rc.has_device_plane(tmp_path / "b")
    assert not rc.has_device_plane(tmp_path / "none")


@pytest.mark.parametrize("key", ["p50_abs", "p99_abs", "worst_sequence_mean_abs"])
def test_each_number_compared_is_printed_beside_its_limit(key):
    cell = Cell(load_manifest(), "mistral7b.chat")
    limits = cell.numbers["correct_limits"]
    sound = {k: v / 2 for k, v in limits.items()}
    ok, checked = rc.decide_correct(cell, sound)
    assert ok and list(checked) == list(limits) and len(limits) == 3
    assert checked[key] == {"value": sound[key], "limit": limits[key]}
    line = rc.compared(key, **checked[key])
    assert f"{key} = " in line and f"limit {limits[key]:g}" in line and "ok" in line
    # One number over its limit is enough, whatever the others read.
    bad, checked = rc.decide_correct(cell, {**sound, key: limits[key] * 2})
    assert not bad
    assert sum("OVER" in rc.compared(k, **c) for k, c in checked.items()) == 1


@pytest.mark.parametrize("args,want", [
    ({}, []),
    ({"kv_cache_dtype": "fp8"}, ["--kv-cache-dtype", "fp8"]),
    ({"enable_prefix_caching": False, "max_model_len": 8192},
     ["--no-enable-prefix-caching", "--max-model-len", "8192"]),
    ({"precompile": True}, ["--precompile"]),
])
def test_engine_arguments_become_the_serve_commands_flags(args, want):
    assert engine_cli_args(args) == want


# ---------------------------------------------------------------------------
# --trace 2: measure first, trace afterwards, in one server
# ---------------------------------------------------------------------------


class StubClient:
    """The client's surface as ``drive`` uses it, against no server: every
    call is written down in order, a plan "runs" until it is told that it
    is not needed any more, and a profile "writes" a trace by hand."""

    log: list = []
    no_device = ()  # attempts whose trace comes back without a device plane

    def __init__(self, port, model):
        self.t0 = 0.0
        self.enough = asyncio.Event()
        self.counter = 0.0
        self.trace_dirs = []

    async def __aenter__(self):
        return self

    async def __aexit__(self, *exc):
        return None

    def now(self):
        return time.perf_counter() - self.t0

    async def ready(self):
        return True

    async def metrics(self):
        self.counter += 1.0
        self.log.append(("scrape", self.counter))
        return {"vllm:step_bucket_compiles": 0.0, "vllm:num_requests_waiting": 0.0,
                'vllm:attn_context_tokens_total{kind="decode"}': 1000.0 * self.counter}

    async def complete(self, req):
        return {"choices": [{"logprobs": {"tokens": ["1"] * req.max_tokens,
                                          "top_logprobs": [{"1": -0.1}] * req.max_tokens}}]}

    async def post_json(self, path, body=None):
        self.log.append(("post", path))
        if path == "/start_profile":
            n = len(self.trace_dirs)
            self.trace_dirs.append(body["trace_dir"])
            planes = [{"name": "/host:CPU", "lines": [
                {"name": "engine-core", "events": [(0, 10, "vllm_tpu.engine.schedule")]}]}]
            if n not in self.no_device:
                planes.append({"name": "/device:TPU:0", "lines": [
                    {"name": "XLA Ops", "events": [(0, 10, "%fusion.1 = x")]}]})
            d = rc.Path(body["trace_dir"]) / "plugins" / "profile" / "t"
            d.mkdir(parents=True)
            (d / "h.xplane.pb").write_bytes(xspace(planes))
            return {"status": "profiling started"}
        return {"status": "profiling stopped", "stop_s": 0.01}

    async def run_plan(self, plan, drain_limit_s):
        self.log.append(("plan", len(plan.requests), plan.score_until))
        if plan.score_until < plan.start_s:
            await self.enough.wait()  # the traced segment: until not needed
        else:
            await asyncio.sleep(max(0.0, plan.end_s - self.now()))
        self.log.append(("plan done", len(plan.requests)))
        return [f"record of {len(plan.requests)}"]


class StubServer:
    port, model_dir = 0, "model"

    def exited(self):
        return None


def _drive_trace2(tmp_path, monkeypatch, no_device=()):
    cell = Cell(load_manifest(), "mistral7b.chat")
    cell.traffic = {**cell.traffic, "ramp_s": 0.3}
    cell.numbers = {**cell.numbers, "warmup_waves": []}
    StubClient.log, StubClient.no_device = [], no_device
    monkeypatch.setattr(rc, "Client", StubClient)
    # Room for every attempt on a loaded machine: the segment ends as soon as
    # its slices are taken, so a run that is on time takes no longer for it.
    monkeypatch.setattr(rc, "SEGMENT_S", 3.0)
    monkeypatch.setattr(rc, "SEGMENT_MORE_S", 0.6)
    args = argparse.Namespace(check_only=False, rate=None, seconds=0.3, seed=5, trace=2,
                              trace_seconds=0.1, trace_slices=2, trace_extension=60.0)
    # (the throw-away first profile of a segment takes 0.2 s of the ramp)
    res = asyncio.run(rc.drive(cell, StubServer(), args, tmp_path))
    return res, list(StubClient.log)


def test_trace_2_fixes_the_windows_records_and_scrapes_before_the_first_start(
        tmp_path, monkeypatch):
    res, log = _drive_trace2(tmp_path, monkeypatch)
    plans = [e for e in log if e[0] == "plan"]
    done = [i for i, e in enumerate(log) if e[0] == "plan done"]
    first_start = log.index(("post", "/start_profile"))
    # The window's plan: scored up to its end, NOT extended, and back,
    # with both of its scrapes in, before the profiler is touched.
    assert len(plans) == 2 and plans[0][2] == pytest.approx(0.3)
    assert done[0] < first_start
    window_scrapes = [e[1] for e in log[:done[0]] if e[0] == "scrape"]
    assert [m['vllm:attn_context_tokens_total{kind="decode"}'] / 1000.0
            for _, m in (res["scrapes"]["m0"], res["scrapes"]["m1"])] == window_scrapes[-2:]
    assert res["records"] == [f"record of {plans[0][1]}"]
    assert res["setup_s"] > 0 and res["drained_s"] >= 0
    # The traced segment: the same mix from the next seed, none of it
    # scored, dropped as soon as the slices are taken.
    assert plans[1][2] < -0.3
    assert res["trace_info"]["records"] == [f"record of {plans[1][1]}"]
    assert log[-1][0] == "plan done" and res["trace_info"]["profiled"] == []


def test_trace_2_scrapes_sit_inside_each_slice_and_a_slice_without_the_device_is_retried(
        tmp_path, monkeypatch):
    # Attempt 0 is the throw-away first pair; the next comes back without
    # a device plane and is taken again.
    res, log = _drive_trace2(tmp_path, monkeypatch, no_device=(1,))
    info = res["trace_info"]
    assert info["attempts"] == 3 and len(info["slices"]) == 2
    assert info["first_stop_s"] == 0.01
    assert not (tmp_path / "trace" / "first").exists()
    assert not (tmp_path / "trace" / "attempt0").exists()
    assert rc.has_device_plane(tmp_path / "trace" / "attempt1")
    seg = log[log.index(("post", "/start_profile")):]
    kinds = [e[0] if e[0] != "post" else e[1] for e in seg if e[0] != "plan done"]
    # start, scrape, scrape, stop: four times (throw-away, refused, two kept).
    assert kinds == ["/start_profile", "scrape", "scrape", "/stop_profile"] * 4
    for sl in info["slices"]:
        assert 0.0 <= sl["lo"] and sl["hi"] - sl["lo"] >= 0.1 and sl["stop_s"] == 0.01
        key = 'vllm:attn_context_tokens_total{kind="decode"}'
        assert sl["m_hi"][key] - sl["m_lo"][key] == 1000.0
    # What the per-layer readers get: counters from the CLEAN window, the
    # device from the traced segment, no profiler stall to leave out.
    scored = {"facts": {}, "metrics": {}}
    ctx = rc.layer_context(Cell(load_manifest(), "mistral7b.chat"), res, scored,
                           {"busy_s": 1.0}, "TPU v5 lite", 0.3)
    assert ctx["slice_delta"]('vllm:attn_context_tokens_total{kind="decode"}') == 2000.0
    assert ctx["slice_delta"]("vllm:no_such_counter_total") is None
    assert ctx["profiled"] == [] and ctx["records"] == res["records"]
    assert ctx["m0"] is res["scrapes"]["m0"][1] and ctx["trace"] == {"busy_s": 1.0}
    assert ctx["trace_slices"] == [(sl["lo"], sl["hi"]) for sl in info["slices"]]


def test_trace_1_slices_carry_no_scrapes_so_a_slice_delta_reads_nothing():
    res = {"scrapes": {"m0": (0.0, {}), "m1": (1.0, {})}, "records": [],
           "trace_info": {"slices": [{"lo": 1.0, "hi": 2.5, "dir": "d", "stopped": 7.0}],
                          "profiled": [(0.9, 7.0)]}}
    ctx = rc.layer_context(Cell(load_manifest(), "mistral7b.chat"), res,
                           {"facts": {}, "metrics": {}}, {}, "TPU v5 lite", 51.0)
    assert ctx["slice_delta"]('vllm:attn_context_tokens_total{kind="decode"}') is None
    assert ctx["profiled"] == [(0.9, 7.0)]


@pytest.mark.parametrize("trace", ["0", "1", "2"])
def test_the_three_kinds_of_run_are_taken(trace, capsys):
    with pytest.raises(SystemExit):
        rc.main(["--workload", "mistral7b.chat", "--seed", "1", "--seconds", "1",
                 "--trace", "3"])
    assert "invalid choice" in capsys.readouterr().err
    ap_ok = ["--workload", "no.such-cell", "--seed", "1", "--seconds", "1", "--trace", trace]
    with pytest.raises(KeyError, match="no workload"):
        rc.main(ap_ok)


# ---------------------------------------------------------------------------
# No process of a run outlives it
# ---------------------------------------------------------------------------

# A child that starts a grandchild (which stays in the child's session),
# writes the grandchild's pid down and then either hangs or returns.
CHILD = ("import subprocess, sys, time\n"
         "p = subprocess.Popen([sys.executable, '-c', 'import time; time.sleep(120)'])\n"
         "open(sys.argv[1], 'w').write(str(p.pid))\n"
         "time.sleep(float(sys.argv[2]))\n")


def alive(pid: int) -> bool:
    try:
        stat = Path(f"/proc/{pid}/stat").read_text()
    except OSError:
        return False
    return stat[stat.rindex(")") + 2] != "Z"


@pytest.mark.parametrize("hangs,limit_s,want", [(120.0, 5.0, -9), (0.0, 60.0, 0)],
                         ids=["cut_at_its_limit", "returns_and_leaves_it"])
def test_a_child_takes_its_grandchild_with_it(tmp_path, hangs, limit_s, want):
    """PR 28 was refused as ``process_left_running``: the checker and the
    reducer run in sessions of their own, and a child killed alone at its
    time limit leaves whatever it started. The whole group goes, whether
    the child was cut or came back."""
    sessions: list[int] = []
    began = time.perf_counter()
    code = rc.run_child([sys.executable, "-c", CHILD, str(tmp_path / "pid"), str(hangs)],
                        dict(os.environ), tmp_path / "child.log", limit_s, sessions)
    assert code == want and time.perf_counter() - began < 30.0
    grandchild = int((tmp_path / "pid").read_text())
    assert len(sessions) == 1 and session_members(sessions) == []
    assert not alive(grandchild)


@pytest.mark.parametrize("dies", [True, False], ids=["it_dies", "it_outlives_the_kill"])
def test_what_a_run_left_behind_is_ended_before_its_line(monkeypatch, capsys, dies):
    """``main`` looks, at its end, through every session the run started and
    ends what it finds there. The line is printed once nothing is left; a
    process that outlives the kill as well (here: the kill reports so) fails
    the run, which then prints none."""
    started = []
    result = {"correct": True, "checked": {}}

    def run(argv, sessions):
        p = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(120)"],
                             start_new_session=True)
        started.append(p)
        sessions.append(p.pid)
        return 0, result

    monkeypatch.setattr(rc, "run", run)
    if not dies:
        monkeypatch.setattr(rc, "end_sessions",
                            lambda sessions, wait_s: end_sessions(sessions) or [(1, "stuck")])
    try:
        assert rc.main([]) == (0 if dies else 1)
        out = capsys.readouterr().out.splitlines()
        assert any("still there at its end" in line for line in out)
        assert any("outlived it" in line for line in out) != dies
        assert [json.loads(line) for line in out if line.startswith("{")] == ([result] if dies else [])
        assert started[0].wait(10) == -9 and session_members([started[0].pid]) == []
    finally:
        end_sessions([p.pid for p in started])


def test_a_clean_run_prints_each_number_beside_its_limit_last(monkeypatch, capsys):
    result = {"correct": True, "attempted": 3, "failed": 0, "metrics": {}, "device": {},
              "checked": {"p50_abs": {"value": 0.0101, "limit": 0.022}}}
    monkeypatch.setattr(rc, "run", lambda argv, sessions: (0, result))
    assert rc.main([]) == 0
    out = capsys.readouterr()
    assert json.loads(out.out.splitlines()[-1]) == result
    assert list(json.loads(out.out.splitlines()[-1]))[-1] == "checked"
    # Once: the lines on standard error, and no second copy in the log.
    assert out.err.splitlines()[-1] == "correct: p50_abs = 0.0101 (limit 0.022) ok"
    assert (out.out + out.err).count("p50_abs = ") == 1
