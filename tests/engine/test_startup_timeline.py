"""The start's timeline (``tracing.StartupTimeline``): the real ``serve``
command on a tiny model on the CPU, through the in-process and the
multiprocess client, read from its log, its ``/metrics`` and its chrome
trace; and the timeline's own arithmetic on a clock written down by hand."""

from __future__ import annotations

import glob
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

from tests.models.utils import tiny_llama_dir
from vllm_tpu.core.sched_output import SchedulerStats
from vllm_tpu.metrics.prometheus import PrometheusRegistry
from vllm_tpu.tracing import StartupTimeline, process_created

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
PHASES = ["import", "init_device", "load_model", "profile", "kv_cache",
          "precompile", "engine", "frontend"]
SERIES = re.compile(r'^vllm:startup_seconds\{phase="(\w+)"\} (\S+)$', re.M)
LOG_LINE = re.compile(r"startup: (import [^\n]*), total ([\d.]+) s")


def _get(port: int, path: str) -> str:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=5) as r:
        return r.read().decode()


@pytest.fixture(scope="module", params=["inproc", "mp"])
def server(request, tmp_path_factory):
    """``vllm-tpu serve`` as an operator starts it, up to its first answer
    on ``/health``; one request; then SIGTERM, so the trace files close."""
    work = tmp_path_factory.mktemp(f"startup_{request.param}")
    model = tiny_llama_dir(work / "model")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu", VLLM_TPU_NO_USAGE_STATS="1",
               VLLM_TPU_TRACE_DIR=str(work / "trace"),
               PYTHONPATH=REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    cmd = [sys.executable, "-m", "vllm_tpu.entrypoints.cli.main", "serve", model,
           "--host", "127.0.0.1", "--port", str(port), "--dtype", "float32",
           "--max-model-len", "128", "--block-size", "16",
           "--num-gpu-blocks-override", "64", "--max-num-seqs", "8",
           "--max-num-batched-tokens", "128"]
    if request.param == "mp":
        cmd += ["--distributed-executor-backend", "mp"]
    log_path = work / "server.log"
    with open(log_path, "w") as log:  # a file: a pipe nobody reads fills up
        began = time.perf_counter()
        proc = subprocess.Popen(cmd, env=env, cwd=REPO_ROOT, stdout=log,
                                stderr=subprocess.STDOUT, start_new_session=True)
        try:
            deadline = began + 300
            while True:
                try:
                    _get(port, "/health")
                    break
                except OSError:
                    assert proc.poll() is None, log_path.read_text()[-3000:]
                    assert time.perf_counter() < deadline, "the server never answered"
                    time.sleep(0.02)
            ready_s = time.perf_counter() - began
            at_ready = _get(port, "/metrics")
            body = json.dumps({"model": model, "prompt": [1, 2, 3, 4, 5],
                               "max_tokens": 4, "temperature": 0}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/completions", body,
                {"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                assert r.status == 200
            after_one = _get(port, "/metrics")
            again = _get(port, "/metrics")
        finally:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(60)
            except subprocess.TimeoutExpired:
                pass
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    traces = {}
    for path in glob.glob(str(work / "trace" / "trace-*.json")):
        text = open(path).read().rstrip().rstrip(",")
        traces[path] = json.loads(text if text.endswith("]") else text + "]")
    return {"mode": request.param, "ready_s": ready_s, "at_ready": at_ready,
            "after_one": after_one, "again": again, "traces": traces,
            "log": log_path.read_text()}


def _gauge(text: str) -> dict[str, float]:
    return {phase: float(v) for phase, v in SERIES.findall(text)}


def test_every_phase_is_on_metrics_once_with_a_sample_and_none_is_negative(server):
    got = _gauge(server["after_one"])
    assert sorted(got) == sorted(PHASES)
    assert len(SERIES.findall(server["after_one"])) == len(PHASES)
    assert all(v >= 0.0 for v in got.values()), got
    assert "# TYPE vllm:startup_seconds gauge" in server["after_one"]
    # The tiny model is given its block count: nothing to profile.
    assert got["profile"] < 0.05 and got["precompile"] < 0.05
    # Set at the start, never again.
    assert _gauge(server["again"]) == got


def test_the_phases_add_up_to_creation_to_ready(server):
    total = sum(_gauge(server["after_one"]).values())
    # From Popen to the first answer on /health, polled every 20 ms.
    assert total == pytest.approx(server["ready_s"], rel=0.05), (
        total, server["ready_s"])
    # Creation is read to a clock tick, rounded down.
    assert total <= server["ready_s"] + 0.02


def test_the_log_has_the_phases_in_the_order_they_ended(server):
    lines = LOG_LINE.findall(server["log"])
    named = [[part.split()[0] for part in phases.split(", ")] for phases, _ in lines]
    if server["mode"] == "inproc":
        assert named == [PHASES]
        assert float(lines[0][1]) == pytest.approx(
            sum(_gauge(server["after_one"]).values()), abs=0.06)
    else:
        # The engine's process first (its READY), then the front end's.
        assert named == [PHASES[:-1], ["import", "frontend"]]
    # Each phase also logs as it ends, with what it made.
    for phase in PHASES:
        assert re.search(rf"startup: {phase} [\d.]+ s", server["log"]), phase
    assert re.search(r"startup: load_model [\d.]+ s \([\d.]+ GB of weights", server["log"])
    assert re.search(r"startup: kv_cache [\d.]+ s \(64 blocks of 16 tokens\)", server["log"])


def test_what_is_on_metrics_when_the_server_first_answers(server):
    got = _gauge(server["at_ready"])
    if server["mode"] == "inproc":
        assert sorted(got) == sorted(PHASES)  # one process: all of it is known
        assert got == _gauge(server["after_one"])
    else:
        # The engine's phases cross with its first stats snapshot, once;
        # until then the front end shows its own, and `import` grows by
        # the engine process's when they come.
        assert sorted(got) == ["frontend", "import"]
        after = _gauge(server["after_one"])
        assert after["frontend"] == got["frontend"]
        assert after["import"] > got["import"]


def test_the_chrome_sink_holds_one_startup_span_over_the_phases(server):
    files = server["traces"]
    assert len(files) == (1 if server["mode"] == "inproc" else 2)
    children, own, whole = [], [], []
    for events in files.values():
        spans = [e for e in events if e.get("cat") == "startup"]
        [parent] = [e for e in spans if e["name"] == "startup"]
        inside = [e for e in spans if e is not parent]
        for e in inside:
            assert e["ph"] == "X" and e["pid"] == parent["pid"]
            assert parent["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1
            assert e["args"]["self_s"] <= e["dur"] / 1e6 + 1e-6
        own.append(sum(e["args"]["self_s"] for e in inside))
        whole.append(parent["dur"] / 1e6)
        children += [e["name"] for e in inside]
    if server["mode"] == "inproc":
        assert sorted(children) == sorted(PHASES)  # eight
        assert own[0] == pytest.approx(whole[0], abs=0.01)
    else:
        assert sorted(children) == sorted(PHASES + ["import"])
        # The engine's process (the shorter span) accounts for all of its
        # own start, and the two together for the front end's, which waited
        # for it (less the spawn and the READY message on their way).
        engine = whole.index(min(whole))
        assert own[engine] == pytest.approx(whole[engine], abs=0.01)
        assert sum(own) == pytest.approx(max(whole), abs=0.5)
    # The compile listener writes there too: a span an event.
    names = {e["name"] for events in files.values() for e in events
             if e.get("cat") == "compile"}
    assert names == {"compile.trace", "compile.lower", "compile.backend"}
    [first_use] = [e for events in files.values() for e in events
                   if e["name"] == "first_use"][:1]
    assert {"trace", "lower", "backend", "run", "cache", "key"} <= set(first_use["args"])


# ---------------------------------------------------------------------
# The timeline's arithmetic, without a server.
# ---------------------------------------------------------------------

def test_a_phase_reads_its_own_seconds_and_the_phases_add_up():
    t = StartupTimeline(created=time.perf_counter() - 1.0)
    t.imported()
    t.begin("frontend")
    time.sleep(0.03)
    with t.phase("engine"):
        time.sleep(0.02)
        with t.phase("load_model") as note:
            time.sleep(0.05)
            note.append("7.5 GB")
    time.sleep(0.01)
    snap = t.ready()
    phases = snap["phases"]
    assert list(phases) == ["import", "load_model", "engine", "frontend"]
    # Each at least what it slept (a loaded machine sleeps longer), and a
    # phase holds none of the sleeps of the phases inside it.
    assert 1.0 <= phases["import"] < 1.5
    assert 0.05 <= phases["load_model"] < 0.5
    assert 0.02 <= phases["engine"] < 0.5
    assert 0.04 <= phases["frontend"] < 0.5
    assert sum(phases.values()) == pytest.approx(t.ready_at - t.created, abs=1e-6)
    assert snap["pid"] == os.getpid() and snap["created"] == t.created


def test_a_phase_counts_once_and_nothing_moves_after_ready():
    t = StartupTimeline(created=time.perf_counter())
    with t.phase("engine"):
        time.sleep(0.01)
    first = dict(t.phases)
    with t.phase("engine"):  # a second engine built in the process
        time.sleep(0.01)
    assert t.phases == first
    t.ready()
    with t.phase("kv_cache"):
        pass
    t.imported()
    assert t.ready() == t.snapshot() and t.phases == first


def test_the_wait_for_another_process_is_nobodys_time_here():
    t = StartupTimeline(created=time.perf_counter())
    t.begin("frontend")
    with t.phase(None):  # the spawned engine keeps its own timeline
        time.sleep(0.05)
    time.sleep(0.01)
    phases = t.ready()["phases"]
    assert list(phases) == ["frontend"]
    assert 0.01 <= phases["frontend"] < t.ready_at - t.created - 0.05


def test_the_process_creation_time_is_on_perf_counters_clock():
    created = process_created()
    assert 0.0 < time.perf_counter() - created < 3600.0
    child = subprocess.run(
        [sys.executable, "-c",
         "import sys, time; sys.path.insert(0, sys.argv[1]); t = time.perf_counter()\n"
         "from vllm_tpu.tracing import process_created\n"
         "print(t - process_created())", REPO_ROOT],
        capture_output=True, text=True, timeout=60)
    assert child.returncode == 0, child.stderr
    # The interpreter's own start: some tens of milliseconds, to a tick.
    assert 0.0 <= float(child.stdout) < 2.0


def test_the_registry_adds_processes_and_leaves_a_respawn_out():
    reg = PrometheusRegistry()
    now = time.perf_counter()
    reg.record_startup({"pid": 1, "created": now - 50.0,
                        "phases": {"import": 2.0, "frontend": 5.0}})
    reg.record(SchedulerStats(startup={
        "pid": 2, "created": now - 45.0,
        "phases": {"import": 3.0, "load_model": 30.0, "engine": 1.0}}))
    want = {"import": 5.0, "frontend": 5.0, "load_model": 30.0, "engine": 1.0}
    assert _gauge(reg.render()) == want
    # An engine created after the server was ready is a respawn.
    reg.record(SchedulerStats(startup={
        "pid": 3, "created": time.perf_counter() + 1.0,
        "phases": {"import": 3.0, "load_model": 31.0}}))
    reg.record(SchedulerStats())
    assert _gauge(reg.render()) == want
