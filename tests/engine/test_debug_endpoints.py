"""/debug/requests introspection endpoint and the /start_profile
trace_dir body param, against a stub engine — no model, tier-1 fast."""

from __future__ import annotations

import asyncio

from vllm_tpu.entrypoints.openai.api_server import build_app


class StubCore:
    def __init__(self):
        self.calls = []

    def start_profile(self, trace_dir=None, python_tracer_level=0):
        self.calls.append(("start", trace_dir))
        self.tracer_levels = getattr(self, "tracer_levels", [])
        self.tracer_levels.append(python_tracer_level)

    def stop_profile(self):
        self.calls.append(("stop",))
        return {"trace_dir": "/tmp/p", "traced_s": 1.5, "stop_s": 0.25}


class StubEngine:
    _dead = False

    def __init__(self, snapshot=None):
        self.engine_core = StubCore()
        self._snapshot = snapshot if snapshot is not None else {
            "num_in_flight": 1,
            "in_flight": [{
                "request_id": "r1", "trace_id": "ab12", "state": "decode",
                "age_s": 0.5, "num_prompt_tokens": 3, "tokens_emitted": 7,
                "kv_blocks_held": 2, "queue_s": 0.01, "ttft_s": 0.2,
            }],
            "recently_finished": [{
                "request_id": "r0", "trace_id": "cd34",
                "finish_reason": "length", "num_prompt_tokens": 4,
                "num_output_tokens": 8, "num_cached_tokens": 0,
                "peak_kv_blocks": 3,
                "phases": {"queue_s": 0.02, "prefill_s": 0.1,
                           "decode_s": 0.3, "detokenize_s": 0.001,
                           "e2e_s": 0.42},
            }],
        }

    def debug_requests(self):
        return self._snapshot


def _request(engine, method, path, **kw):
    from aiohttp.test_utils import TestClient, TestServer

    async def run():
        app = build_app(engine, "stub")
        async with TestClient(TestServer(app)) as client:
            resp = await client.request(method, path, **kw)
            return resp.status, await resp.json()

    return asyncio.run(run())


def test_debug_requests_returns_both_views():
    engine = StubEngine()
    status, body = _request(engine, "GET", "/debug/requests")
    assert status == 200
    assert body == engine.debug_requests()
    assert body["in_flight"][0]["state"] == "decode"
    assert body["recently_finished"][0]["phases"]["e2e_s"] == 0.42


def test_debug_requests_unsupported_engine_is_501():
    class Bare:
        _dead = False

    status, body = _request(Bare(), "GET", "/debug/requests")
    assert status == 501
    assert "error" in body


def test_start_profile_passes_trace_dir():
    engine = StubEngine()
    status, body = _request(engine, "POST", "/start_profile",
                            json={"trace_dir": "/tmp/prof"})
    assert status == 200
    assert body["trace_dir"] == "/tmp/prof"
    assert engine.engine_core.calls == [("start", "/tmp/prof")]


def test_start_profile_without_body_defaults():
    engine = StubEngine()
    status, body = _request(engine, "POST", "/start_profile")
    assert status == 200
    assert body["trace_dir"] is None
    assert engine.engine_core.calls == [("start", None)]


def test_start_profile_rejects_bad_body():
    engine = StubEngine()
    status, _ = _request(engine, "POST", "/start_profile",
                         data=b"not json",
                         headers={"Content-Type": "application/json"})
    assert status == 400
    status, _ = _request(engine, "POST", "/start_profile",
                         json={"trace_dir": 42})
    assert status == 400
    status, _ = _request(engine, "POST", "/start_profile",
                         json={"python_tracer_level": 7})
    assert status == 400
    assert engine.engine_core.calls == []


def test_start_profile_python_tracer_is_off_unless_asked_for():
    engine = StubEngine()
    _request(engine, "POST", "/start_profile", json={"trace_dir": "/tmp/p"})
    _request(engine, "POST", "/start_profile",
             json={"python_tracer_level": 1})
    assert engine.engine_core.tracer_levels == [0, 1]


def test_stop_profile_roundtrip():
    engine = StubEngine()
    status, _ = _request(engine, "POST", "/start_profile",
                         json={"trace_dir": "/tmp/p"})
    assert status == 200
    status, body = _request(engine, "POST", "/stop_profile")
    assert status == 200
    # The stop answers with the seconds it took and the trace directory.
    assert body == {"status": "profiling stopped", "trace_dir": "/tmp/p",
                    "traced_s": 1.5, "stop_s": 0.25}
    assert engine.engine_core.calls == [("start", "/tmp/p"), ("stop",)]


def test_the_measurement_routes_are_these_and_no_other():
    """One way to read the program from outside: ``/metrics``, the profiler
    control and the two ``/debug`` views. A route added beside them, or one
    of them gone, fails here by name; anything else under those prefixes is
    the 404 of a route that was never there."""
    from aiohttp.test_utils import TestClient, TestServer

    app = build_app(StubEngine(), "stub")
    routes = {(r.method, r.resource.canonical) for r in app.router.routes()
              if r.method != "HEAD"}
    measuring = {(m, p) for m, p in routes if p.startswith(
        ("/metrics", "/debug", "/start_profile", "/stop_profile"))}
    assert measuring == {
        ("GET", "/metrics"), ("GET", "/metrics/cluster"),
        ("POST", "/start_profile"), ("POST", "/stop_profile"),
        ("GET", "/debug/requests"), ("GET", "/debug/deadletter"),
    }

    async def run():
        async with TestClient(TestServer(app)) as client:
            return [(await client.request(m, p)).status for m, p in (
                ("GET", "/debug/no-such-view"), ("POST", "/debug/requests"),
                ("GET", "/debug/requests"))]

    assert asyncio.run(run()) == [404, 405, 200]


def test_the_observability_options_are_these_and_no_other(capsys):
    """The options that turn measurement on are a list held by name (a
    simplification adds none), each reaches the server's parser as a flag,
    and a flag the parser does not have is refused by name, exit 2."""
    import argparse
    import dataclasses

    import pytest

    from vllm_tpu.config import ObservabilityConfig
    from vllm_tpu.engine.arg_utils import AsyncEngineArgs

    assert {f.name for f in dataclasses.fields(ObservabilityConfig)} == {
        "collect_detailed_traces", "otlp_traces_endpoint", "log_stats",
        "log_stats_interval_s", "request_trace_dir", "slo_targets"}
    parser = AsyncEngineArgs.add_cli_args(argparse.ArgumentParser())
    args = parser.parse_args(["--model", "m", "--request-trace-dir", "/t",
                              "--slo-targets", "a=ttft:1s"])
    assert (args.request_trace_dir, args.slo_targets) == ("/t", "a=ttft:1s")
    with pytest.raises(SystemExit) as exc:
        parser.parse_args(["--model", "m", "--no-such-interval-s", "1"])
    assert exc.value.code == 2
    assert ("unrecognized arguments: --no-such-interval-s"
            in capsys.readouterr().err)
