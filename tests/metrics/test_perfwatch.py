"""Perfwatch: streaming op-split, quiet-window scheduling, roofline
math, the /debug/perf endpoints, and the capture + kernel-A/B path end
to end on a CPU engine (ISSUE 10).

The xplane fixtures hand-encode the protobuf wire format (the same
schema ``vllm_tpu/metrics/op_split.py`` reads), so the streaming parser
is tested without a TPU or a profiler run.
"""

from __future__ import annotations

import asyncio
import os

import pytest

# ---------------------------------------------------------------------------
# Synthetic xplane encoding (XSpace wire format; see op_split.py).
# ---------------------------------------------------------------------------


def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _tag(field: int, wire_type: int) -> bytes:
    return _varint((field << 3) | wire_type)


def _len_field(field: int, payload: bytes) -> bytes:
    return _tag(field, 2) + _varint(len(payload)) + payload


def _varint_field(field: int, v: int) -> bytes:
    return _tag(field, 0) + _varint(v)


def make_xplane(ops: list[tuple[str, int]],
                line_name: str = "XLA Ops") -> bytes:
    """An XSpace with one plane, one line, and ``ops`` as
    ``(op_name, duration_ps)`` events."""
    events = b""
    metadata = b""
    for i, (name, dur_ps) in enumerate(ops, start=1):
        events += _len_field(4, _varint_field(1, i) + _varint_field(3, dur_ps))
        meta = _varint_field(1, i) + _len_field(2, name.encode())
        metadata += _len_field(4, _varint_field(1, i) + _len_field(2, meta))
    line = _len_field(2, line_name.encode()) + events
    plane = (_len_field(2, b"/device:TPU:0") + _len_field(3, line)
             + metadata)
    return _len_field(1, plane)


def _write_trace(tmp_path, ops, line_name="XLA Ops"):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True, exist_ok=True)
    (d / "host.xplane.pb").write_bytes(make_xplane(ops, line_name))
    return str(tmp_path)


def test_op_split_stream_from_synthetic_trace(tmp_path):
    from vllm_tpu.metrics.op_split import OpSplitStream

    trace = _write_trace(tmp_path, [
        ("fused_ragged_paged_attention.1", 4_000_000_000),  # 4 ms
        ("dot_general.2", 2_000_000_000),                   # 2 ms
        ("all-reduce.3", 1_000_000_000),                    # 1 ms (comms)
        ("sort.4", 500_000_000),                            # 0.5 ms
        ("copy.5", 500_000_000),                            # 0.5 ms
    ])
    stream = OpSplitStream()
    assert stream.split_ms() is None  # nothing streamed yet
    assert stream.add_trace(trace) == 5
    split = stream.split_ms()
    assert split == {"attention": 4.0, "matmul": 2.0, "sampler": 0.5,
                     "comms": 1.0, "other": 0.5, "total": 8.0}
    # Per-step scaling (2 steps): every phase halves.
    assert stream.split_ms(scale=0.5)["total"] == 4.0
    assert stream.split_ms(scale=0.5)["comms"] == 0.5


def test_op_split_stream_accumulates_across_traces(tmp_path):
    from vllm_tpu.metrics.op_split import OpSplitStream

    t1 = _write_trace(tmp_path / "a", [("dot.1", 1_000_000_000)])
    t2 = _write_trace(tmp_path / "b", [("dot.2", 3_000_000_000)])
    stream = OpSplitStream()
    stream.add_trace(t1)
    stream.add_trace(t2)
    assert stream.split_ms()["matmul"] == 4.0


def test_op_split_stream_ignores_non_xla_lines(tmp_path):
    from vllm_tpu.metrics.op_split import OpSplitStream

    trace = _write_trace(
        tmp_path, [("dot.1", 1_000_000_000)], line_name="Steps")
    stream = OpSplitStream()
    assert stream.add_trace(trace) == 0
    assert stream.split_ms() is None


# ---------------------------------------------------------------------------
# Quiet-window / PerfWatch scheduling (fake clock; no engine).
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def test_quiet_window_settle():
    from vllm_tpu.metrics.perfwatch import QuietWindow

    clock = FakeClock()
    qw = QuietWindow(settle_s=2.0, clock=clock)
    assert qw.state == QuietWindow.BUSY
    qw.update(busy=False)
    assert qw.state == QuietWindow.SETTLING
    clock.t += 1.0
    assert qw.state == QuietWindow.SETTLING
    clock.t += 1.5
    assert qw.state == QuietWindow.QUIET
    # Any busy observation resets the machine.
    qw.update(busy=True)
    assert qw.state == QuietWindow.BUSY
    qw.update(busy=False)
    assert qw.state == QuietWindow.SETTLING


def test_perfwatch_interval_capture_fires_when_busy():
    from vllm_tpu.metrics.perfwatch import PerfWatch

    clock = FakeClock()
    pw = PerfWatch(interval_s=10.0, quiet_settle_s=2.0, clock=clock)
    assert pw.poll(busy=True) is None  # not due yet
    clock.t += 10.0
    assert pw.poll(busy=True) == "capture"
    assert pw.poll(busy=True) is None  # tick consumed, next in 10s
    clock.t += 10.0
    assert pw.poll(busy=True) == "capture"


def test_perfwatch_interval_ab_waits_for_quiet():
    from vllm_tpu.metrics.perfwatch import PerfWatch

    clock = FakeClock()
    pw = PerfWatch(interval_s=10.0, quiet_settle_s=2.0, clock=clock)
    pw.poll(busy=True)
    clock.t += 10.0
    # Due, but the engine only just went idle: the tick is held through
    # the settle, then fires as an A/B.
    assert pw.poll(busy=False) is None
    clock.t += 1.0
    assert pw.poll(busy=False) is None
    clock.t += 1.5
    assert pw.poll(busy=False) == "ab"


def test_perfwatch_disabled_never_fires():
    from vllm_tpu.metrics.perfwatch import PerfWatch

    clock = FakeClock()
    pw = PerfWatch(interval_s=0.0, clock=clock)
    for _ in range(5):
        clock.t += 1e6
        assert pw.poll(busy=True) is None
        assert pw.poll(busy=False) is None


def test_perfwatch_armed_waits_for_matching_state():
    from vllm_tpu.metrics.perfwatch import PerfWatch

    clock = FakeClock()
    pw = PerfWatch(interval_s=0.0, quiet_settle_s=2.0, clock=clock)
    ack = pw.arm(mode="capture")
    assert ack == {"armed": "capture", "force": False}
    # A capture needs live traffic: stays armed while idle.
    assert pw.poll(busy=False) is None
    assert pw.armed
    assert pw.poll(busy=True) == "capture"
    assert not pw.armed
    # An A/B needs quiet: force skips the settle timer.
    pw.arm(mode="ab", force=True)
    assert pw.poll(busy=True) is None  # never past live traffic
    assert pw.poll(busy=False) == "ab"
    # Without force, the settle timer gates it.
    pw.arm(mode="ab")
    assert pw.poll(busy=False) is None
    clock.t += 2.5
    assert pw.poll(busy=False) == "ab"
    # Unknown modes are rejected at arm time.
    assert "error" in pw.arm(mode="bogus")


def test_perfwatch_capture_session_and_roofline():
    from vllm_tpu.metrics.perfwatch import PerfWatch
    from vllm_tpu.metrics.roofline import RooflineModel

    clock = FakeClock()
    pw = PerfWatch(interval_s=0.0, capture_steps=2, clock=clock)
    pw.begin_capture("/tmp/x", None,
                     {"launch_sampled_tokens": 100, "step_launches": 10})
    assert not pw.note_step()
    assert pw.note_step()  # hit the 2-step target
    clock.t += 2.0  # window took 2 s
    rl = RooflineModel(weight_bytes=197e9 // 2, active_params=0,
                       kv_tok_bytes=0, device_kind="TPU v5e")
    snap = pw.finish_capture(
        {"attention": 1.0, "total": 2.0},
        {"launch_sampled_tokens": 300, "step_launches": 14},
        ctx_tokens=0, roofline=rl)
    assert pw.captures_total == 1 and pw.active is None
    assert snap["steps"] == 2
    assert snap["tok_per_s"] == 100.0  # (300-100)/2s
    # 2 steps/s * (197e9/2 bytes + 0 KV) / 819e9 B/s peak
    assert snap["hbm_bw_util_est"] == pytest.approx(0.2405, abs=1e-3)
    assert snap["device_ms_per_step"]["attention"] == 1.0
    fields = pw.stats_fields()
    assert fields["perfwatch_captures"] == 1
    assert fields["perfwatch_mfu_est"] == snap["mfu_est"]


def test_perfwatch_abort_counts():
    from vllm_tpu.metrics.perfwatch import PerfWatch

    pw = PerfWatch(clock=FakeClock())
    pw.begin_capture("/tmp/x", 4, {})
    pw.abort_capture("engine went idle")
    assert pw.active is None
    assert pw.captures_aborted == 1
    # Aborted A/B replays count into the same abort counter.
    pw.note_ab({"kind": "ab", "aborted": True, "reason": "traffic"})
    assert pw.captures_aborted == 2
    assert pw.ab_runs_total == 0
    pw.note_ab({"kind": "ab", "aborted": False, "ab": {}})
    assert pw.ab_runs_total == 1


def test_ab_delta_pct():
    from vllm_tpu.metrics.perfwatch import ab_delta_pct

    assert ab_delta_pct(8.0, 10.0) == -20.0  # kernel on is 20% faster
    assert ab_delta_pct(None, 10.0) is None
    assert ab_delta_pct(8.0, None) is None
    assert ab_delta_pct(0.0, 10.0) is None


# ---------------------------------------------------------------------------
# Roofline math.
# ---------------------------------------------------------------------------


def test_roofline_model_math():
    from vllm_tpu.metrics.roofline import RooflineModel

    m = RooflineModel(weight_bytes=16_000_000_000,
                      active_params=8_000_000_000,
                      kv_tok_bytes=1024, device_kind="TPU v5e")
    # 2000 tok/s * 2 FLOPs/param * 8e9 params / 197e12 peak.
    assert m.mfu(2000.0) == pytest.approx(0.16244, abs=1e-4)
    assert m.mfu(0.0) == 0.0
    # One step reads all weights + ctx KV.
    assert m.hbm_bytes_per_step(1000) == 16_000_000_000 + 1024_000
    assert m.hbm_bw_util(30.0, 1000) == pytest.approx(
        (16_000_000_000 + 1024_000) * 30.0 / 819e9, rel=1e-6)
    # Round-trips the worker->engine RPC boundary.
    assert RooflineModel.from_dict(m.to_dict()) == m


def test_unknown_device_kind_has_no_peak():
    """A device that is not in the peak table has no peak: the math
    raises a KeyError naming the kind, and a capture publishes no
    estimate for it (never a share of some other chip's peak)."""
    from vllm_tpu.metrics.perfwatch import PerfWatch
    from vllm_tpu.metrics.roofline import RooflineModel

    m = RooflineModel(weight_bytes=1_000, active_params=1_000,
                      kv_tok_bytes=8, device_kind="cpu")
    assert not m.has_peaks
    with pytest.raises(KeyError, match="cpu"):
        m.mfu(100.0)
    with pytest.raises(KeyError, match="cpu"):
        m.hbm_bw_util(10.0, 100)
    clock = FakeClock()
    pw = PerfWatch(interval_s=0.0, capture_steps=1, clock=clock)
    pw.begin_capture("/tmp/x", None,
                     {"launch_sampled_tokens": 0, "step_launches": 0})
    pw.note_step()
    clock.t += 1.0
    snap = pw.finish_capture(
        None, {"launch_sampled_tokens": 10, "step_launches": 10},
        ctx_tokens=0, roofline=m)
    assert snap["tok_per_s"] == 10.0
    assert snap["mfu_est"] is None and snap["hbm_bw_util_est"] is None
    assert "device_kind" not in snap


def test_roofline_param_helpers():
    import numpy as np

    from vllm_tpu.metrics import roofline as rf

    params = {
        "w": np.zeros((4, 4), dtype=np.float32),  # 64 B, 16 params
        "q": np.zeros((8,), dtype=np.uint8),      # 8 B, 16 logical (int4)
    }
    assert rf.weight_bytes(params) == 64 + 8
    assert rf.logical_params(params) == 16 + 16
    assert rf.kv_bytes_per_token(2, 4, 64, 2) == 2 * 2 * 4 * 64 * 2


# ---------------------------------------------------------------------------
# /debug/perf endpoints (stub engine; full engine covered below).
# ---------------------------------------------------------------------------


class StubPerfCore:
    def __init__(self):
        self.captured = None
        self._status = {
            "enabled": True, "armed": False, "capturing": False,
            "captures_total": 3, "captures_aborted_total": 1,
            "ab_runs_total": 1, "last_capture": {"steps": 8},
            "last_ab": None, "last_batch_shape": None,
        }

    def perf_status(self):
        return dict(self._status)

    def perf_capture(self, opts):
        self.captured = opts
        if opts["mode"] not in ("auto", "capture", "ab"):
            return {"error": f"unknown mode {opts['mode']!r}"}
        return {"armed": opts["mode"], "force": opts["force"]}


class StubPerfEngine:
    _dead = False

    def __init__(self):
        self.engine_core = StubPerfCore()


def _request(engine, method, path, **kw):
    from aiohttp.test_utils import TestClient, TestServer

    from vllm_tpu.entrypoints.openai.api_server import build_app

    async def run():
        app = build_app(engine, "stub")
        async with TestClient(TestServer(app)) as client:
            resp = await client.request(method, path, **kw)
            return resp.status, await resp.json()

    return asyncio.run(run())


def test_debug_perf_get():
    engine = StubPerfEngine()
    status, body = _request(engine, "GET", "/debug/perf")
    assert status == 200
    assert body["captures_total"] == 3
    assert body["last_capture"] == {"steps": 8}


def test_debug_perf_capture_arms():
    engine = StubPerfEngine()
    status, body = _request(
        engine, "POST", "/debug/perf/capture",
        json={"mode": "ab", "steps": 4, "force": True})
    assert status == 200
    assert body["capture"] == {"armed": "ab", "force": True}
    assert engine.engine_core.captured == {
        "mode": "ab", "steps": 4, "force": True}
    assert body["status"]["captures_total"] == 3


def test_debug_perf_capture_rejects_bad_mode():
    engine = StubPerfEngine()
    status, body = _request(engine, "POST", "/debug/perf/capture",
                            json={"mode": "bogus"})
    assert status == 400
    assert "error" in body


def test_debug_perf_unsupported_engine_is_501():
    class Bare:
        _dead = False

    status, body = _request(Bare(), "GET", "/debug/perf")
    assert status == 501
    assert "error" in body
    status, body = _request(Bare(), "POST", "/debug/perf/capture")
    assert status == 501


# ---------------------------------------------------------------------------
# End to end on a CPU engine: triggered capture over live traffic, then
# the quiet-window kernel A/B (ISSUE 10 acceptance).
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def llm():
    from transformers import LlamaConfig

    from vllm_tpu.entrypoints.llm import LLM

    cfg = LlamaConfig(
        hidden_size=128, intermediate_size=512, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=4, vocab_size=1024,
        max_position_embeddings=2048, tie_word_embeddings=False,
    )
    cfg.architectures = ["LlamaForCausalLM"]
    llm = LLM(
        model="dummy-llama", hf_config=cfg, load_format="dummy",
        max_model_len=512, max_num_batched_tokens=256, max_num_seqs=4,
        num_gpu_blocks_override=512,
    )
    # The CPU has no published peak, so a CPU engine publishes no
    # utilization estimate (test_unknown_device_kind_has_no_peak). These
    # tests are about the capture -> stats -> /metrics path: give the
    # engine the model's roofline under a kind that has peaks.
    from vllm_tpu.metrics.roofline import RooflineModel

    core = llm.llm_engine.engine_core.engine_core
    info = core.executor.collective_rpc("roofline_info")[0]
    core._perf_roofline = RooflineModel.from_dict(
        {**info, "device_kind": "TPU v5e"})
    return llm


def _core(llm):
    return llm.llm_engine.engine_core.engine_core


def test_e2e_triggered_capture(llm):
    """Arm a capture over the HTTP-thread path, drive live traffic the
    way the engine loop does (poll + step), and assert the landed
    snapshot: phase split (None on CPU — no device ops) + roofline
    estimates from the window's token counters."""
    from vllm_tpu.request import EngineCoreRequest
    from vllm_tpu.sampling_params import SamplingParams

    core = _core(llm)
    ack = core.perf_capture({"mode": "capture", "steps": 2})
    assert ack == {"armed": "capture", "force": False}
    core.add_request(EngineCoreRequest(
        request_id="live-0",
        prompt_token_ids=[(3 * j) % 997 + 1 for j in range(8)],
        sampling_params=SamplingParams(
            temperature=0.0, max_tokens=8, ignore_eos=True),
    ))
    guard = 0
    while core.has_unfinished_requests() and guard < 128:
        core.poll_perfwatch()
        core.step()
        guard += 1
    core.poll_perfwatch()  # close a window left open at end of traffic
    assert guard < 128
    status = core.perf_status()
    assert status["captures_total"] == 1
    assert status["capturing"] is False and status["armed"] is False
    cap = status["last_capture"]
    assert cap["kind"] == "capture" and cap["steps"] >= 2
    # CPU backend: the trace has no device-op line.
    assert cap["device_ms_per_step"] is None
    # Roofline estimates computed from the worker's reported model.
    assert cap["mfu_est"] is not None and cap["mfu_est"] >= 0
    assert cap["hbm_bw_util_est"] is not None
    assert cap["tok_per_s"] > 0


def test_e2e_quiet_window_ab(llm):
    """The in-engine kernel A/B on an idle engine: synthetic replay
    batch, sampler-kernel and decode-attention variants, artifact with
    on/off deltas (wall-clock-sourced on CPU)."""
    core = _core(llm)
    assert not core.has_unfinished_requests()
    result = core.perf_ab({"steps": 2})
    assert result.get("error") is None, result
    assert result["aborted"] is False
    assert result["steps"] == 2
    ab = result["ab"]
    for kernel in ("sampler_kernel", "decode_attention"):
        d = ab[kernel]
        assert set(d) >= {"device_ms_on", "device_ms_off", "delta_pct",
                          "wall_ms_on", "wall_ms_off", "wall_delta_pct",
                          "source"}
        assert d["device_ms_on"] is None  # CPU: no device ops
        assert d["source"] == "wall_clock"
        assert d["wall_ms_on"] > 0 and d["wall_ms_off"] > 0
    # The replay left nothing behind: engine empty, flags restored.
    assert not core.has_unfinished_requests()
    runner = core.executor.worker.runner
    assert runner.enable_sampler_kernel == \
        core.config.scheduler_config.enable_sampler_kernel
    assert runner.enable_decode_attention == \
        core.config.scheduler_config.enable_decode_attention
    status = core.perf_status()
    assert status["ab_runs_total"] == 1
    assert status["last_ab"]["batch"]["num_reqs"] >= 1


def test_e2e_ab_refuses_busy_engine(llm):
    from vllm_tpu.request import EngineCoreRequest
    from vllm_tpu.sampling_params import SamplingParams

    core = _core(llm)
    core.add_request(EngineCoreRequest(
        request_id="busy-0",
        prompt_token_ids=[5, 6, 7, 8],
        sampling_params=SamplingParams(
            temperature=0.0, max_tokens=2, ignore_eos=True),
    ))
    try:
        assert "error" in core.perf_ab({})
    finally:
        guard = 0
        while core.has_unfinished_requests() and guard < 64:
            core.step()
            guard += 1


def test_e2e_stats_fields_reach_scheduler_stats(llm):
    """The engine attaches perfwatch fields to SchedulerStats (the
    /metrics bridge) once a capture has landed."""
    from vllm_tpu.request import EngineCoreRequest
    from vllm_tpu.sampling_params import SamplingParams

    core = _core(llm)
    core.add_request(EngineCoreRequest(
        request_id="stats-0",
        prompt_token_ids=[11, 12, 13, 14],
        sampling_params=SamplingParams(
            temperature=0.0, max_tokens=2, ignore_eos=True),
    ))
    stats = None
    guard = 0
    while core.has_unfinished_requests() and guard < 64:
        out = core.step()
        if out.scheduler_stats is not None:
            stats = out.scheduler_stats
        guard += 1
    assert stats is not None
    assert stats.perfwatch_captures >= 1
    assert stats.perfwatch_mfu_est is not None
    # And the Prometheus registry renders them.
    from vllm_tpu.metrics.prometheus import PrometheusRegistry

    reg = PrometheusRegistry()
    reg.record(stats)
    text = "".join(m.render() for m in reg._metrics)
    assert "vllm:perfwatch_captures_total 1.0" in text
    assert "vllm:mfu_est" in text


def test_debug_perf_endpoint_round_trip_real_engine(llm):
    """GET /debug/perf against the real engine (InprocClient exposes
    perf_status through the same attribute path the server uses)."""

    class Wrap:
        _dead = False

        def __init__(self, client):
            self.engine_core = client

    status, body = _request(
        Wrap(llm.llm_engine.engine_core), "GET", "/debug/perf")
    assert status == 200
    assert body["captures_total"] >= 1
    assert body["last_capture"]["mfu_est"] is not None
