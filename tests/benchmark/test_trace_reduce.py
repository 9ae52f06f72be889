"""The trace reducer: busy union, idle share, kernel sums and gap labels,
on a trace written down by hand and on a small one recorded on the v5e."""

from __future__ import annotations

from pathlib import Path

import pytest

from benchmark.harness import trace_reduce as R

from .xplane_writer import xspace

# One chip, times in ns. A while (100..500) holds two fusions and a custom
# call; a convolution follows after a gap; then a long gap and one more op.
OPS = [
    (100, 400, "while.7"),
    (100, 100, "fusion.12"),
    (200, 150, "attention_kernel.3"),
    (350, 100, "fusion.13"),
    (600, 200, "convolution.1"),
    (1800, 200, "fusion.12"),
]
HOST = [
    (0, 2000, "engine_loop"),      # covers everything: the outer frame
    (500, 100, "prepare_inputs"),  # exactly the gap 500..600
    (800, 900, "schedule"),        # most of the gap 800..1800
    (1700, 50, "too_short"),
]
PLANES = [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "timestamp_ns": 0, "events": OPS},
        {"name": "XLA Modules", "timestamp_ns": 0,
         "events": [(100, 700, "jit_step(123)"), (1800, 200, "jit_step(123)")]},
        {"name": "Steps", "timestamp_ns": 0, "events": [(100, 1900, "0")]},
    ]},
    {"name": "/host:CPU", "lines": [{"name": "python/123", "timestamp_ns": 0, "events": HOST}]},
]


def check_hand_trace(out):
    ns = 1e-9
    assert out["devices"] == 1
    # Busy: 100..500, 600..800, 1800..2000 = 800 of the 1900 from 100 to 2000.
    assert out["busy_s"] == pytest.approx(800 * ns)
    assert out["window_s"] == pytest.approx(1900 * ns)
    k = out["kernel_seconds"]
    # The while's own time is what its children leave: 400 - 350.
    assert k["while"] == pytest.approx(50 * ns)
    assert k["fusion"] == pytest.approx(400 * ns)
    assert k["attention_kernel"] == pytest.approx(150 * ns)
    assert k["convolution"] == pytest.approx(200 * ns)
    assert sum(k.values()) == pytest.approx(out["busy_s"])
    assert out["kernel_calls"]["fusion"] == 3 and out["kernel_calls"]["attention_kernel"] == 1
    assert out["device_ops"][0] == ["fusion", pytest.approx(400 * ns)]
    assert out["module_calls"] == {"jit_step": 2}
    assert out["module_seconds"]["jit_step"] == pytest.approx(900 * ns)
    gaps = out["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([1000 * ns, 100 * ns])
    assert gaps[0][0] == "python: schedule" and gaps[1][0] == "python: prepare_inputs"


def as_reducer_planes(planes):
    return [{"name": p["name"], "lines": [
        {"name": l["name"], "events": [(l["timestamp_ns"] + s, d, n) for s, d, n in l["events"]]}
        for l in p["lines"]]} for p in planes]


def test_reduce_on_planes_written_by_hand():
    check_hand_trace(R.reduce_planes(as_reducer_planes(PLANES)))


def test_reduce_on_an_xplane_file_written_by_hand(tmp_path):
    f = tmp_path / "plugins" / "profile" / "t" / "hand.xplane.pb"
    f.parent.mkdir(parents=True)
    f.write_bytes(xspace(PLANES))
    check_hand_trace(R.reduce_planes(R.read_planes(str(tmp_path), min_host_ns=60.0)))


def test_a_gap_no_host_event_covers_is_labelled_so():
    planes = as_reducer_planes(PLANES)
    planes[1]["lines"][0]["events"] = [(500, 100, "prepare_inputs")]
    out = R.reduce_planes(planes)
    assert out["idle_gaps"][0][0] == "unattributed"


def test_two_chips_are_averaged():
    planes = as_reducer_planes(PLANES)
    other = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [(100, 1900, "fusion.1")]}]}
    out = R.reduce_planes(planes + [other])
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx((800 + 1900) / 2 * 1e-9)
    assert out["window_s"] == pytest.approx(1900e-9)


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError, match="/device:TPU:"):
        R.reduce_planes(as_reducer_planes(PLANES[1:]))


def test_kernel_name_drops_the_instance_number():
    assert R.kernel_name("fusion.123") == "fusion"
    assert R.kernel_name("%custom-call.4.1") == "custom-call"
    assert R.kernel_name("ragged_paged_attention") == "ragged_paged_attention"
    hlo = "%convolution_multiply_fusion.2 = bf16[2048,2048]{1,0:T(8,128)(2,1)} fusion(bf16[8]{0} %copy-done.1), kind=kOutput"
    assert R.kernel_name(hlo) == "convolution_multiply_fusion"


def test_union_seconds():
    busy, gaps = R.union_seconds([(0, 10), (5, 12), (20, 30), (30, 31), (40, 41)])
    assert busy == 12 + 11 + 1 and gaps == [(12, 20), (31, 40)]
    assert R.union_seconds([]) == (0.0, [])


RECORDED = Path(__file__).with_name("tiny_tpu.xplane.pb")


@pytest.mark.skipif(not RECORDED.is_file(), reason="no recorded trace beside the tests")
def test_reduce_on_a_trace_recorded_on_the_v5e():
    """Four rounds of a matmul and an elementwise program with 2 ms pauses,
    recorded by the script in benchmark/README.md."""
    out = R.reduce_planes(R.read_planes(str(RECORDED)))
    assert out["devices"] == 1
    assert 0 < out["busy_s"] < out["window_s"]
    assert sum(out["kernel_seconds"].values()) == pytest.approx(out["busy_s"], rel=1e-6)
    assert sum(out["module_calls"].values()) == 8
    assert out["kernel_calls"]["convolution_multiply_fusion"] == 4
    assert out["kernel_calls"]["tanh_add_fusion"] == 4
    # A 2048^3 bf16 matmul is 17.2 GFLOP: 90.9 us each is 189 TFLOP/s, under the 197 peak.
    assert 4 * 2 * 2048**3 / out["kernel_seconds"]["convolution_multiply_fusion"] < 197e12
    # The pauses are the gaps: at least three of 2 ms or more.
    assert sum(1 for _, s in out["idle_gaps"] if s >= 2e-3) >= 3


def test_the_slices_of_a_run_add_up(tmp_path):
    one = R.reduce_planes(as_reducer_planes(PLANES))
    both = R.combine([one, one])
    assert both["slices"] == 2 and both["devices"] == 1
    assert both["busy_s"] == pytest.approx(2 * one["busy_s"])
    assert both["window_s"] == pytest.approx(2 * one["window_s"])
    assert both["kernel_seconds"]["fusion"] == pytest.approx(2 * one["kernel_seconds"]["fusion"])
    assert both["kernel_calls"]["fusion"] == 6 and both["module_calls"] == {"jit_step": 4}
    assert both["device_ops"][0] == ["fusion", pytest.approx(800e-9)]
    # The longest gaps of all slices, longest first.
    assert [g[1] for g in both["idle_gaps"]] == pytest.approx([1000e-9, 1000e-9, 100e-9, 100e-9])
    # From the command line: <out.json> <trace>...
    for name in ("a", "b"):
        f = tmp_path / name / "plugins" / "profile" / "t" / "hand.xplane.pb"
        f.parent.mkdir(parents=True)
        f.write_bytes(xspace(PLANES))
    assert R.main([str(tmp_path / "out.json"), str(tmp_path / "a"), str(tmp_path / "b")]) == 0
    import json
    out = json.loads((tmp_path / "out.json").read_text())
    assert out["slices"] == 2 and out["kernel_calls"]["fusion"] == 6
