"""The per-layer readers that read the program's own phase counters and
spans, each on a ``ctx`` written down by hand: the value it computes, and
None where its counter is missing (a program without it)."""

from __future__ import annotations

import pytest

from benchmark.harness.manifest import Cell, kernel_counts, load_manifest, peaks_for

CELL = Cell(load_manifest(), "mistral7b.chat")
SUM = "vllm:engine_step_duration_seconds_sum"
COUNT = 'vllm:engine_step_duration_seconds_count{phase="dispatch"}'
CONTEXT = 'vllm:attn_context_tokens_total{kind="decode"}'
# 65,536 bytes a context token (8 KV heads x 128 x 2 x bf16 x 16 layers).
BYTES_A_TOKEN = 65536


def make_ctx(m0: dict, m1: dict, trace=None, slice_deltas=None, scrape_dt=50.0) -> dict:
    def delta(*names):
        for n in names:
            if n in m0 and n in m1:
                return m1[n] - m0[n]
        return None

    ctx = {
        "m0": m0, "m1": m1, "delta": delta, "steps": lambda: delta(COUNT),
        "scrape_dt": scrape_dt, "trace": trace, "config": CELL.hf_config,
        "peaks": peaks_for("TPU v5 lite"), "kernel_counts": kernel_counts,
    }
    if slice_deltas is not None:
        ctx["slice_delta"] = lambda *names: next(
            (slice_deltas[n] for n in names if n in slice_deltas), None)
    return ctx


def phases(**seconds) -> dict:
    return {f'{SUM}{{phase="{p}"}}': v for p, v in seconds.items()}


M0 = {COUNT: 1000.0, **phases(schedule=1.0, prepare=2.0, fetch=30.0, update=0.5, output=0.25),
      "vllm:step_first_use_seconds_total": 111.5,
      'vllm:engine_steps_total{kind="mixed"}': 300.0, 'vllm:engine_steps_total{kind="decode"}': 700.0}
M1 = {COUNT: 3500.0, **phases(schedule=1.5, prepare=4.5, fetch=70.0, update=1.0, output=1.0),
      "vllm:step_first_use_seconds_total": 111.5,
      'vllm:engine_steps_total{kind="mixed"}': 800.0, 'vllm:engine_steps_total{kind="decode"}': 2700.0}
# 4 s of the decode kernel for 3e7 context tokens: 2.4 s at 819 GB/s.
TRACE = {"kernel_seconds": {"rpa_decode_kernel": 4.0, "fusion": 9.0},
         "kernel_calls": {"rpa_decode_kernel": 1600, "rpa_kernel": 400},
         "idle_gaps": [["engine-core: vllm_tpu.engine.update", 0.003],
                       ["engine-core: vllm_tpu.runner.prepare", 0.002],
                       ["engine-core: PjitFunction(vllm_tpu_step)", 0.001],
                       ["unattributed", 0.002]]}
LEAST = 3e7 * BYTES_A_TOKEN / peaks_for("TPU v5 lite")["hbm_bytes_per_s"]


@pytest.mark.parametrize("name,want,without", [
    # 0.5 s of scheduling over 2500 steps.
    ("schedule_ms_per_step", 0.2, 'phase="schedule"'),
    ("prepare_ms_per_step", 1.0, 'phase="prepare"'),
    # update (0.5 s) and output (0.75 s) over 2500 steps.
    ("output_ms_per_step", 0.5, 'phase="output"'),
    # 40 s of the 50 s between the scrapes spent waiting for the device.
    ("device_wait_share", 80.0, 'phase="fetch"'),
    ("setup_first_use_s", 111.5, "first_use"),
    # 500 of the window's 2500 steps carried a prompt's chunk.
    ("mixed_step_share", 20.0, 'kind="mixed"'),
    ("mixed_step_share", 20.0, 'kind="decode"'),
    ("decode_attn_roofline_counted", 100.0 * LEAST / 4.0, "attn_context"),
    # 3 ms of 8 ms carry no span of the program's.
    ("idle_unnamed_share", 37.5, "idle_gaps"),
])
def test_a_reader_of_the_programs_counters(name, want, without):
    read = CELL.layer_metric(name).read
    assert any(m["name"] == name for m in CELL.per_layer), "listed in BENCHMARK.json"
    ctx = make_ctx(M0, M1, TRACE, {CONTEXT: 3e7})
    assert read(ctx) == pytest.approx(want)
    # The same run of a program that lacks the counter (or whose trace
    # lists no gap): nothing to read, and nothing raised.
    m0 = {k: v for k, v in M0.items() if without not in k}
    m1 = {k: v for k, v in M1.items() if without not in k}
    trace = {k: v for k, v in TRACE.items() if k != without}
    deltas = {} if without == "attn_context" else {CONTEXT: 3e7}
    assert read(make_ctx(m0, m1, trace, deltas)) is None


def test_the_counted_roofline_needs_the_slices_scrapes_and_the_kernel():
    read = CELL.layer_metric("decode_attn_roofline_counted").read
    # --trace 1: no scrapes inside the slices, so no slice_delta at all.
    assert read(make_ctx(M0, M1, TRACE, None)) is None
    assert read(make_ctx(M0, M1, None, {CONTEXT: 3e7})) is None
    no_kernel = {**TRACE, "kernel_seconds": {"fusion": 9.0}}
    assert read(make_ctx(M0, M1, no_kernel, {CONTEXT: 3e7})) is None
    # Fewer tokens counted than traced (the slices' edges): under-read.
    assert read(make_ctx(M0, M1, TRACE, {CONTEXT: 2e7})) < read(
        make_ctx(M0, M1, TRACE, {CONTEXT: 3e7})) < 100.0
    assert LEAST / 3e7 * 819e9 == BYTES_A_TOKEN


def test_no_steps_in_the_window_reads_nothing():
    still = dict(M0)
    for name in ("schedule_ms_per_step", "prepare_ms_per_step", "output_ms_per_step"):
        assert CELL.layer_metric(name).read(make_ctx(M0, still, TRACE, {})) is None
    assert CELL.layer_metric("device_wait_share").read(make_ctx(M0, still)) == 0.0
    # Counters that stand still: nothing to read.
    assert CELL.layer_metric("mixed_step_share").read(make_ctx(M0, still)) is None


@pytest.mark.parametrize("cell", ["mistral7b.chat", "kimilinear48b.long-answers",
                                  "lagunaxs2.code-mixed", "mimov25.long-answers"])
def test_a_cell_of_single_token_rows_says_which_step_each_percentile_is(cell):
    """``mixed_step_share`` lists the four cells in which it read a number on
    the chip (PR 46). Not ``sdar30b.chat``: its reader found nothing to read
    in both of that PR's runs of the cell (rows of several tokens; which of
    the two samples is missing there was not looked up), and a metric without
    a list has to print in every cell."""
    manifest = load_manifest()
    c = Cell(manifest, cell)
    entry = next(m for m in c.per_layer if m["name"] == "mixed_step_share")
    assert cell in entry["workloads"] and "sdar30b.chat" not in entry["workloads"]
    assert all(m["name"] != "mixed_step_share" for m in Cell(manifest, "sdar30b.chat").per_layer)
    assert entry["moves"] == "itl_p95_ms"
    assert entry["layer"] == "scheduler" and entry["source"] == "program_counter"
    only_mixed = {'vllm:engine_steps_total{kind="mixed"}': 900.0,
                  'vllm:engine_steps_total{kind="decode"}': 700.0}
    assert c.layer_metric("mixed_step_share").read(make_ctx(M0, {**M1, **only_mixed})) == 100.0
