"""The reader of ``kda_conv_roofline``: the least time of the short
convolution for the tokens and live rows that
``vllm:linear_attn_tokens_total`` (both kinds) and
``vllm:linear_attn_state_rows_total`` counted in the traced slices over the
device seconds of ``kda_conv_kernel``, in per cent; None from a trace
without the kernel (the parent of PR 38) or a program without the counters.

The entry in ``BENCHMARK.json`` came with PR 42 (appended after
``kda_chunk_roofline``); its form is held here."""

from __future__ import annotations

import pytest

from benchmark.harness import manifest as M

NAME = "kda_conv_roofline"
CELL_NAME = "kimilinear48b.long-answers"
MANIFEST = M.load_manifest()
CELL = M.Cell(MANIFEST, CELL_NAME)
V5E = M.peaks_for("TPU v5 lite")
CHUNK = 'vllm:linear_attn_tokens_total{kind="chunk"}'
DECODE = 'vllm:linear_attn_tokens_total{kind="decode"}'
ROWS = "vllm:linear_attn_state_rows_total"


def ctx_of(slice_deltas, trace):
    ctx = {"config": CELL.hf_config, "peaks": V5E, "trace": trace,
           "kernel_counts": M.kernel_counts}
    if slice_deltas is not None:
        ctx["slice_delta"] = lambda *names: next(
            (slice_deltas[n] for n in names if n in slice_deltas), None)
    return ctx


def test_an_entry_is_the_one_cells_under_the_kernels():
    entries = [m for m in MANIFEST["per_layer"] if m["name"] == NAME]
    assert entries == [{
        "name": NAME, "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernels", "moves": "itl_p95_ms", "workloads": [CELL_NAME]}]
    assert NAME in {m["name"] for m in CELL.per_layer}
    for other in ("mistral7b.chat", "sdar30b.chat"):
        assert NAME not in {m["name"] for m in M.Cell(MANIFEST, other).per_layer}


# 50 steps in the slices of 73 live rows through 10 KDA layers, 23 of them
# with a 440-token chunk beside 72 rows of one token.
ROW_LAYERS = 50 * 73 * 10.0
CHUNK_TOKENS, DECODE_TOKENS = 23 * 440 * 10.0, (50 * 73 - 23) * 10.0
# A token: 12,288 channels, bf16 in and float32 out; a row's tail: 3 tokens
# of channels in bf16, read and written.
TOKEN, TAIL = 12288 * 6, 2 * 3 * 12288 * 2
LEAST = ((CHUNK_TOKENS + DECODE_TOKENS) * TOKEN + ROW_LAYERS * TAIL) / 819e9
ALL = {CHUNK: CHUNK_TOKENS, DECODE: DECODE_TOKENS, ROWS: ROW_LAYERS}


@pytest.mark.parametrize("trace,deltas,want", [
    # 0.08 ms a call of the kernel, 500 calls; the state kernels beside it
    # are not this metric's.
    ({"kernel_seconds": {"kda_conv_kernel": 0.04, "kda_decode_kernel": 0.2, "fusion": 0.5}},
     ALL, 100 * LEAST / 0.04),
    # Two instances of the kernel in one trace (the two step programs) are one kernel.
    ({"kernel_seconds": {"kda_conv_kernel": 0.03, "kda_conv_kernel.1": 0.02}}, ALL,
     100 * LEAST / 0.05),
    # The parent: the convolution is XLA fusions, no such kernel in the trace.
    ({"kernel_seconds": {"kda_decode_kernel": 0.2, "fusion": 0.9}}, ALL, None),
    # A program without one of the counters, and a run without slices' scrapes.
    ({"kernel_seconds": {"kda_conv_kernel": 0.04}}, {CHUNK: 1.0, DECODE: 5.0}, None),
    ({"kernel_seconds": {"kda_conv_kernel": 0.04}}, {ROWS: 5.0}, None),
    ({"kernel_seconds": {"kda_conv_kernel": 0.04}}, None, None),
    (None, ALL, None),
], ids=["recorded", "two_instances", "no_kernel", "no_rows", "no_tokens", "no_slices", "no_trace"])
def test_the_share_of_the_least_bytes_time(trace, deltas, want):
    got = CELL.layer_metric(NAME).read(ctx_of(deltas, trace))
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want) and 0 < got < 100


def test_the_bytes_are_a_tokens_channels_and_a_live_rows_tail():
    """73,728 bytes a token (24,576 in, 49,152 out) and 147,456 a live row
    (73,728 of tail each way): at the cell's mixed step, 512 tokens over 73
    rows, 48.5 MB a layer, 59 us at 819 GB/s."""
    counts = M.kernel_counts("kda_conv")
    assert counts.token_bytes(CELL.hf_config) == TOKEN == 73728
    assert counts.tail_bytes(CELL.hf_config) == TAIL == 147456
    assert counts.least_seconds(CELL.hf_config, 512, 73, V5E) == pytest.approx(48.51e6 / 819e9, rel=1e-3)
    assert counts.kernel_seconds({"kernel_seconds": {"kda_conv_kernel.2": 0.5, "gmm": 1.0}}) == 0.5
