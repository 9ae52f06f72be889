"""The reader of ``kda_chunk_roofline``: the chunked form's least time for
the tokens ``vllm:linear_attn_tokens_total{kind="chunk"}`` counted in the
traced slices over the device seconds of ``kda_chunk_kernel``, in per cent;
None from a trace without the kernel (the parent of PR 35) or a program
without the counter."""

from __future__ import annotations

import pytest

from benchmark.harness import manifest as M

NAME = "kda_chunk_roofline"
CELL_NAME = "kimilinear48b.long-answers"
MANIFEST = M.load_manifest()
CELL = M.Cell(MANIFEST, CELL_NAME)
V5E = M.peaks_for("TPU v5 lite")
CHUNK = 'vllm:linear_attn_tokens_total{kind="chunk"}'
DECODE = 'vllm:linear_attn_tokens_total{kind="decode"}'


def ctx_of(slice_deltas, trace):
    ctx = {"config": CELL.hf_config, "peaks": V5E, "trace": trace,
           "kernel_counts": M.kernel_counts}
    if slice_deltas is not None:
        ctx["slice_delta"] = lambda *names: next(
            (slice_deltas[n] for n in names if n in slice_deltas), None)
    return ctx


def test_the_entry_lists_the_one_cell_under_the_kernels():
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "device_trace",
                     "layer": "kernels", "moves": "itl_p95_ms", "workloads": [CELL_NAME]}
    assert NAME in {m["name"] for m in CELL.per_layer}
    for other in ("mistral7b.chat", "sdar30b.chat"):
        assert NAME not in {m["name"] for m in M.Cell(MANIFEST, other).per_layer}


# 25 mixed steps in the slices, each a 382-token chunk through 10 KDA layers.
TOKENS = 25 * 382 * 10.0
# A token's operations: 32 heads x (2 x 4 x 64 x 128 + 8 x 128 x 128); its
# bytes: 32 x 641 x 4 of operands and a 64th of 4 MiB of state.
OPS, BYTES = 32 * (8 * 64 * 128 + 8 * 128 * 128), 32 * 641 * 4 + 2 ** 22 / 64


@pytest.mark.parametrize("trace,deltas,want", [
    # 0.6 ms a call of the kernel, 250 calls; the one-token kernel's time
    # and rows beside it are not this metric's.
    ({"kernel_seconds": {"kda_chunk_kernel": 0.15, "kda_decode_kernel": 0.2, "fusion": 0.5}},
     {CHUNK: TOKENS, DECODE: 25 * 130 * 10.0},
     100 * max(TOKENS * BYTES / 819e9, TOKENS * OPS / 197e12) / 0.15),
    # Two instances of the kernel in one trace are one kernel.
    ({"kernel_seconds": {"kda_chunk_kernel": 0.1, "kda_chunk_kernel.1": 0.05}},
     {CHUNK: TOKENS}, 100 * TOKENS * BYTES / 819e9 / 0.15),
    # The parent: the chunks are XLA fusions, no such kernel in the trace.
    ({"kernel_seconds": {"kda_decode_kernel": 0.2, "fusion": 0.9}}, {CHUNK: TOKENS}, None),
    # A program without the counter, and a run without slices' scrapes.
    ({"kernel_seconds": {"kda_chunk_kernel": 0.15}}, {DECODE: 5.0}, None),
    ({"kernel_seconds": {"kda_chunk_kernel": 0.15}}, None, None),
    (None, {CHUNK: TOKENS}, None),
], ids=["recorded", "two_instances", "no_kernel", "no_counter", "no_slices", "no_trace"])
def test_the_share_of_the_chunk_tokens_least_time(trace, deltas, want):
    got = CELL.layer_metric(NAME).read(ctx_of(deltas, trace))
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want) and 0 < got < 100


def test_the_bound_is_the_bytes_at_these_widths():
    """A chunk token moves 147,584 bytes (0.18 us at 819 GB/s) and needs
    6.3 MFLOP (0.032 us at the bf16 peak): the bytes set the least time,
    so a kernel at three bfloat16 passes of small matrices reads low, and
    never over 100."""
    counts = M.kernel_counts("kda")
    assert counts.state_bytes(CELL.hf_config) / 64 + counts.token_bytes(CELL.hf_config) == BYTES
    assert counts.chunk_token_ops(CELL.hf_config) == OPS
    assert counts.least_seconds(CELL.hf_config, 0.0, 1.0, V5E) == pytest.approx(BYTES / 819e9)
