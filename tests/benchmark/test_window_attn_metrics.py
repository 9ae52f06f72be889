"""The four per-layer metrics ``lagunaxs2.code-mixed`` brings: the counting
functions of ``benchmark/kernels/window_attn.py`` and each reader on
counters written down by hand, and on a program that has none of them."""

from __future__ import annotations

import pytest

from benchmark.harness import manifest as M

MANIFEST = M.load_manifest()
NAME = "lagunaxs2.code-mixed"
CELL = M.Cell(MANIFEST, NAME)
V5E = M.peaks_for("TPU v5 lite")
COUNTS = M.kernel_counts("window_attn")
NEW = ["window_pages_freed_share", "kv_pages_in_use_share", "attn_window_roofline",
       "attn_full_roofline"]


def ctx_of(deltas=None, slice_deltas=None, trace=None, steps=1000.0):
    deltas = deltas or {}
    ctx = {"delta": lambda *names: next((deltas[n] for n in names if n in deltas), None),
           "steps": lambda: steps, "config": CELL.hf_config, "peaks": V5E, "trace": trace,
           "kernel_counts": M.kernel_counts}
    if slice_deltas is not None:
        ctx["slice_delta"] = lambda *names: next(
            (slice_deltas[n] for n in names if n in slice_deltas), None)
    return ctx


def read(name, ctx):
    return CELL.layer_metric(name).read(ctx)


def test_the_counting_functions():
    cfg = CELL.hf_config
    assert (COUNTS.layers(cfg, COUNTS.FULL), COUNTS.layers(cfg, COUNTS.WINDOW)) == (10, 30)
    assert (COUNTS.heads(cfg, COUNTS.FULL), COUNTS.heads(cfg, COUNTS.WINDOW)) == (48, 64)
    assert COUNTS.kv_bytes_per_context_token(cfg, COUNTS.FULL) == 10 * 4096
    assert COUNTS.kv_bytes_per_context_token(cfg, COUNTS.WINDOW) == 30 * 4096
    assert COUNTS.qo_bytes_per_query_token(cfg, COUNTS.WINDOW) == 2 * 64 * 128 * 2 * 30
    assert COUNTS.pair_ops(cfg, COUNTS.FULL) == 4 * 48 * 128 * 10
    # 12 decode rows at 3,000 tokens of context: bytes bind. The full
    # layers read 36,000 keys' worth, the window layers 12 x 512.
    full = COUNTS.least_seconds(cfg, COUNTS.FULL, 12 * 3000, 12, 12 * 3000, V5E)
    assert full == pytest.approx((12 * 3000 * 40960 + 12 * 245760) / 819e9)
    window = COUNTS.least_seconds(cfg, COUNTS.WINDOW, 12 * 512, 12, 12 * 512, V5E)
    assert window == pytest.approx((12 * 512 * 122880 + 12 * 983040) / 819e9)
    assert 1.8e-3 < full < 1.9e-3 and 0.9e-3 < window < 1.0e-3
    # A chunk of 512 tokens at context 4,096: operations bind in both kinds.
    pairs_full = 512 * 3584 + 512 * 513 // 2
    chunk = COUNTS.least_seconds(cfg, COUNTS.FULL, 4096, 512, pairs_full, V5E)
    assert chunk == pytest.approx(pairs_full * 245760 / 197e12) and chunk > 2.4e-3
    pairs_window = 512 * 512
    chunk_w = COUNTS.least_seconds(cfg, COUNTS.WINDOW, 1023, 512, pairs_window, V5E)
    assert chunk_w == pytest.approx(pairs_window * 983040 / 197e12)
    trace = {"kernel_seconds": {"rpa_kernel": 0.3, "rpa_decode_kernel": 0.2,
                                "rpa_kernel_window": 0.5, "rpa_decode_kernel_window": 0.25,
                                "fusion": 9.0}}
    assert COUNTS.kernel_seconds(trace, COUNTS.FULL) == 0.5
    assert COUNTS.kernel_seconds(trace, COUNTS.WINDOW) == 0.75


def test_the_runners_count_of_a_windows_pairs_and_keys():
    """What the two window counters count, on rows written down by hand:
    a decode row past the window, one under it, and a chunk across it."""
    from vllm_tpu.worker.model_runner import window_live_pairs

    assert window_live_pairs([1], [3000], 512) == 512
    assert window_live_pairs([1], [100], 512) == 100
    # 512 queries at positions 256..767: 256 of them see p + 1 keys (257 ..
    # 512), the other 256 see 512.
    assert window_live_pairs([512], [768], 512) == sum(range(257, 513)) + 256 * 512
    assert window_live_pairs([1, 1, 512], [3000, 100, 768], 512) == (
        512 + 100 + sum(range(257, 513)) + 256 * 512)


def test_the_pool_readers_on_counters_written_down_by_hand():
    deltas = {'vllm:kv_pages_allocated_total{group="%d"}' % g: 30_000.0 for g in range(4)}
    deltas["vllm:kv_window_pages_freed_total"] = 3 * 24_000.0
    assert read("window_pages_freed_share", ctx_of(deltas)) == pytest.approx(60.0)
    steps = {'vllm:kv_page_steps_total{kind="held"}': 1000 * 3100.0,
             'vllm:kv_page_steps_total{kind="pool"}': 1000 * 4100.0}
    assert read("kv_pages_in_use_share", ctx_of(steps)) == pytest.approx(100 * 31 / 41)


def test_the_roofline_readers_on_counters_written_down_by_hand():
    trace = {"kernel_seconds": {"rpa_kernel": 0.10, "rpa_decode_kernel": 0.20,
                                "rpa_kernel_window": 0.12, "rpa_decode_kernel_window": 0.18,
                                "gmm": 1.0}}
    # 100 decode steps of 12 rows at 3,000 tokens, 25 mixed steps of a
    # 500-token chunk at context 4,000 beside the 12 rows.
    pairs_chunk = 500 * 3500 + 500 * 501 // 2
    in_slices = {
        'vllm:attn_context_tokens_total{kind="decode"}': 100 * 12 * 3000.0,
        'vllm:attn_context_tokens_total{kind="mixed"}': 25 * (12 * 3000.0 + 4000),
        'vllm:attn_context_tokens_total{kind="window"}': 125 * 12 * 512.0 + 25 * 1011,
        'vllm:engine_step_tokens_total{kind="decode"}': 100 * 12.0,
        'vllm:engine_step_tokens_total{kind="mixed"}': 25 * 512.0,
        'vllm:attn_ragged_pairs_total{kind="live"}': 25 * (12 * 3000.0 + pairs_chunk),
        "vllm:window_attn_pairs_total": 125 * 12 * 512.0 + 25 * 500 * 512,
        "vllm:window_attn_rows_total": (125 * 12 + 25) * 30.0,
    }
    ctx = ctx_of(slice_deltas=in_slices, trace=trace)
    keys = 100 * 12 * 3000.0 + 25 * (12 * 3000.0 + 4000)
    tokens = 100 * 12.0 + 25 * 512.0
    pairs = 100 * 12 * 3000.0 + 25 * (12 * 3000.0 + pairs_chunk)
    full = read("attn_full_roofline", ctx)
    assert full == pytest.approx(100 * max(
        (keys * 40960 + tokens * 245760) / 819e9, pairs * 245760 / 197e12) / 0.30)
    window = read("attn_window_roofline", ctx)
    w_keys, w_pairs = 125 * 12 * 512.0 + 25 * 1011, 125 * 12 * 512.0 + 25 * 500 * 512
    assert window == pytest.approx(100 * max(
        (w_keys * 122880 + tokens * 983040) / 819e9, w_pairs * 983040 / 197e12) / 0.30)
    assert 0 < full < 100 and 0 < window < 100


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_on_a_program_without_its_counters(name):
    """The parent's program has none of them (and cannot run the cell), the
    other cells' programs run the kernels under their plain names only: the
    reader returns None and does not raise, and the line leaves the metric
    out."""
    trace = {"kernel_seconds": {"fusion": 1.0, "rpa_kernel": 0.5, "rpa_decode_kernel": 0.5}}
    others = {'vllm:attn_context_tokens_total{kind="decode"}': 1e6,
              'vllm:engine_step_tokens_total{kind="decode"}': 1e3,
              'vllm:attn_ragged_pairs_total{kind="live"}': 1e6}
    assert read(name, ctx_of(others, others, trace)) is None
    assert read(name, ctx_of({}, {}, trace)) is None
    assert read(name, ctx_of({}, None, None)) is None
