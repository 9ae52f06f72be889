"""The per-layer metrics ``mimov25.long-answers`` adds: the two kernels'
shares of their roofline at the PUBLISHED shapes (keys of 192 lanes beside
values of 128, 8 and 4 KV heads) and the share of the held bytes a token
that the model's config states, each on counters written down by hand and
on a program that lacks them."""

from __future__ import annotations

import pytest

from benchmark.harness import manifest as M

MANIFEST = M.load_manifest()
CELL = M.Cell(MANIFEST, "mimov25.long-answers")
COUNTS, NAMED = M.kernel_counts("attn_qk_v"), M.kernel_counts("window_attn")
NEW = ["attn_kv_window_roofline", "attn_kv_full_roofline", "kv_held_bytes_share"]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def read(name, ctx):
    return CELL.layer_metric(name).read(ctx)


def ctx_of(deltas=None, slice_deltas=None, trace=None, m1=None):
    deltas = deltas or {}
    out = {"cell": CELL, "config": CELL.hf_config, "peaks": PEAKS, "trace": trace,
           "m1": m1 or {}, "delta": lambda *names: next(
               (deltas[n] for n in names if n in deltas), None),
           "kernel_counts": M.kernel_counts}
    if slice_deltas is not None:
        out["slice_delta"] = lambda *names: next(
            (slice_deltas[n] for n in names if n in slice_deltas), None)
    return out


def test_the_counts_are_at_the_published_shapes():
    cfg = CELL.hf_config
    assert (COUNTS.layers(cfg, COUNTS.FULL), COUNTS.layers(cfg, COUNTS.WINDOW)) == (2, 8)
    assert COUNTS.geometry(cfg, COUNTS.FULL) == (64, 4, 192, 128)
    assert COUNTS.geometry(cfg, COUNTS.WINDOW) == (64, 8, 192, 128)
    # 320 lanes a KV head a key in bf16, not the 384 a page holds.
    assert COUNTS.kv_bytes_per_context_token(cfg, COUNTS.FULL) == 2 * 4 * 320 * 2 == 5120
    assert COUNTS.kv_bytes_per_context_token(cfg, COUNTS.WINDOW) == 8 * 8 * 320 * 2 == 40_960
    assert COUNTS.qo_bytes_per_query_token(cfg, COUNTS.FULL) == 2 * 64 * 320 * 2
    assert COUNTS.pair_ops(cfg, COUNTS.WINDOW) == 8 * 2 * 64 * 320
    trace = {"kernel_seconds": {"rpa_kernel": 0.2, "rpa_decode_kernel": 0.3,
                                "rpa_kernel_window": 0.25, "rpa_decode_kernel_window": 0.5}}
    # The kinds' names, the kernels' names and their time are window_attn's.
    assert (COUNTS.FULL, COUNTS.WINDOW) == (NAMED.FULL, NAMED.WINDOW)
    assert NAMED.kernel_seconds(trace, COUNTS.FULL) == 0.5
    assert NAMED.kernel_seconds(trace, COUNTS.WINDOW) == 0.75
    # At the published depth: 9 full layers, 39 window layers.
    whole = {**cfg, "num_hidden_layers": 48}
    assert (COUNTS.layers(whole, COUNTS.FULL), COUNTS.layers(whole, COUNTS.WINDOW)) == (9, 39)


def test_the_roofline_readers_on_counters_written_down_by_hand():
    trace = {"kernel_seconds": {"rpa_kernel": 0.05, "rpa_decode_kernel": 0.15,
                                "rpa_kernel_window": 0.05, "rpa_decode_kernel_window": 0.15,
                                "gmm": 1.0}}
    # 100 decode steps of 80 rows at 1,000 tokens, 25 mixed steps of a
    # 432-token chunk at context 1,500 beside the 80 rows.
    pairs_chunk = 432 * 1068 + 432 * 433 // 2
    in_slices = {
        'vllm:attn_context_tokens_total{kind="decode"}': 100 * 80 * 1000.0,
        'vllm:attn_context_tokens_total{kind="mixed"}': 25 * (80 * 1000.0 + 1500),
        'vllm:attn_context_tokens_total{kind="window"}': 125 * 80 * 128.0 + 25 * 559,
        'vllm:engine_step_tokens_total{kind="decode"}': 100 * 80.0,
        'vllm:engine_step_tokens_total{kind="mixed"}': 25 * 512.0,
        'vllm:attn_ragged_pairs_total{kind="live"}': 25 * (80 * 1000.0 + pairs_chunk),
        "vllm:window_attn_pairs_total": 125 * 80 * 128.0 + 25 * 432 * 128,
    }
    ctx = ctx_of(slice_deltas=in_slices, trace=trace)
    keys = 100 * 80 * 1000.0 + 25 * (80 * 1000.0 + 1500)
    tokens = 100 * 80.0 + 25 * 512.0
    pairs = 100 * 80 * 1000.0 + 25 * (80 * 1000.0 + pairs_chunk)
    full = read("attn_kv_full_roofline", ctx)
    assert full == pytest.approx(100 * max(
        (keys * 5120 + tokens * 81_920) / 819e9, pairs * 81_920 / 197e12) / 0.20)
    window = read("attn_kv_window_roofline", ctx)
    w_keys, w_pairs = 125 * 80 * 128.0 + 25 * 559, 125 * 80 * 128.0 + 25 * 432 * 128
    assert window == pytest.approx(100 * max(
        (w_keys * 40_960 + tokens * 327_680) / 819e9, w_pairs * 327_680 / 197e12) / 0.20)
    assert 0 < full < 100 and 0 < window < 100


def test_the_held_bytes_share_on_gauges_written_down_by_hand():
    """The cut's five groups: the full group 2 x 2,560 published bytes a
    token in 6,144 held, a window group 2 x 5,120 in 12,288: 83.3 whatever
    the groups' pages, since every group pads alike."""
    m1, deltas = {}, {}
    for g, (tokens, published, held, pages) in enumerate(
            [(32, 5120.0, 6144.0, 900.0)] + [(16, 10_240.0, 12_288.0, 1700.0)] * 4):
        m1[f'vllm:kv_group_page_tokens{{group="{g}"}}'] = tokens
        m1[f'vllm:kv_token_bytes{{group="{g}",what="published"}}'] = published
        m1[f'vllm:kv_token_bytes{{group="{g}",what="held"}}'] = held
        deltas[f'vllm:kv_pages_allocated_total{{group="{g}"}}'] = pages
    assert read("kv_held_bytes_share", ctx_of(deltas, m1=m1)) == pytest.approx(100 * 320 / 384)
    # A short group's places count as held: the published 9 + 39 layers
    # have a last window group of 3 layers in 9 places; here one of 1 in 2.
    m1['vllm:kv_token_bytes{group="4",what="published"}'] = 5120.0
    got = read("kv_held_bytes_share", ctx_of(deltas, m1=m1))
    want = 100 * (900 * 32 * 5120 + 1700 * 16 * (3 * 10_240 + 5120)) / (
        900 * 32 * 6144 + 4 * 1700 * 16 * 12_288)
    assert got == pytest.approx(want) and got < 75


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_on_a_program_without_its_counters(name):
    """The parent's program has none of them (and cannot run the cell): a
    trace without the kernels, kernels without the counters, no gauge a
    group, no trace at all: the reader returns None and does not raise,
    and the line leaves the metric out. (Which cells a reader is asked in
    is the entry's ``workloads`` list, not the reader's to guess.)"""
    kernels = {"kernel_seconds": {"fusion": 1.0, "rpa_kernel": 0.5, "rpa_decode_kernel": 0.5,
                                  "rpa_kernel_window": 0.5}}
    counters = {'vllm:attn_context_tokens_total{kind="decode"}': 1e6,
                'vllm:attn_context_tokens_total{kind="window"}': 1e5,
                'vllm:window_attn_pairs_total': 1e5,
                'vllm:engine_step_tokens_total{kind="decode"}': 1e3,
                'vllm:attn_ragged_pairs_total{kind="live"}': 1e6,
                'vllm:kv_pages_allocated_total{group="0"}': 10.0}
    assert read(name, ctx_of(counters, counters, {"kernel_seconds": {"fusion": 1.0}})) is None
    assert read(name, ctx_of({}, {}, kernels)) is None
    assert read(name, ctx_of({}, None, None)) is None


def test_the_entries_are_appended_with_the_one_cell():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == ["mimov25.long-answers"] and m["unit"] == "%"
        assert m["better"] == "higher"
    assert by_name["attn_kv_window_roofline"]["layer"] == "kernels"
    assert by_name["attn_kv_full_roofline"]["source"] == "device_trace"
    assert by_name["kv_held_bytes_share"]["layer"] == "KV cache manager"
    assert by_name["kv_held_bytes_share"]["moves"] == "output_tokens_per_s"
    # Both shares are the decode kernel's (0.446 of 0.461 s of the window kind
    # in the ledger's PR 45 line), which runs in no mixed step: since PR 46 they
    # bound a claim on the MEDIAN gap, which is a decode-only step.
    assert {by_name[n]["moves"] for n in NEW[:2]} == {"itl_p50_ms"}
    # Appended together and in this order; what follows them is a later PR's.
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 3] == NEW
