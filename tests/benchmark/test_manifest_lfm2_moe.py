"""What ``lfm2-8b-a1b.chat`` adds to ``BENCHMARK.json``: the configuration
against the catalog's, the arithmetic of its cut, the ladder of step programs
the cell really meets, the counting functions and the four readers (on
counters written down by hand and on a trace written by
``xplane_writer.py``)."""

from __future__ import annotations

import hashlib
import json

import pytest

from benchmark.harness import manifest as M
from benchmark.harness import trace_reduce as R

from .xplane_writer import xspace

MANIFEST = M.load_manifest()
NAME = "lfm2-8b-a1b.chat"
CELL = M.Cell(MANIFEST, NAME)
V5E = M.peaks_for("TPU v5 lite")
ROWS = CELL.engine_args["max_num_seqs"]
PATTERN = (["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 4
           + ["full_attention", "conv", "conv"] * 2)

# The catalog's ``config`` for LFM2-8B-A1B, key for key.
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
    "layer_types": PATTERN, "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536,
}


def test_the_catalogs_row_is_what_is_written_down_here():
    import pathlib

    rows = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not rows.exists():
        pytest.skip("the catalog is not on this machine")
    row = next(json.loads(line) for line in rows.read_text().splitlines()
               if '"name": "LFM2-8B-A1B"' in line)
    assert row["config"] == PUBLISHED and row["source_url"] == CELL.config["bench"]["source"]


def test_the_configuration_holds_every_published_width_and_cuts_the_depth_alone():
    cfg, bench = CELL.hf_config, CELL.config["bench"]
    assert {k: cfg[k] for k in PUBLISHED if k != "num_hidden_layers"} == {
        k: v for k, v in PUBLISHED.items() if k != "num_hidden_layers"}
    assert list(bench["reduced"]) == ["num_hidden_layers"]
    assert bench["reduced"]["num_hidden_layers"]["published"] == 24
    assert bench["reduced"]["num_hidden_layers"]["run"] == cfg["num_hidden_layers"] == 16
    assert set(cfg) - set(PUBLISHED) == {"architectures", "tie_embedding", "torch_dtype"}
    assert (cfg["architectures"], cfg["tie_embedding"], cfg["torch_dtype"]) == (
        ["Lfm2MoeForCausalLM"], True, "bfloat16")
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "lfm2-8b-a1b")
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == bench["source"]
    assert CELL.family == "lfm2_moe" and CELL.engine_args["max_model_len"] == 12288
    # The run's sixteen layers: four whole periods behind the two dense layers.
    run = cfg["layer_types"][:16]
    assert [i for i, k in enumerate(run) if k == "full_attention"] == [2, 6, 10, 14]
    assert "two v5e chips" in bench["stands_for"] and "1.5 times" in bench["stands_for"]
    assert len(bench["assumed"]) >= 7
    for said in ("tie_embedding", "1e-6", "q_layernorm, which is 2", "-0.6 + 0.1",
                 "B, C, x in that order"):
        assert any(said in a for a in bench["assumed"]), said


def test_the_arithmetic_of_the_cut():
    c = CELL.hf_config
    d, v, f, fm, e = (c["hidden_size"], c["vocab_size"], c["intermediate_size"],
                      c["moe_intermediate_size"], c["num_experts"])
    a, kh, dh = c["num_attention_heads"], c["num_key_value_heads"], 64
    expert = 3 * d * fm
    sparse = e * expert + d * e + e
    dense = 3 * d * f
    conv = d * 3 * d + d * d + 3 * d
    attn = 2 * d * a * dh + 2 * d * kh * dh + 2 * dh
    assert expert == 11_010_048 and sparse == pytest.approx(352.39e6, rel=1e-4)
    assert dense == pytest.approx(44.04e6, rel=1e-3) and conv == pytest.approx(16.78e6, rel=1e-3)
    assert attn == pytest.approx(10.49e6, rel=1e-3) and v * d == pytest.approx(134.2e6, rel=1e-3)
    whole = 22 * sparse + 2 * dense + 18 * conv + 6 * attn + v * d
    assert whole == pytest.approx(8.34e9, rel=2e-3) and 2 * whole > 16e9  # does not fit
    run = 14 * sparse + 2 * dense + 12 * conv + 4 * attn + v * d + 33 * d
    assert 2 * run == pytest.approx(10.80e9, rel=1e-3)  # bytes of bf16: 67% of 16 GB
    # A request: 12 layers x 2 tokens x 2048 channels in bf16; a token's pages in 4 layers.
    assert 12 * 2 * d * 2 == 98_304 and 4 * 2 * kh * dh * 2 == 8_192
    assert 0.25 < (2 * run + (ROWS + 1) * 98_304) / 16e9 < 0.9
    # A decode-only step reads all of it but the embedding's gather: the floor.
    assert 2 * run / V5E["hbm_bytes_per_s"] == pytest.approx(13.2e-3, rel=5e-3)


def test_the_program_sees_the_same_sizes():
    import jax.numpy as jnp
    from transformers import PretrainedConfig

    from vllm_tpu.models.registry import get_model_class

    cfg = PretrainedConfig.from_dict(CELL.hf_config)
    model = get_model_class(cfg)(cfg, dtype=jnp.bfloat16)
    assert model.state_bytes_per_slot() == 98_304
    assert model.fixed_state_bytes(ROWS) == (ROWS + 1) * 98_304
    assert (model.num_linear_attn_layers, model.num_attn_layers, model.num_moe_layers) == (
        12, 4, 14)
    assert model.is_hybrid_ssm and model.plain_kv_pages and model.first_expert is None
    assert (model.num_heads, model.num_kv_heads, model.head_dim) == (32, 8, 64)
    spec = model.get_kv_cache_spec(16, 2)
    assert sorted(spec) == sorted(f"layers.{i}" for i in (2, 6, 10, 14))
    assert spec["layers.2"].page_size_bytes == 16 * 2 * 8 * 64 * 2  # 16 tokens of 8 KV heads
    assert len({type(s) for s in spec.values()}) == 1  # one KV group
    assert (model.head_layers, model.period, model.reps, model.tail_layers) == (
        [0, 1], 4, 3, [14, 15])


def test_the_traffic_is_the_accepted_file_byte_for_byte_and_the_rate_four_fifths_of_the_knee():
    t = CELL.traffic
    assert t == M.Cell(MANIFEST, "mistral7b.chat").traffic
    raw = (M.BENCH_DIR / "traffic" / "chat.json").read_bytes()
    assert hashlib.sha256(raw).hexdigest() == (
        "20da8e97473d09df5ada0b827056dd9a220bad49fa839102e810fc81e584e6e6")
    assert t["generator"] == "open_poisson" and t["check"]["output_len"] == 48
    assert CELL.numbers["rate_per_s"] == pytest.approx(0.8 * CELL.numbers["knee_per_s"], rel=0.03)
    assert len(CELL.numbers["knee_why"]) > 300 and "sweep" in CELL.numbers["knee_why"]


def test_the_warm_up_walks_the_ladder_this_cell_really_meets():
    """The cell pins the step to one mixed and one decode-only shape through
    the server's own flags: token bucket 512, one request bucket (all the
    rows), the whole page table of 768 pages. Worked out with the program's
    own bucket rule."""
    import argparse

    from benchmark.harness.server import engine_cli_args
    from vllm_tpu.engine.arg_utils import EngineArgs

    parser = EngineArgs.add_cli_args(argparse.ArgumentParser())
    cli = parser.parse_args(["--model", "none", *engine_cli_args(CELL.engine_args)])
    config = EngineArgs.from_cli_args(cli).create_engine_config()
    comp = config.compilation_config
    assert comp.token_buckets == [512] and comp.request_buckets == [ROWS]
    assert config.scheduler_config.max_num_seqs == ROWS
    assert config.scheduler_config.max_num_batched_tokens == 512
    pages = comp.page_buckets(config.scheduler_config.max_model_len // 16)
    assert pages == [768] and pages[-1] * 16 == config.scheduler_config.max_model_len

    def programs_of(wave):
        out = set()
        for _, prompt, answer, *_ in wave:
            out |= {(512, ROWS, 768)} | ({(ROWS, ROWS, 768)} if answer > 1 else set())
        return out

    waves = CELL.numbers["warmup_waves"]
    want = {(512, ROWS, 768), (ROWS, ROWS, 768)}
    assert programs_of(waves[0]) == want == set().union(*map(programs_of, waves))
    assert "2 programs" in CELL.numbers["warmup_waves_why"]
    t = CELL.traffic
    assert t["prompt_len"]["max"] + t["output_len"]["max"] <= 768 * 16


def test_the_counting_functions():
    cfg = CELL.hf_config
    conv, attn, gmm = (M.kernel_counts(k) for k in ("short_conv", "attn_packed", "moe_gmm"))
    assert conv.layers(cfg) == 12 and attn.layers(cfg) == 4
    assert conv.token_bytes(cfg) == 2048 * 6 and conv.tail_bytes(cfg) == 2 * 2 * 2048 * 2
    # A step of 512 tokens and 120 live rows through 12 layers: 77.5 MB, 95 us.
    least = conv.least_seconds(cfg, 512 * 12, 120 * 12, V5E)
    assert least == pytest.approx((512 * 12 * 12_288 + 120 * 12 * 16_384) / 819e9)
    assert conv.kernel_seconds({"kernel_seconds": {"kda_conv_kernel.2": 0.25, "gmm": 1.0}}) == 0.25
    assert attn.geometry(cfg) == (32, 8, 64)
    assert attn.kv_bytes_per_context_token(cfg) == 8_192  # 2,048 a layer, four layers
    assert attn.qo_bytes_per_query_token(cfg) == 2 * 32 * 64 * 2 * 4
    assert attn.pair_ops(cfg) == 4 * 32 * 64 * 4
    # Decode rows are bound by bytes, a long prompt's chunk by operations.
    assert attn.least_seconds(cfg, 70_000, 100, 70_000, V5E) == pytest.approx(
        (70_000 * 8_192 + 100 * 32_768) / 819e9)
    assert attn.least_seconds(cfg, 4_000, 512, 512 * 3_800, V5E) == pytest.approx(
        512 * 3_800 * 32_768 / 197e12)
    assert attn.kernel_seconds({"kernel_seconds": {
        "rpa_kernel": 0.2, "rpa_decode_kernel": 0.1, "rpa_kernel_window": 9.0}}) == pytest.approx(0.3)
    # Every expert of a layer read once: 32 x 11.01M x 2 bytes, 0.86 ms.
    assert gmm.expert_bytes(cfg) == 3 * 2048 * 1792 * 2
    assert gmm.least_seconds(cfg, 32, 400, V5E) == pytest.approx(32 * 22_020_096 / 819e9)


def ctx_of(deltas=None, slice_deltas=None, trace=None, steps=1000.0, config=None):
    deltas = deltas or {}
    ctx = {"delta": lambda *names: next((deltas[n] for n in names if n in deltas), None),
           "steps": lambda: steps, "config": config or CELL.hf_config, "peaks": V5E,
           "trace": trace, "kernel_counts": M.kernel_counts}
    if slice_deltas is not None:
        ctx["slice_delta"] = lambda *names: next(
            (slice_deltas[n] for n in names if n in slice_deltas), None)
    return ctx


NEW = ["short_conv_roofline", "attn_packed_roofline", "moe_full_gmm_roofline",
       "conv_state_rows_per_step"]
CHUNK = 'vllm:linear_attn_tokens_total{kind="chunk"}'
DECODE = 'vllm:linear_attn_tokens_total{kind="decode"}'
STATE_ROWS = "vllm:linear_attn_state_rows_total"
KEYS = 'vllm:attn_context_tokens_total{kind="mixed"}'
TOKENS = 'vllm:engine_step_tokens_total{kind="mixed"}'
PAIRS = 'vllm:attn_ragged_pairs_total{kind="live"}'
HIT, ASSIGNED = "vllm:moe_experts_hit_total", "vllm:moe_assignments_total"


def hand_trace(tmp_path):
    """A device plane written by hand: 3 mixed steps; in each, 12
    ``kda_conv_kernel`` calls of 40 us, 4 ``rpa_kernel`` calls of 300 us and
    42 ``gmm`` calls of 330 us (three a sparse layer), then a fusion;
    reduced by the harness's own reducer from the ``.xplane.pb`` file."""
    ops, at = [], 1000
    for _ in range(3):
        start = at
        for name, dur, calls in (("kda_conv_kernel.1", 40_000, 12), ("rpa_kernel", 300_000, 4),
                                 ("gmm.3", 330_000, 42), ("fusion.3", 100_000, 16)):
            for _ in range(calls):
                ops.append((at, dur, name))
                at += dur
        ops.append((start, at - start, "while.2"))
        at += 50_000
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "timestamp_ns": 0, "events": sorted(ops)},
        {"name": "XLA Modules", "timestamp_ns": 0, "events": [(1000, at, "jit_step(1)")]}]},
        {"name": "/host:CPU", "lines": [{"name": "python/1", "timestamp_ns": 0,
                                         "events": [(0, at + 1000, "engine_loop")]}]}]
    f = tmp_path / "plugins" / "profile" / "t" / "hand.xplane.pb"
    f.parent.mkdir(parents=True)
    f.write_bytes(xspace(planes))
    return R.reduce_planes(R.read_planes(str(tmp_path), min_host_ns=60.0))


def test_the_readers_on_a_trace_written_by_hand(tmp_path):
    trace = hand_trace(tmp_path)
    assert trace["kernel_seconds"]["kda_conv_kernel"] == pytest.approx(36 * 40e-6)
    assert trace["kernel_seconds"]["gmm"] == pytest.approx(126 * 330e-6)
    read = lambda name, ctx: CELL.layer_metric(name).read(ctx)  # noqa: E731
    # 3 mixed steps of 400 tokens (100 decode rows and one chunk of 300 at a
    # context of 900) over 101 live rows whose contexts sum to 70,000.
    in_slices = {CHUNK: 3 * 300 * 12.0, DECODE: 3 * 100 * 12.0, STATE_ROWS: 3 * 101 * 12.0,
                 KEYS: 3 * 70_000.0, TOKENS: 3 * 400.0,
                 PAIRS: 3 * (69_100 + 300 * 900 - 300 * 299 / 2),
                 HIT: 3 * 14 * 32.0, ASSIGNED: 3 * 400 * 4 * 14.0}
    ctx = ctx_of(slice_deltas=in_slices, trace=trace)
    conv = read("short_conv_roofline", ctx)
    assert conv == pytest.approx(
        100 * (3 * 12 * (400 * 12_288 + 101 * 16_384) / 819e9) / (36 * 40e-6))
    attn = read("attn_packed_roofline", ctx)
    by_bytes = 3 * (70_000 * 8_192 + 400 * 32_768) / 819e9
    by_ops = in_slices[PAIRS] * 32_768 / 197e12
    assert attn == pytest.approx(100 * max(by_bytes, by_ops) / (12 * 300e-6)) and by_bytes > by_ops
    gmm = read("moe_full_gmm_roofline", ctx)
    assert gmm == pytest.approx(100 * (3 * 14 * 32 * 22_020_096 / 819e9) / (126 * 330e-6))
    assert 15 < conv < 25 and 55 < attn < 65 and 85 < gmm < 90
    rows = read("conv_state_rows_per_step", ctx_of({STATE_ROWS: 1000.0 * 101 * 12}))
    assert rows == pytest.approx(101.0)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_on_a_program_or_a_configuration_without_its_mixer(name):
    """The parent's program cannot run the cell and has no such counters in a
    trace of its own: the reader returns None and does not raise, and the
    line leaves the metric out. Another configuration's cell (Kimi's, whose
    trace holds the SAME convolution kernel, the same ``gmm`` and the same
    counters) reads None too for the three whose counts are this family's
    geometry; ``moe_full_gmm_roofline`` IS the accepted reader and counts by
    the configuration's own widths wherever it is listed."""
    read = CELL.layer_metric(name).read
    counters = {CHUNK: 10.0, DECODE: 10.0, STATE_ROWS: 10.0, KEYS: 10.0, TOKENS: 10.0,
                PAIRS: 10.0, HIT: 10.0, ASSIGNED: 10.0}
    assert read(ctx_of({}, {}, {"kernel_seconds": {"fusion": 1.0}})) is None
    assert read(ctx_of({}, None, None)) is None
    if name != "moe_full_gmm_roofline":
        kimi = M.Cell(MANIFEST, "kimilinear48b.long-answers").hf_config
        trace = {"kernel_seconds": {"kda_conv_kernel": 0.5, "rpa_kernel": 0.5, "gmm": 0.5}}
        assert read(ctx_of(counters, counters, trace, config=kimi)) is None
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [NAME] and entry["moves"] == "itl_p95_ms"
    assert name in {m["name"] for m in CELL.per_layer}


def test_the_entries_are_appended_and_the_accepted_lists_untouched():
    # Behind what was there, in order; what a later PR puts behind THEM is
    # free (no place is counted from a list's end: test_manifest.py's head).
    names = lambda key: [e["name"] for e in MANIFEST[key]]  # noqa: E731
    at = names("per_layer").index(NEW[0])
    assert names("per_layer")[at: at + 4] == NEW and at > names("per_layer").index(
        "ssm_state_rows_per_step")
    assert names("workloads").index(NAME) > names("workloads").index("falconh1-34b.long-answers")
    assert names("configs").index("lfm2-8b-a1b") > names("configs").index(
        "falcon-h1-34b-instruct")
    assert next(w for w in MANIFEST["workloads"] if w["name"] == NAME)["chips"] == 1
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    # A `benchmark` PR's to join (PERF.md section 7): this cell is on none of them.
    for accepted in ("decode_attn_roofline", "mixed_step_share", "linear_state_rows_per_step",
                     "moe_gmm_roofline", "moe_experts_hit_share"):
        assert NAME not in by_name[accepted].get("workloads", [])
    assert NAME not in next(m for m in MANIFEST["end_to_end"] if m["name"] == "itl_p50_ms")[
        "workloads"]
    reported = {m["name"] for m in CELL.per_layer}
    listless = {m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m}
    assert len(listless) == 22 and listless <= reported
    assert set(NEW) | {"sampler_fallback_rows_per_step", "ragged_attn_live_share",
                       "gen_late_p95_ms", "setup_compile_cache_hit_share"} <= reported
    assert len(CELL.entry["why"]) <= 200


def test_the_limits_of_correct_are_explained():
    limits = CELL.numbers["correct_limits"]
    assert {"p50_abs", "worst_sequence_mean_abs"} <= set(limits) <= {
        "p50_abs", "p90_abs", "p99_abs", "worst_sequence_mean_abs",
        "p50_factor", "p90_factor", "mean_factor", "worst_sequence_factor"}
    why = CELL.numbers["correct_limits_why"]
    for control in ("float8", "forgets its tail", "s + b"):
        assert control in why, control
    assert len(why) > 300 and json.dumps(CELL.numbers)
