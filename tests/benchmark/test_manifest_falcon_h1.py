"""What ``falconh1-34b.long-answers`` adds to ``BENCHMARK.json``: the
configuration against the catalog's, the arithmetic of its cut, the ladder
of step programs the cell really meets, the counting functions and the
three readers (on counters written down by hand and on a trace written by
``xplane_writer.py``)."""

from __future__ import annotations

import json

import pytest

from benchmark.harness import manifest as M
from benchmark.harness import trace_reduce as R

from .xplane_writer import xspace

MANIFEST = M.load_manifest()
NAME = "falconh1-34b.long-answers"
CELL = M.Cell(MANIFEST, NAME)
V5E = M.peaks_for("TPU v5 lite")
ROWS = CELL.engine_args["max_num_seqs"]

# The catalog's ``config`` for Falcon-H1-34B-Instruct, key for key.
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
    "attn_layer_indices": None, "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2, "mamba_n_groups": 2,
    "mamba_n_heads": 32, "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284], "model_type": "falcon_h1",
    "num_attention_heads": 20, "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120,
}


def test_the_catalogs_row_is_what_is_written_down_here():
    import pathlib

    rows = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not rows.exists():
        pytest.skip("the catalog is not on this machine")
    row = next(json.loads(line) for line in rows.read_text().splitlines()
               if '"Falcon-H1-34B-Instruct"' in line)
    assert row["config"] == PUBLISHED and row["source_url"] == CELL.config["bench"]["source"]


def test_the_configuration_holds_every_published_width_and_cuts_the_depth_alone():
    cfg, bench = CELL.hf_config, CELL.config["bench"]
    assert {k: cfg[k] for k in PUBLISHED if k != "num_hidden_layers"} == {
        k: v for k, v in PUBLISHED.items() if k != "num_hidden_layers"}
    assert list(bench["reduced"]) == ["num_hidden_layers"]
    assert bench["reduced"]["num_hidden_layers"]["published"] == 72
    assert bench["reduced"]["num_hidden_layers"]["run"] == cfg["num_hidden_layers"] == 6
    assert set(cfg) - set(PUBLISHED) == {"architectures", "torch_dtype"}
    assert (cfg["architectures"], cfg["torch_dtype"]) == (["FalconH1ForCausalLM"], "bfloat16")
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "falcon-h1-34b-instruct")
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == bench["source"]
    assert CELL.family == "falcon_h1" and CELL.engine_args["max_model_len"] == 12288
    assert "twelve pipeline stages" in bench["stands_for"] and "twelve times" in bench["stands_for"]
    assert len(bench["assumed"]) >= 7 and "DRAW_WIDTHS" in bench["weights"]
    for leaf in ("embed 1", "lm_head 2.5", "wk 4", "W_out 1", "conv bias 0.1"):
        assert leaf in bench["weights"], leaf


def test_the_arithmetic_of_the_cut():
    c = CELL.hf_config
    d, v, f = c["hidden_size"], c["vocab_size"], c["intermediate_size"]
    a, kh, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    inner, bc, hm = c["mamba_d_ssm"], c["mamba_n_groups"] * c["mamba_d_state"], c["mamba_n_heads"]
    attn = 2 * d * a * dh + 2 * d * kh * dh
    conv = inner + 2 * bc
    ssm = d * (2 * inner + 2 * bc + hm) + inner * d + 5 * conv + inner + 3 * hm
    assert attn == 31_457_280 and 2 * inner + 2 * bc + hm == 9248 and conv == 5120
    assert ssm == 68_351_072 and 3 * d * f == 330_301_440
    layer = attn + ssm + 3 * d * f + 2 * d
    assert layer == pytest.approx(430.12e6, rel=1e-4)
    total = 6 * layer + 2 * v * d + d
    assert total == pytest.approx(5254.6e6, rel=1e-4)
    assert 2 * total == pytest.approx(10.51e9, rel=1e-3)  # bytes of bf16: 66% of 16 GB
    assert 72 * layer + 2 * v * d == pytest.approx(33.6e9, rel=5e-3)
    # A request: 6 layers of 32 x 128 x 256 float32 and the tail of 3 inputs of 5120 in bf16.
    state = 6 * (hm * c["mamba_d_head"] * c["mamba_d_state"] * 4 + 3 * conv * 2)
    assert state == 6 * (4_194_304 + 30_720) == 25_350_144
    assert 2 * kh * dh * 2 * 6 == 12_288  # bytes of pages a token
    assert 0.25 < (2 * total + (ROWS + 1) * state) / 16e9 < 0.9


def test_the_program_sees_the_same_sizes():
    import jax.numpy as jnp
    from transformers import PretrainedConfig

    from vllm_tpu.models.registry import get_model_class

    cfg = PretrainedConfig.from_dict(CELL.hf_config)
    model = get_model_class(cfg)(cfg, dtype=jnp.bfloat16)
    assert model.state_bytes_per_slot() == 25_350_144
    assert model.fixed_state_bytes(ROWS) == (ROWS + 1) * 25_350_144
    assert (model.in_dim, model.conv_dim, model.conv_lanes) == (9248, 5120, 128)
    assert model.num_linear_attn_layers == 6 and model.is_hybrid_ssm
    spec = model.get_kv_cache_spec(16, 2)
    assert sorted(spec) == [f"layers.{i}" for i in range(6)]
    assert spec["layers.0"].page_size_bytes == 16 * 2 * 4 * 128 * 2  # 16 tokens of 4 KV heads
    assert len({type(s) for s in spec.values()}) == 1  # one KV group


def test_the_traffic_is_the_accepted_file_and_the_rate_four_fifths_of_the_knee():
    t = CELL.traffic
    assert t == M.Cell(MANIFEST, "kimilinear48b.long-answers").traffic
    assert t["generator"] == "open_poisson" and t["check"]["output_len"] == 1024
    assert CELL.numbers["rate_per_s"] == pytest.approx(0.8 * CELL.numbers["knee_per_s"], rel=0.03)
    assert len(CELL.numbers["knee_why"]) > 300 and "sweep" in CELL.numbers["knee_why"]


def test_the_warm_up_walks_the_ladder_this_cell_really_meets():
    """The cell pins the step to one mixed and one decode-only shape through
    the server's own flags: token bucket 512, one request bucket (all the
    slots), the whole page table of 768 pages. Worked out with the program's
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
    ssd, conv = M.kernel_counts("ssd"), M.kernel_counts("ssd_conv")
    assert ssd.state_bytes(cfg) == 2 * 32 * 128 * 256 * 4 == 8_388_608
    assert ssd.token_bytes(cfg) == (3 * 4096 + 2 * 512) * 4 == 53_248
    # 64 decode rows through 6 layers: 3.24 GB, 3.96 ms.
    least = ssd.least_seconds(cfg, 64 * 6, V5E)
    assert least == pytest.approx(64 * 6 * (8_388_608 + 53_248) / 819e9) and 3.9e-3 < least < 4.0e-3
    assert ssd.kernel_seconds({"kernel_seconds": {"ssd_decode_kernel.1": 0.5, "fusion": 2.0}}) == 0.5
    assert conv.channels(cfg) == 5120
    assert conv.token_bytes(cfg) == 5120 * 6 and conv.tail_bytes(cfg) == 2 * 3 * 5120 * 2
    assert conv.least_seconds(cfg, 512, 64, V5E) == pytest.approx(
        (512 * 30_720 + 64 * 61_440) / 819e9)
    assert conv.kernel_seconds({"kernel_seconds": {"kda_conv_kernel": 0.25, "gmm": 1.0}}) == 0.25


def ctx_of(deltas=None, slice_deltas=None, trace=None, steps=1000.0, config=None):
    deltas = deltas or {}
    ctx = {"delta": lambda *names: next((deltas[n] for n in names if n in deltas), None),
           "steps": lambda: steps, "config": config or CELL.hf_config, "peaks": V5E,
           "trace": trace, "kernel_counts": M.kernel_counts}
    if slice_deltas is not None:
        ctx["slice_delta"] = lambda *names: next(
            (slice_deltas[n] for n in names if n in slice_deltas), None)
    return ctx


NEW = ["ssd_decode_roofline", "ssd_conv_roofline", "ssm_state_rows_per_step"]
CHUNK = 'vllm:linear_attn_tokens_total{kind="chunk"}'
DECODE = 'vllm:linear_attn_tokens_total{kind="decode"}'
STATE_ROWS = "vllm:linear_attn_state_rows_total"


def hand_trace(tmp_path):
    """A device plane written by hand: 3 steps of 6 layers, in each layer a
    ``kda_conv_kernel`` of 20 us and an ``ssd_decode_kernel`` of 700 us inside
    the layer scan's ``while``, then a fusion; reduced by the harness's own
    reducer from the ``.xplane.pb`` file."""
    ops, at = [], 1000
    for step in range(3):
        start = at
        inner = []
        for _ in range(6):
            inner += [(at, 20_000, "kda_conv_kernel.1"), (at + 20_000, 700_000, "ssd_decode_kernel"),
                      (at + 720_000, 100_000, "fusion.3")]
            at += 820_000
        ops += [(start, at - start, "while.2")] + inner
        at += 50_000
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "timestamp_ns": 0, "events": ops},
        {"name": "XLA Modules", "timestamp_ns": 0, "events": [(1000, at, "jit_step(1)")]}]},
        {"name": "/host:CPU", "lines": [{"name": "python/1", "timestamp_ns": 0,
                                         "events": [(0, at + 1000, "engine_loop")]}]}]
    f = tmp_path / "plugins" / "profile" / "t" / "hand.xplane.pb"
    f.parent.mkdir(parents=True)
    f.write_bytes(xspace(planes))
    return R.reduce_planes(R.read_planes(str(tmp_path), min_host_ns=60.0))


def test_the_readers_on_a_trace_written_by_hand(tmp_path):
    trace = hand_trace(tmp_path)
    assert trace["kernel_seconds"]["ssd_decode_kernel"] == pytest.approx(18 * 700e-6)
    assert trace["kernel_seconds"]["kda_conv_kernel"] == pytest.approx(18 * 20e-6)
    read = lambda name, ctx: CELL.layer_metric(name).read(ctx)  # noqa: E731
    # 3 decode-only steps of 64 live rows through 6 layers.
    in_slices = {DECODE: 3 * 64 * 6.0, CHUNK: 0.0, STATE_ROWS: 3 * 64 * 6.0}
    ctx = ctx_of(slice_deltas=in_slices, trace=trace)
    ssd = read("ssd_decode_roofline", ctx)
    assert ssd == pytest.approx(100 * (3 * 64 * 6 * 8_441_856 / 819e9) / (18 * 700e-6))
    conv = read("ssd_conv_roofline", ctx)
    assert conv == pytest.approx(100 * (3 * 64 * 6 * (30_720 + 61_440) / 819e9) / (18 * 20e-6))
    assert 90 < ssd < 100 and 30 < conv < 40
    rows = read("ssm_state_rows_per_step", ctx_of({STATE_ROWS: 1000.0 * 64 * 6}))
    assert rows == pytest.approx(64.0)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_on_a_program_or_a_configuration_without_its_mixer(name):
    """The parent's program has no such kernel (and cannot run the cell): the
    reader returns None and does not raise, and the line leaves the metric
    out. Another configuration's cell (Kimi's, whose trace holds the SAME
    convolution kernel and whose program the same counters) reads None too:
    the counts here are this family's geometry."""
    read = CELL.layer_metric(name).read
    counters = {DECODE: 10.0, CHUNK: 10.0, STATE_ROWS: 10.0}
    assert read(ctx_of({}, {}, {"kernel_seconds": {"fusion": 1.0, "rpa_kernel": 0.5}})) is None
    assert read(ctx_of({}, None, None)) is None
    kimi = M.Cell(MANIFEST, "kimilinear48b.long-answers").hf_config
    trace = {"kernel_seconds": {"kda_conv_kernel": 0.5, "ssd_decode_kernel": 0.5}}
    assert read(ctx_of(counters, counters, trace, config=kimi)) is None
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [NAME] and entry["moves"] == "itl_p95_ms"
    assert name in {m["name"] for m in CELL.per_layer}


def test_the_entries_are_appended_and_the_accepted_lists_untouched():
    assert [m["name"] for m in MANIFEST["per_layer"]][-3:] == NEW
    assert MANIFEST["workloads"][-1]["name"] == NAME and MANIFEST["workloads"][-1]["chips"] == 1
    assert MANIFEST["configs"][-1]["name"] == "falcon-h1-34b-instruct"
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    # A `benchmark` PR's to join (PERF.md section 7): this cell is on none of them.
    for accepted in ("decode_attn_roofline", "mixed_step_share", "linear_state_rows_per_step"):
        assert NAME not in by_name[accepted].get("workloads", [])
    assert NAME not in next(m for m in MANIFEST["end_to_end"] if m["name"] == "itl_p50_ms")[
        "workloads"]
    reported = {m["name"] for m in CELL.per_layer}
    assert set(NEW) | {"sampler_fallback_rows_per_step", "ragged_attn_live_share",
                       "device_idle_share"} <= reported


def test_the_limits_of_correct_are_explained():
    limits = CELL.numbers["correct_limits"]
    assert {"p50_abs", "worst_sequence_mean_abs"} <= set(limits) <= {
        "p50_abs", "p90_abs", "p99_abs", "worst_sequence_mean_abs",
        "p50_factor", "p90_factor", "mean_factor", "worst_sequence_factor"}
    why = CELL.numbers["correct_limits_why"]
    for control in ("bfloat16", "multiplied by zero", "key_multiplier"):
        assert control in why, control
    assert len(why) > 300 and json.dumps(CELL.numbers)
