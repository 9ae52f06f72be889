"""What ``kimilinear48b.long-answers`` adds to ``BENCHMARK.json``: the
configuration against the catalog's, the arithmetic of its cut, the ladder
of step programs the cell really meets, the counting functions and the
readers."""

from __future__ import annotations

import json

import pytest

from benchmark.harness import manifest as M

MANIFEST = M.load_manifest()
NAME = "kimilinear48b.long-answers"
CELL = M.Cell(MANIFEST, NAME)
V5E = M.peaks_for("TPU v5 lite")

# The catalog's ``config`` for Kimi-Linear-48B-A3B-Instruct, key for key.
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
    "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32, "num_expert_group": 1,
    "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "routed_scaling_factor": 2.446,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840,
}
REDUCED = ["num_hidden_layers", "linear_attn_config", "num_experts"]


def test_the_configuration_holds_every_published_width_and_cuts_three_keys():
    cfg, bench = CELL.hf_config, CELL.config["bench"]
    assert {k: cfg[k] for k in PUBLISHED if k not in REDUCED} == {
        k: v for k, v in PUBLISHED.items() if k not in REDUCED}
    assert list(bench["reduced"]) == REDUCED
    for key in REDUCED:
        assert bench["reduced"][key]["published"] == PUBLISHED[key]
    assert (cfg["num_hidden_layers"], cfg["num_experts"]) == (13, 32)
    la = cfg["linear_attn_config"]
    assert la == {**PUBLISHED["linear_attn_config"],
                  "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13], "full_attn_layers": [4, 8, 12]}
    assert set(cfg) - set(PUBLISHED) == {"architectures", "torch_dtype", "ep_size", "ep_rank",
                                         "max_position_embeddings"}
    assert cfg["max_position_embeddings"] == cfg["model_max_length"]
    assert (cfg["architectures"], cfg["torch_dtype"], cfg["ep_size"], cfg["ep_rank"]) == (
        ["KimiLinearForCausalLM"], "bfloat16", 8, 0)
    assert CELL.family == "kimi_linear" and CELL.engine_args["max_model_len"] == 12288
    assert "8 v5e chips" in bench["stands_for"] and len(bench["assumed"]) >= 7


def test_the_arithmetic_of_the_cut():
    cfg = CELL.hf_config
    d, v, f, fm = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"]
    la = cfg["linear_attn_config"]
    pj = la["num_heads"] * la["head_dim"]
    expert = 3 * d * fm
    assert expert == 7_077_888 and 32 * expert == 226_492_416
    kda = 4 * d * pj + 2 * (d * 128 + 128 * pj) + d * 32 + 3 * 4 * pj + 2 * pj + 32 + 128
    heads = cfg["num_attention_heads"]
    mla = (d * heads * 192 + d * 576 + 512 + 512 * heads * 256 + heads * 128 * d)
    assert 39.4e6 < kda < 39.7e6 and 29.0e6 < mla < 29.2e6
    moe = 32 * expert + expert + d * 256 + 256 + 2 * d
    total = (kda + 3 * d * f + 2 * d) + 9 * (kda + moe) + 3 * (mla + moe) + 2 * v * d + d
    assert 8.21e9 < 2 * total < 8.24e9  # bytes in bf16: 49% of the chip's 16.9 GB
    # A request's state: 10 layers of 32 x 128 x 128 float32 and the tail of 3 inputs.
    state = 10 * (32 * 128 * 128 * 4 + 3 * 3 * pj * 2)
    assert state == 21_708_800 and 193 * state == pytest.approx(4.19e9, rel=2e-3)
    assert 0.6 < (2 * total + 193 * state) / 16.9e9 < 0.75
    assert M.kernel_counts("mla_attn").bytes_per_context_token(cfg) == 3 * 1152
    assert M.kernel_counts("kda").state_bytes(cfg) == 2 * 2 ** 21


def test_the_program_sees_the_same_sizes():
    import jax.numpy as jnp
    from transformers import PretrainedConfig

    from vllm_tpu.models.registry import get_model_class

    cfg = PretrainedConfig.from_dict(CELL.hf_config)
    model = get_model_class(cfg)(cfg, dtype=jnp.bfloat16)
    assert model.state_bytes_per_slot() == 21_708_800
    assert (model.router_width, model.num_experts, model.first_expert) == (256, 32, 0)
    assert (model.head_layers, model.period, model.reps, model.tail_layers) == ([0], 4, 3, [])
    assert len(model.kda_layer_indices) == 10 and model.attn_layer_indices == [3, 7, 11]
    spec = model.get_kv_cache_spec(16, 2)
    assert sorted(spec) == ["layers.11", "layers.3", "layers.7"]
    assert spec["layers.3"].page_size_bytes == 16 * 640 * 2  # 576 numbers in 640 lanes


def test_the_traffic_is_what_the_issue_names():
    t = CELL.traffic
    assert t["generator"] == "open_poisson"
    assert t["prompt_len"] == {"dist": "lognormal", "median": 700, "sigma": 1.0, "min": 64, "max": 8192}
    assert t["output_len"] == {"dist": "lognormal", "median": 640, "sigma": 0.6, "min": 128, "max": 2048}
    assert t["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert (t["ramp_s"], t["drain_limit_s"]) == (30, 90)
    # chat's four greedy sequences, but a thousand answer tokens each: the
    # state is rounded where it is stored, once a step, so a check of 48
    # answer tokens cannot tell a bfloat16 state from a float32 one.
    assert t["check"] == {**M.load_json(M.BENCH_DIR / "traffic" / "chat.json")["check"],
                          "output_len": 1024}
    assert CELL.numbers["rate_per_s"] == pytest.approx(0.8 * CELL.numbers["knee_per_s"], rel=0.02)


def test_the_warm_up_walks_the_ladder_this_cell_really_meets():
    """The cell pins the step to one mixed and one decode-only shape,
    through the server's own flags: token bucket 512, request bucket 192
    (all the slots), the whole page table of 768 pages. Any request alone
    walks both. Worked out with the program's own bucket rule."""
    import argparse

    from benchmark.harness.server import engine_cli_args
    from vllm_tpu.engine.arg_utils import EngineArgs

    parser = EngineArgs.add_cli_args(argparse.ArgumentParser())
    cli = parser.parse_args(["--model", "none", *engine_cli_args(CELL.engine_args)])
    config = EngineArgs.from_cli_args(cli).create_engine_config()
    comp = config.compilation_config
    assert comp.token_buckets == [512] and comp.request_buckets == [192]
    assert config.scheduler_config.max_num_seqs == 192
    pages = comp.page_buckets(config.scheduler_config.max_model_len // 16)
    assert pages == [768] and pages[-1] * 16 == config.scheduler_config.max_model_len

    def pages_of(tokens):
        return next(b for b in pages if b >= -(-tokens // 16))

    def programs_of(wave):
        """A request's chunks run the mixed program at each context they
        reach, its answer tokens the decode-only one (T == R), unless
        another request's chunk shares the step."""
        out = set()
        for _, prompt, answer, *_ in wave:
            out |= {(512, 192, pages_of(ctx)) for ctx in [*range(512, prompt, 512), prompt]}
            out |= {(192, 192, pages_of(prompt + i)) for i in range(1, answer)}
        return out

    waves = CELL.numbers["warmup_waves"]
    want = {(t, 192, b) for t in (192, 512) for b in pages}
    assert programs_of(waves[0]) == want and len(want) == 2
    assert set().union(*map(programs_of, waves)) == want
    assert "2 programs" in CELL.numbers["warmup_waves_why"]
    # The longest request of the traffic fits the table.
    t = CELL.traffic
    assert pages_of(t["prompt_len"]["max"] + t["output_len"]["max"]) == 768


def test_the_counting_functions():
    cfg = CELL.hf_config
    kda, mla, gmm = (M.kernel_counts(k) for k in ("kda", "mla_attn", "moe_gmm"))
    # 130 decode rows through 10 layers: 5.5 GB of state, 6.8 ms.
    least = kda.least_seconds(cfg, 130 * 10, 0, V5E)
    assert least == pytest.approx(130 * 10 * (2 ** 22 + 32 * 641 * 4) / 819e9)
    assert 6.6e-3 < least < 6.9e-3
    # 382 chunk tokens: a 64th of the state a token, then the operations.
    chunk = kda.least_seconds(cfg, 0, 382 * 10, V5E)
    assert chunk == pytest.approx(382 * 10 * (2 ** 22 / 64 + 32 * 641 * 4) / 819e9)
    assert kda.chunk_token_ops(cfg) * 382 * 10 / 197e12 < chunk
    assert kda.kernel_seconds({"kernel_seconds": {"kda_decode_kernel": 0.5, "fusion": 2.0}}) == 0.5
    # 130 rows at 2,300 tokens of context: 1.03 GB of latent rows, 1.26 ms.
    assert mla.least_seconds(cfg, 130 * 2300, V5E) == pytest.approx(130 * 2300 * 3456 / 819e9)
    # Their pairs, one query a row, are a quarter of that in operations; a
    # 382-token chunk at context 2,000 is bound by its 837k pairs instead.
    assert mla.pair_ops(cfg) == (2 * 576 + 2 * 512) * 32 * 3
    assert mla.least_seconds(cfg, 130 * 2300, V5E, 130 * 2300) == mla.least_seconds(cfg, 130 * 2300, V5E)
    pairs = 382 * 2000 + 382 * 383 // 2
    assert mla.least_seconds(cfg, 2382, V5E, pairs) == pytest.approx(pairs * 208896 / 197e12)
    assert mla.least_seconds(cfg, 2382, V5E, pairs) > 50 * mla.least_seconds(cfg, 2382, V5E)
    assert gmm.expert_bytes(cfg) == 3 * 2304 * 1024 * 2


def ctx_of(deltas=None, slice_deltas=None, trace=None, steps=1000.0):
    deltas = deltas or {}
    ctx = {"delta": lambda *names: next((deltas[n] for n in names if n in deltas), None),
           "steps": lambda: steps, "config": CELL.hf_config, "peaks": V5E, "trace": trace,
           "kernel_counts": M.kernel_counts}
    if slice_deltas is not None:
        ctx["slice_delta"] = lambda *names: next(
            (slice_deltas[n] for n in names if n in slice_deltas), None)
    return ctx


NEW = ["kda_roofline", "mla_attn_roofline", "linear_state_rows_per_step", "moe_share_gmm_roofline"]


def test_the_readers_on_counters_written_down_by_hand():
    read = lambda name, ctx: CELL.layer_metric(name).read(ctx)  # noqa: E731
    rows = read("linear_state_rows_per_step",
                ctx_of({"vllm:linear_attn_state_rows_total": 1000.0 * 130 * 10}))
    assert rows == pytest.approx(130.0)
    trace = {"kernel_seconds": {"kda_decode_kernel": 1.0, "mla_kernel": 0.5, "gmm": 1.2, "fusion": 1.0}}
    in_slices = {'vllm:linear_attn_tokens_total{kind="decode"}': 100 * 130 * 10.0,
                 'vllm:linear_attn_tokens_total{kind="chunk"}': 100 * 300 * 10.0,
                 'vllm:attn_context_tokens_total{kind="latent"}': 100 * 130 * 2300.0,
                 "vllm:moe_experts_hit_total": 100 * 12 * 32.0,
                 "vllm:moe_assignments_total": 100 * 12 * 130.0}
    ctx = ctx_of(slice_deltas=in_slices, trace=trace)
    kda = read("kda_roofline", ctx)
    assert kda == pytest.approx(100 * (100 * 130 * 10 * (2 ** 22 + 32 * 641 * 4) / 819e9) / 1.0)
    attn = read("mla_attn_roofline", ctx)
    assert attn == pytest.approx(100 * (100 * 130 * 2300 * 3456 / 819e9) / 0.5)
    gmm = read("moe_share_gmm_roofline", ctx)
    assert gmm == pytest.approx(100 * (100 * 12 * 32 * 14_155_776 / 819e9) / 1.2)
    assert 0 < kda < 100 and 0 < attn < 100 and 0 < gmm < 100
    # The decode rows' own pairs change nothing (bytes bind); a slice full
    # of chunks is held to its operations.
    in_slices['vllm:attn_ragged_pairs_total{kind="live"}'] = 100 * 130 * 2300.0
    assert read("mla_attn_roofline", ctx_of(slice_deltas=in_slices, trace=trace)) == pytest.approx(attn)
    in_slices['vllm:attn_ragged_pairs_total{kind="live"}'] = 4.0e8
    assert read("mla_attn_roofline", ctx_of(slice_deltas=in_slices, trace=trace)) == pytest.approx(
        100 * (4.0e8 * 208896 / 197e12) / 0.5)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_on_a_program_without_its_counters(name):
    """The parent's program has none of them (and cannot run the cell): the
    reader returns None and does not raise, and the line leaves the metric
    out."""
    trace = {"kernel_seconds": {"fusion": 1.0, "rpa_kernel": 0.5}}
    read = CELL.layer_metric(name).read
    assert read(ctx_of({}, {}, trace)) is None
    assert read(ctx_of({}, None, None)) is None
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    # The cell that brought the entry leads its list (PR 46 put two more cells on
    # ``moe_share_gmm_roofline``); each of these is read in a mixed step too.
    assert entry["workloads"][0] == NAME and entry["moves"] == "itl_p95_ms"


def test_what_the_accepted_listless_metrics_find_here():
    """Slot state turns the prefix cache off, so ``prefix_hit_share`` would
    print nothing: it lists the two cells that report it.
    ``ragged_attn_live_share`` keeps its entry as it is (an accepted test
    holds it key for key) and reads a number here: the runner counts the
    latent kernel's pairs, the ragged kernel's fork, under the same
    counter."""
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert by_name["prefix_hit_share"]["workloads"] == ["mistral7b.chat", "sdar30b.chat"]
    assert "workloads" not in by_name["ragged_attn_live_share"]
    reported = {m["name"] for m in CELL.per_layer}
    assert set(NEW) | {"sampler_fallback_rows_per_step", "ragged_attn_live_share"} <= reported
    assert not {"prefix_hit_share", "moe_gmm_roofline", "moe_experts_hit_share",
                "decode_attn_roofline"} & reported


def test_the_latent_kernels_pairs_by_its_own_blocks():
    from vllm_tpu.ops.mla_kernel import count_latent_pairs, latent_blocks

    assert latent_blocks(32, 192, 768, 16) == (4, 32)
    # 130 decode rows at 2,300 tokens of context: a row is one of a block's
    # 4 queries and the block runs against the row's 5 blocks of 512 keys.
    live, computed = count_latent_pairs([1] * 130, [2300] * 130, 4, 512)
    assert live == 130 * 2300 and computed == 130 * 5 * 4 * 512
    # A 40-token chunk from position 100 that straddles three blocks of queries.
    live, computed = count_latent_pairs([10, 40], [10, 140], 16, 128)
    assert live == 55 + 40 * 100 + 820
    assert computed == (1 * 1 + 4 * 2) * 16 * 128


def test_the_limits_of_correct_are_three_and_explained():
    limits = CELL.numbers["correct_limits"]
    assert {"p50_abs", "p90_abs", "worst_sequence_mean_abs"} <= set(limits) <= {
        "p50_abs", "p90_abs", "p99_abs", "worst_sequence_mean_abs",
        "p50_factor", "p90_factor", "mean_factor", "worst_sequence_factor"}
    why = CELL.numbers["correct_limits_why"]
    assert "bfloat16" in why and "float8" in why and len(why) > 300
    # The chip's readings under the 1,024-token check (PERF.md section 4):
    # every limit lies between the largest of 41 sound seeds and the smallest
    # reading of the float8-latent control (5 seeds), with 5% of room on
    # both sides. The bfloat16-state control's smallest reading (0.0370,
    # 0.0919, 0.0469 on the seed that reads lowest sound) stands 2-4% over
    # the largest sound one: no limit separates those two on every seed.
    sound = {"p50_abs": 0.0357, "p90_abs": 0.0898, "worst_sequence_mean_abs": 0.0463}
    float8 = {"p50_abs": 0.0447, "p90_abs": 0.1081, "worst_sequence_mean_abs": 0.0544}
    for key, most in sound.items():
        assert 1.05 * most < limits[key] < float8[key] / 1.05


def test_two_factors_are_held_against_both_lower_precisions():
    """What the three absolute limits cannot do, a factor over the
    reference's own bfloat16 pass does (the review of PR 42; chip calls R1
    and R2, PERF.md section 4): each factor's limit lies over the largest
    sound reading and under the smallest reading of the state kept in
    bfloat16, the nearer of the two controls, with 5% of room on both
    sides; the float8 control reads a quarter higher still."""
    limits = CELL.numbers["correct_limits"]
    sound = {"p50_factor": 1.093, "worst_sequence_factor": 1.136}
    state_in_bfloat16 = {"p50_factor": 1.240, "worst_sequence_factor": 1.325}
    latent_rows_through_float8 = {"p50_factor": 1.523, "worst_sequence_factor": 1.549}
    assert {k for k in limits if k.endswith("_factor")} == set(sound)
    for key, most in sound.items():
        assert 1.05 * most < limits[key] < state_in_bfloat16[key] / 1.05
        assert state_in_bfloat16[key] < latent_rows_through_float8[key]
    assert "p50_factor" in CELL.numbers["correct_limits_why"]
    assert json.dumps(CELL.numbers)  # plain data
