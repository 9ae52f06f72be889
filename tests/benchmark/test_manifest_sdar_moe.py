"""What ``sdar30b.chat`` adds to ``BENCHMARK.json``: the configuration
against the published one, the ladder of step programs that generation by
diffusion really meets, the counting functions and the four readers."""

from __future__ import annotations

import pytest

from benchmark.harness import manifest as M

MANIFEST = M.load_manifest()
CELL = M.Cell(MANIFEST, "sdar30b.chat")
V5E = M.peaks_for("TPU v5 lite")

PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}


def test_the_configuration_holds_the_published_widths_and_cuts_depth_alone():
    cfg = CELL.hf_config
    assert {k: cfg[k] for k in PUBLISHED if k != "num_hidden_layers"} == {
        k: v for k, v in PUBLISHED.items() if k != "num_hidden_layers"}
    assert cfg["num_hidden_layers"] == 7 and list(CELL.config["bench"]["reduced"]) == [
        "num_hidden_layers"]
    assert cfg["architectures"] == ["SDARMoeForCausalLM"] and cfg["torch_dtype"] == "bfloat16"
    # The model's generation defaults, read by the program from config.json.
    assert (cfg["block_length"], cfg["mask_token_id"], cfg["remasking_strategy"],
            cfg["denoising_steps"], cfg["confidence_threshold"]) == (4, 151669, "sequential", 4, 0.9)
    assert CELL.family == "sdar_moe" and CELL.engine_args["max_model_len"] == 12288
    assert len(CELL.config["bench"]["assumed"]) >= 4


def test_the_arithmetic_of_the_cut():
    cfg = CELL.hf_config
    d, f, e, v = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_experts"], cfg["vocab_size"]
    heads, kv = cfg["num_attention_heads"] * cfg["head_dim"], cfg["num_key_value_heads"] * cfg["head_dim"]
    layer = e * 3 * d * f + d * heads + 2 * d * kv + heads * d + d * e + 2 * d + 2 * cfg["head_dim"]
    assert e * 3 * d * f == 603_979_776 and d * heads + 2 * d * kv + heads * d == 18_874_368
    total = 7 * layer + 2 * v * d + d
    assert 9.9e9 < 2 * total < 10.0e9  # bytes in bf16: 59% of the chip's 16.9 GB
    assert M.kernel_counts("block_attn").kv_bytes_per_context_token(cfg) == 14336


def test_the_traffic_is_chats_under_the_static_rule_at_two_steps():
    chat = M.load_json(M.BENCH_DIR / "traffic" / "chat.json")
    ours = CELL.traffic
    assert {k: ours[k] for k in ours if k not in ("why", "sampling")} == {
        k: chat[k] for k in chat if k not in ("why", "sampling")}
    assert ours["sampling"] == {**chat["sampling"], "remasking": "low_confidence_static",
                                "denoising_steps": 2}
    # The check requests carry temperature 0 and nothing else: they run the
    # configuration's default rule, sequential.
    from benchmark.harness import traffic as T

    reqs, _ = T.check_requests(ours, 5, 151936, 32768)
    assert all(q.sampling == {"temperature": 0.0} for q in reqs)
    assert [len(q.prompt) % 4 for q in reqs] == [0, 3, 1, 0]


def test_the_warm_up_walks_the_ladder_generation_by_diffusion_really_meets():
    """The cell pins the step to one shape: token bucket 512, request
    buckets 128 and 256, through the server's own flags. A decoding request
    is a row of 4 tokens and no program is decode-only, so what is left is
    (512, 128, pages), one program a page bucket, and one request alone
    with a prompt inside each page bucket (waves 0-5) walks them all.
    Worked out with the program's own bucket rule, as the test beside this
    one does for Mistral's ladder."""
    import argparse

    from benchmark.harness.server import engine_cli_args
    from vllm_tpu.engine.arg_utils import EngineArgs

    parser = EngineArgs.add_cli_args(argparse.ArgumentParser())
    cli = parser.parse_args(["--model", "none", *engine_cli_args(CELL.engine_args)])
    config = EngineArgs.from_cli_args(cli).create_engine_config()
    comp = config.compilation_config
    assert comp.token_buckets == [512] and comp.request_buckets == [128, 256]
    pages = comp._pow2_buckets(16, config.scheduler_config.max_model_len // 16)

    def bucket(n, ladder):
        return next(b for b in ladder if b >= n)

    def pages_of(tokens):
        return bucket(-(-tokens // 16), pages)

    def programs_of(wave):
        """Every request of a wave is a row; the longest prompt's chunks
        and block passes set the page bucket."""
        n = sum(count for count, *_ in wave)
        r = bucket(n, comp.request_buckets)
        _, prompt, answer, *_ = max(wave, key=lambda e: e[1])
        stored = prompt // 4 * 4
        contexts = list(range(512, stored, 512)) + [stored]  # its prefill chunks
        contexts += range(stored + 4, prompt + answer + 4, 4)  # its block passes
        return {(bucket(4 * n, comp.token_buckets), r, pages_of(ctx)) for ctx in contexts}

    waves = CELL.numbers["warmup_waves"]
    want = {(512, 128, b) for b in pages if b <= 512}
    assert set().union(*map(programs_of, waves[:6])) == want and len(want) == 6
    # Waves 6-11 are there for the old test alone: nothing new.
    assert set().union(*map(programs_of, waves[6:])) <= want
    assert "6 programs" in CELL.numbers["warmup_waves_why"]


def test_grouped_matmul_counts():
    k = M.kernel_counts("moe_gmm")
    cfg = CELL.hf_config
    assert k.expert_bytes(cfg) == 3 * 2048 * 768 * 2 == 9_437_184
    assert k.pair_ops(cfg) == 6 * 2048 * 768
    # One step that hits every expert of 7 layers with 80 live tokens: 8.46
    # GB, 10.3 ms at 819 GB/s; its 4480 pairs are 42 GFLOP, 0.2 ms.
    least = k.least_seconds(cfg, 128 * 7, 80 * 8 * 7, V5E)
    assert least == pytest.approx(128 * 7 * 9_437_184 / 819e9) == pytest.approx(10.32e-3, rel=1e-3)
    # 8192 tokens a step: the operations set the least time.
    assert k.least_seconds(cfg, 128 * 7, 8192 * 8 * 7, V5E) == pytest.approx(
        8192 * 8 * 7 * 6 * 2048 * 768 / 197e12)
    assert k.kernel_seconds({"kernel_seconds": {"gmm": 1.5, "fusion": 9.0, "rpa_kernel": 2.0}}) == 1.5


def ctx_of(deltas=None, slice_deltas=None, trace=None, steps=1000.0):
    deltas = deltas or {}
    ctx = {"delta": lambda *names: next((deltas[n] for n in names if n in deltas), None),
           "steps": lambda: steps, "config": CELL.hf_config, "peaks": V5E, "trace": trace,
           "kernel_counts": M.kernel_counts}
    if slice_deltas is not None:
        ctx["slice_delta"] = lambda *names: next(
            (slice_deltas[n] for n in names if n in slice_deltas), None)
    return ctx


def test_the_four_readers_on_counters_written_down_by_hand():
    read = lambda name, ctx: CELL.layer_metric(name).read(ctx)  # noqa: E731
    passes = {'vllm:diffusion_passes_total{kind="denoise"}': 2000.0,
              'vllm:diffusion_passes_total{kind="store"}': 1000.0,
              "vllm:diffusion_committed_tokens_total": 3960.0,
              "vllm:moe_experts_hit_total": 1000.0 * 7 * 128 * 0.75}
    assert read("diffusion_tokens_per_pass", ctx_of(passes)) == pytest.approx(1.32)
    assert read("moe_experts_hit_share", ctx_of(passes)) == pytest.approx(75.0)
    trace = {"kernel_seconds": {"gmm": 2.0, "rpa_kernel": 0.5, "fusion": 1.0}}
    in_slices = {"vllm:moe_experts_hit_total": 100 * 7 * 128.0,
                 "vllm:moe_assignments_total": 100 * 80 * 8 * 7.0,
                 'vllm:attn_context_tokens_total{kind="block"}': 100 * 20 * 800.0}
    gmm = read("moe_gmm_roofline", ctx_of(slice_deltas=in_slices, trace=trace))
    assert gmm == pytest.approx(100 * (100 * 7 * 128 * 9_437_184 / 819e9) / 2.0)
    attn = read("block_attn_roofline", ctx_of(slice_deltas=in_slices, trace=trace))
    assert attn == pytest.approx(100 * (100 * 20 * 800 * 14336 / 819e9) / 0.5)
    assert 0 < attn < 100 and 0 < gmm < 100


@pytest.mark.parametrize("name", ["diffusion_tokens_per_pass", "moe_experts_hit_share",
                                  "moe_gmm_roofline", "block_attn_roofline"])
def test_a_reader_finds_nothing_on_a_program_without_its_counters(name):
    """The parent's program has none of them: the reader returns None and
    does not raise, and the line leaves the metric out."""
    trace = {"kernel_seconds": {"fusion": 1.0, "rpa_kernel": 0.5}}
    read = CELL.layer_metric(name).read
    assert read(ctx_of({}, {}, trace)) is None
    assert read(ctx_of({}, None, None)) is None
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    # PR 46 put ``lagunaxs2.code-mixed`` on ``moe_experts_hit_share`` too.
    assert entry["workloads"][0] == "sdar30b.chat"
