"""Offline decode-throughput benchmark: one JSON line to stdout.

One process, one fixed configuration, on one TPU chip; anything else is an
error (exit code != 0 and no JSON). The process that runs the engine is
the only one that touches JAX: a chip belongs to one process at a time.

Configuration (the one ``BENCH_r05.json`` scored): Llama-3-8B widths
(hidden 4096, intermediate 14336, 32 layers, 32 q / 8 KV heads, vocab
128256), int8 weights with int8 embedding and lm_head, fp8 KV cache,
page 16, 1408 KV blocks; 128 requests of 32 prompt tokens and 128 output
tokens, greedy, closed loop, through ``LLM.generate``; dummy weights
(tok/s does not depend on weight values). 8B in bf16 cannot be the
configuration: its 16 GB of weights do not fit the chip's 16 GB.

Protocol follows the reference's ``vllm bench throughput`` shape: one
warm-up pass compiles every bucket, then ``VLLM_TPU_BENCH_PASSES``
(default 5) timed passes. ``value`` is the best pass; ``median_value`` and
``worst_pass_value`` give the spread. Metric: output tokens/sec/chip;
``vs_baseline`` is against 2000 tok/s/chip (BASELINE.json). The JSON also
names the device it ran on and carries a roofline context (estimated HBM
bytes per decode step -> implied bandwidth utilization at the scored
rate, model FLOPs/token -> MFU, from ``vllm_tpu/metrics/roofline.py``),
the host prep/dispatch/wait step-time split, the goodput block, the
device-side op split of one profiled pass (``VLLM_TPU_BENCH_OP_SPLIT=0``
opts out) and the in-engine kernel A/B (``VLLM_TPU_BENCH_AB=0`` opts
out). ``VLLM_TPU_BENCH_DECODE_STEPS`` accepts "dynamic" (default: the
device-resident decode loop, chain depth 8) or a fixed K.

What earlier rounds measured with this script is in PERF.md.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

os.environ.setdefault("VLLM_TPU_LOG_LEVEL", "WARNING")
# The bench model is synthetic; never touch the HF hub (no network here —
# the retry loop alone wastes ~40s).
os.environ.setdefault("HF_HUB_OFFLINE", "1")

BASELINE_TOK_S_PER_CHIP = 2000.0

SHAPE = dict(
    hidden_size=4096, intermediate_size=14336, num_hidden_layers=32,
    num_attention_heads=32, num_key_value_heads=8, vocab_size=128256,
)
QUANT = "int8"
NUM_REQUESTS, PROMPT_LEN, OUTPUT_LEN = 128, 32, 128
BLOCK_SIZE, NUM_KV_BLOCKS = 16, 1408


def main() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(
            f"bench.py measures a TPU; jax found {dev.platform!r} "
            f"({dev.device_kind}). Nothing was measured."
        )

    from transformers import LlamaConfig

    from vllm_tpu.entrypoints.llm import LLM
    from vllm_tpu.sampling_params import SamplingParams

    shape, quant = SHAPE, QUANT
    n_req, prompt_len, output_len = NUM_REQUESTS, PROMPT_LEN, OUTPUT_LEN
    params = SamplingParams(
        temperature=0.0, max_tokens=output_len, ignore_eos=True
    )
    prompts = [
        {"prompt_token_ids": [(7 * i + j) % 32000 for j in range(prompt_len)]}
        for i in range(n_req)
    ]
    cfg = LlamaConfig(
        max_position_embeddings=4096, tie_word_embeddings=False, **shape
    )
    cfg.architectures = ["LlamaForCausalLM"]
    decode_steps_env = os.environ.get(
        "VLLM_TPU_BENCH_DECODE_STEPS", "dynamic"
    ).strip().lower()
    decode_steps_dynamic = decode_steps_env == "dynamic"
    if not decode_steps_dynamic:
        # A numeric K scores the fixed unrolled chain in isolation.
        os.environ["VLLM_TPU_DISABLE_DYNAMIC_DECODE"] = "1"
    llm = LLM(
        model="dummy-llama",
        hf_config=cfg,
        load_format="dummy",
        quantization=quant,
        max_model_len=2048,
        max_num_batched_tokens=512,
        max_num_seqs=n_req,
        block_size=BLOCK_SIZE,
        # Explicit KV budget: the workload is known (n_req x 160 tokens
        # -> 10 blocks/req) and headroom is scarce next to 8B weights.
        num_gpu_blocks_override=NUM_KV_BLOCKS,
        # Per-row-int8 embedding + int8 lm_head shave the 2.1 GiB bf16
        # table/head, and fp8 KV halves the cache.
        quantize_embedding_layers=True,
        kv_cache_dtype="fp8",
        # In-jit multi-step decode amortizes per-launch host overhead;
        # exact for greedy. VLLM_TPU_BENCH_DECODE_STEPS accepts "dynamic"
        # (default — the device-resident lax.while_loop path, chain-depth
        # gate 8) or a numeric fixed K (which also disables the dynamic
        # loop so the score really measures the fixed-K unrolled chain).
        num_decode_steps=(8 if decode_steps_dynamic
                          else int(decode_steps_env)),
    )
    # Warm-up: one full dress-rehearsal pass compiles every (tokens, reqs,
    # blocks) bucket (the persistent compilation cache makes a second
    # cold start skip even these).
    llm.generate(prompts, params)

    # engine_core is an InprocClient wrapping the real EngineCore.
    core = llm.llm_engine.engine_core.engine_core
    worker = core.executor.worker
    runner = worker.runner
    runner.timing = {k: 0 if k == "steps" else 0.0 for k in runner.timing}

    # Identical consecutive passes still vary: best-of-N scores the
    # framework, median/worst report the spread.
    passes = max(1, int(os.environ.get("VLLM_TPU_BENCH_PASSES", 5)))
    times = []
    goodput = None
    for i in range(passes):
        # The last pass doubles as the goodput window: per-step ITL
        # samples + the spec-accepted counter delta score accepted
        # tokens/s under the ITL SLO (spec off: accepted == emitted).
        instrument = i == passes - 1
        if instrument:
            core.drain_itl_samples()
            acc0 = core.scheduler._spec_num_accepted_tokens
            draft0 = core.scheduler._spec_num_draft_tokens
        t0 = time.monotonic()
        outs = llm.generate(prompts, params)
        dt = time.monotonic() - t0
        times.append(dt)
        if instrument:
            from vllm_tpu.metrics.goodput import goodput_summary

            spec_on = core.scheduler._spec_num_draft_tokens > draft0
            pass_tokens = sum(
                len(o.outputs[0].token_ids) for o in outs
            )
            goodput = goodput_summary(
                core.drain_itl_samples(),
                elapsed_s=dt,
                accepted_tokens=(
                    core.scheduler._spec_num_accepted_tokens - acc0
                    if spec_on else None
                ),
                emitted_tokens=pass_tokens,
                slo_itl_ms=float(
                    os.environ.get("VLLM_TPU_BENCH_SLO_ITL_MS", 50.0)
                ),
            )

    n_out = sum(len(o.outputs[0].token_ids) for o in outs)
    n_chips = len(jax.devices())

    def rate(dt: float) -> float:
        return round(n_out / dt / n_chips, 2)

    # Roofline context. Weight bytes actually resident (quantized models
    # stream ~1 byte/param); per decode step every weight is read once and
    # the running requests' KV context is read once.
    from vllm_tpu.metrics import roofline as rl

    weight_bytes = rl.weight_bytes(worker.params)
    kv_tok = rl.kv_bytes_per_token(
        shape["num_hidden_layers"], shape["num_key_value_heads"],
        shape["hidden_size"] // shape["num_attention_heads"], 1)  # fp8 KV
    # 2 FLOPs/param/token over non-embedding LOGICAL params (int4
    # packs two params per uint8 byte).
    active = (rl.logical_params(worker.params)
              - shape["vocab_size"] * shape["hidden_size"])
    model = rl.RooflineModel(
        weight_bytes=weight_bytes, active_params=active,
        kv_tok_bytes=kv_tok, device_kind=dev.device_kind)
    avg_ctx = prompt_len + output_len / 2
    best_rate = n_out / min(times) / n_chips
    steps_s = best_rate / n_req  # decode steps/sec (one token/req/step)
    extras: dict = {
        "model": f"llama-8B-{quant}-qembed-fp8kv",
        "batch": n_req,
        "weight_gib": round(weight_bytes / 2**30, 2),
        "hbm_bw_util_est": round(
            model.hbm_bw_util(steps_s, int(n_req * avg_ctx)), 3),
        "mfu_est": round(model.mfu(best_rate), 4),
    }
    if runner.timing.get("steps"):
        tm = dict(runner.timing)
        n = max(tm.pop("steps"), 1)
        extras["step_ms"] = {
            k: round(v / n * 1e3, 2) for k, v in tm.items()
        }
        extras["step_ms"]["wall"] = round(sum(times) / n * 1e3, 2)
    # Which decode path was scored, and (dynamic mode) the realized
    # per-launch step-length distribution: {realized K: launches},
    # read from the scheduler's cumulative histogram. A distribution
    # pinned at low K with distant stops means the loop exited on
    # budget/bounds, not stop tokens — a tuning signal, not a bug.
    extras["decode_mode"] = (
        "dynamic" if decode_steps_dynamic else f"fixed-{decode_steps_env}"
    )
    hist = dict(core.scheduler.decode_len_hist)
    if hist:
        launches = sum(hist.values())
        toks = sum(k * v for k, v in hist.items())
        extras["decode_steps_realized"] = {
            "launches": launches,
            "mean": round(toks / launches, 2),
            "hist": {str(k): v for k, v in sorted(hist.items())},
        }
    # Device-side attention/matmul/sampler split of one profiled
    # pass (same classifier as tools/profile_decode.py —
    # vllm_tpu/metrics/op_split.py). attn_ms_per_layer divides the
    # traced attention time over the pass's jitted-step launches and
    # layer count: the number the per-layer roofline argues about.
    if os.environ.get("VLLM_TPU_BENCH_OP_SPLIT", "1") != "0":
        from vllm_tpu.metrics.op_split import profile_op_split

        launches0 = runner.step_launches
        split = profile_op_split(lambda: llm.generate(prompts, params))
        if split is not None:
            extras["device_ms"] = split
            launches = runner.step_launches - launches0
            if launches > 0:
                extras["attn_ms_per_layer"] = round(
                    split["attention"] / launches
                    / shape["num_hidden_layers"], 4)
    # In-engine kernel A/B (perfwatch): the engine is idle here (scoring
    # passes done), so run the sampler-kernel / decode-attention /
    # dynamic-decode on-vs-off replay against the retained batch shape
    # and record the deltas (ab.dynamic_decode.device_ms_{on,off} +
    # delta_pct) next to the score they explain. An A/B that raises
    # fails the run; one the engine declines is recorded as such.
    if os.environ.get("VLLM_TPU_BENCH_AB", "1") != "0":
        ab = core.perf_ab({"steps": None})
        if ab and not ab.get("error") and not ab.get("aborted"):
            extras["ab"] = ab.get("ab")
            extras["ab_batch"] = ab.get("batch")
        elif ab:
            extras["ab_error"] = (
                ab.get("error") or ab.get("aborted_reason") or "aborted")

    print(json.dumps({
        "metric": "output_tokens_per_sec_per_chip",
        "value": rate(min(times)),
        "unit": "tok/s/chip",
        "vs_baseline": round(rate(min(times)) / BASELINE_TOK_S_PER_CHIP, 4),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": n_chips},
        "passes": passes,
        "median_value": rate(statistics.median(times)),
        "worst_pass_value": rate(max(times)),
        **({"goodput": goodput} if goodput is not None else {}),
        **extras,
    }))


if __name__ == "__main__":
    sys.exit(main())
