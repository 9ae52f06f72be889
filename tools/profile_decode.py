"""Op-level TPU profile of the 8B decode step (the bench workload).

Runs the bench engine briefly under jax.profiler, parses the xplane with
jax.profiler.ProfileData, and prints the top device ops by total time —
the ground truth for where the decode step goes — plus the
attention/matmul/sampler phase split (same classifier bench.py uses for
its JSON, vllm_tpu/metrics/op_split.py).

On CPU the engine runs a tiny model and the trace carries no device-op
line; the run still exercises the full path (tier-1 smoke coverage) and
prints the host-side step timing instead.
"""

from __future__ import annotations

import collections
import os
import sys
import tempfile

os.environ.setdefault("VLLM_TPU_LOG_LEVEL", "WARNING")
os.environ.setdefault("HF_HUB_OFFLINE", "1")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_llm():
    """The bench 8B-int8 engine on TPU; a tiny CPU-feasible engine
    elsewhere. Returns (llm, prompts, params, num_layers)."""
    import jax
    from transformers import LlamaConfig

    from vllm_tpu.entrypoints.llm import LLM
    from vllm_tpu.sampling_params import SamplingParams

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        shape = dict(
            hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32,
            num_key_value_heads=8, vocab_size=128256,
        )
        extra = dict(
            quantization="int8", quantize_embedding_layers=True,
            kv_cache_dtype="fp8", num_gpu_blocks_override=704,
        )
        n_req, prompt_len, out_len = 64, 32, 32
    else:
        shape = dict(
            hidden_size=128, intermediate_size=512, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4, vocab_size=1024,
        )
        extra = dict(num_gpu_blocks_override=512)
        n_req, prompt_len, out_len = 4, 8, 8
    cfg = LlamaConfig(
        max_position_embeddings=4096, tie_word_embeddings=False, **shape
    )
    cfg.architectures = ["LlamaForCausalLM"]
    llm = LLM(
        model="dummy-llama", hf_config=cfg, load_format="dummy",
        max_model_len=2048, max_num_batched_tokens=512,
        max_num_seqs=n_req, num_decode_steps=4, **extra,
    )
    prompts = [
        {"prompt_token_ids": [(7 * i + j) % 1000 for j in range(prompt_len)]}
        for i in range(n_req)
    ]
    params = SamplingParams(
        temperature=0.0, max_tokens=out_len, ignore_eos=True
    )
    return llm, prompts, params, shape["num_hidden_layers"]


def main() -> int:
    import jax

    from vllm_tpu.metrics.op_split import PHASES, classify_op, parse_trace

    llm, prompts, params, num_layers = build_llm()
    llm.generate(prompts, params)  # warmup/compile

    trace_dir = tempfile.mkdtemp(prefix="prof_decode_")
    jax.profiler.start_trace(trace_dir)
    llm.generate(prompts, params)
    jax.profiler.stop_trace()

    printed_ops = False
    for plane_name, lines in parse_trace(trace_dir):
        per_op: dict[str, float] = collections.defaultdict(float)
        per_op_n: dict[str, int] = collections.defaultdict(int)
        per_phase: dict[str, float] = collections.defaultdict(float)
        total = 0.0
        for line_name, events in lines:
            if "XLA Ops" not in line_name:
                continue
            for name, ns in events:
                # Collapse fused op instances: strip trailing .N ids.
                key = name.rstrip("0123456789").rstrip(".")
                per_op[key] += ns
                per_op_n[key] += 1
                per_phase[classify_op(name)] += ns
                total += ns
        if not per_op:
            continue
        printed_ops = True
        print(f"=== plane: {plane_name} ===")
        print(f"total device op time: {total / 1e6:.1f} ms")
        for phase in PHASES:
            ms = per_phase.get(phase, 0.0) / 1e6
            print(f"  {phase:10s} {ms:9.2f} ms "
                  f"({ms * 1e6 / max(total, 1) * 100:5.1f}%)")
        attn_ms = per_phase.get("attention", 0.0) / 1e6
        print(f"  attention/layer (trace total / {num_layers} layers): "
              f"{attn_ms / num_layers:.3f} ms")
        top = sorted(per_op.items(), key=lambda kv: -kv[1])[:30]
        for name, ns in top:
            print(
                f"{ns / 1e6:9.2f} ms  x{per_op_n[name]:<5d} "
                f"{name[:100]}"
            )
    if not printed_ops:
        # CPU backend: no device-op line; report host-side step timing.
        print("no device ops in trace (CPU backend?)")
        try:
            runner = (
                llm.llm_engine.engine_core.engine_core
                .executor.worker.runner
            )
            print("host step timing:", dict(runner.timing))
        except AttributeError:
            pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
