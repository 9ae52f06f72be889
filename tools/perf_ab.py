"""Quiet-window kernel A/B against a running (or in-proc) engine.

Two modes:

* HTTP (default): talk to a live server's perfwatch endpoints —
  ``POST /debug/perf/capture`` arms a capture or quiet-window A/B, then
  ``GET /debug/perf`` reports the device-time attribution and the
  per-kernel on/off deltas. The engine runs the replay itself during
  its next quiet window (or immediately with ``--force`` while idle);
  no external load generator, no manual kernel-flag flipping.

      python tools/perf_ab.py --url http://localhost:8000 --mode ab --wait 120

* ``--smoke``: build a tiny in-proc CPU engine, run one generate pass
  to retain a batch shape, execute the A/B synchronously, and validate
  the artifact schema. Tier-1 coverage for the whole replay path (on
  CPU the split is wall-clock-sourced; device_ms fields are null).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

os.environ.setdefault("VLLM_TPU_LOG_LEVEL", "WARNING")
os.environ.setdefault("HF_HUB_OFFLINE", "1")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _http_json(url: str, payload: dict | None = None) -> dict:
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(
        url, data=data,
        headers={"Content-Type": "application/json"} if data else {},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


def _print_ab(ab: dict) -> None:
    for kernel, d in sorted(ab.items()):
        src = d.get("source", "?")
        if src == "device":
            on, off = d.get("device_ms_on"), d.get("device_ms_off")
            delta = d.get("delta_pct")
        else:
            on, off = d.get("wall_ms_on"), d.get("wall_ms_off")
            delta = d.get("wall_delta_pct")
        sign = "" if delta is None or delta < 0 else "+"
        print(f"  {kernel:18s} on={on} ms  off={off} ms  "
              f"delta(off vs on)={sign}{delta}%  [{src}]")


def run_http(args: argparse.Namespace) -> int:
    base = args.url.rstrip("/")
    ack = _http_json(f"{base}/debug/perf/capture", {
        "mode": args.mode, "steps": args.steps, "force": args.force,
        "wait_s": 0,
    })
    print("armed:", json.dumps(ack.get("capture", ack)))
    deadline = time.monotonic() + args.wait
    status: dict = {}
    while time.monotonic() < deadline:
        status = _http_json(f"{base}/debug/perf")
        if not status.get("armed") and not status.get("capturing"):
            break
        time.sleep(1.0)
    print(json.dumps(status, indent=2))
    last_ab = status.get("last_ab")
    if last_ab and not last_ab.get("aborted") and last_ab.get("ab"):
        print("kernel A/B (per decode step):")
        _print_ab(last_ab["ab"])
        return 0
    cap = status.get("last_capture")
    if cap:
        print("last capture device_ms/step:", cap.get("device_ms_per_step"))
        return 0
    print("no capture landed before --wait expired (engine never went "
          "quiet? use --force)", file=sys.stderr)
    return 1


def run_smoke(base_only: bool = False) -> int:
    from transformers import LlamaConfig

    from vllm_tpu.entrypoints.llm import LLM
    from vllm_tpu.sampling_params import SamplingParams

    cfg = LlamaConfig(
        hidden_size=128, intermediate_size=512, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=4, vocab_size=1024,
        max_position_embeddings=2048, tie_word_embeddings=False,
    )
    cfg.architectures = ["LlamaForCausalLM"]
    llm = LLM(
        model="dummy-llama", hf_config=cfg, load_format="dummy",
        max_model_len=512, max_num_batched_tokens=256, max_num_seqs=4,
        num_gpu_blocks_override=512,
        # Multi-step on so the A/B also exercises its dynamic-decode
        # (device while_loop) on/off variant.
        num_decode_steps=4,
    )
    prompts = [
        {"prompt_token_ids": [(7 * i + j) % 1000 for j in range(8)]}
        for i in range(2)
    ]
    llm.generate(prompts, SamplingParams(temperature=0.0, max_tokens=4,
                                         ignore_eos=True))
    core = llm.llm_engine.engine_core.engine_core
    result = core.perf_ab({"steps": 2})
    print(json.dumps(result, indent=2))
    assert result.get("error") is None, result
    assert result["aborted"] is False, result
    ab = result["ab"]
    for kernel in ("sampler_kernel", "decode_attention", "dynamic_decode"):
        d = ab[kernel]
        for key in ("device_ms_on", "device_ms_off", "delta_pct",
                    "wall_ms_on", "wall_ms_off", "source"):
            assert key in d, (kernel, key, d)
        assert d["wall_ms_on"] is not None and d["wall_ms_on"] > 0, d
    status = core.perf_status()
    assert status["ab_runs_total"] >= 1, status

    # Second tiny engine for the adaptive-speculation variant: spec
    # decoding pins num_decode_steps=1 (so the dynamic-decode variant
    # can't ride the same engine), and the adaptive controller only
    # exists when --spec-adaptive is on.
    if base_only:
        print("perf_ab smoke ok (base only)")
        return 0
    llm2 = LLM(
        model="dummy-llama", hf_config=cfg, load_format="dummy",
        max_model_len=256, max_num_batched_tokens=128, max_num_seqs=2,
        num_gpu_blocks_override=512,
        speculative_method="ngram", num_speculative_tokens=3,
        spec_adaptive=True,
    )
    # Repetitive prompts so the ngram proposer actually drafts.
    spec_prompts = [
        {"prompt_token_ids": [5, 6, 7, 5, 6, 7, 5, 6]},
        {"prompt_token_ids": [9, 9, 9, 9, 9, 9, 9, 9]},
    ]
    llm2.generate(spec_prompts, SamplingParams(
        temperature=0.0, max_tokens=4, ignore_eos=True))
    core2 = llm2.llm_engine.engine_core.engine_core
    assert core2.scheduler.adaptive_spec is not None
    result2 = core2.perf_ab({"steps": 2})
    print(json.dumps(result2, indent=2))
    assert result2.get("error") is None, result2
    assert result2["aborted"] is False, result2
    d = result2["ab"]["adaptive_spec"]
    for key in ("device_ms_on", "device_ms_off", "delta_pct",
                "wall_ms_on", "wall_ms_off", "source"):
        assert key in d, ("adaptive_spec", key, d)
    assert d["wall_ms_on"] is not None and d["wall_ms_on"] > 0, d
    print("perf_ab smoke ok")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--url", default="http://localhost:8000",
                    help="server base URL (HTTP mode)")
    ap.add_argument("--mode", default="ab",
                    choices=["auto", "capture", "ab"],
                    help="what to arm: a profiling capture, the kernel "
                         "A/B, or whichever fits (auto)")
    ap.add_argument("--steps", type=int, default=None,
                    help="steps per profiled window (default: engine "
                         "config)")
    ap.add_argument("--force", action="store_true",
                    help="skip the quiet-window settle (run on the next "
                         "idle poll)")
    ap.add_argument("--wait", type=float, default=120.0,
                    help="seconds to wait for the window to land")
    ap.add_argument("--smoke", action="store_true",
                    help="in-proc tiny-engine self-test (no server)")
    ap.add_argument("--base-only", action="store_true",
                    help="with --smoke: skip the second (ngram + "
                         "adaptive-spec) engine — the fast CPU test "
                         "tier uses this; the full smoke covers both")
    args = ap.parse_args()
    if args.smoke:
        return run_smoke(base_only=args.base_only)
    return run_http(args)


if __name__ == "__main__":
    sys.exit(main())
