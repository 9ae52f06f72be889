"""The process that decides ``correct``. It runs after the server has
exited and freed the chip.

It reads the greedy answers the served path gave (token ids and the top
logprobs of each generated position), makes the configuration's weights
again from the seed with the reference's own ``seeded_weights``, runs the
plain float32 reference over prompt plus answer, and compares logprobs
position by position: for generated token j the reference's log-softmax at
position ``len(prompt) + j - 1``, at the token ids the server reported.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np



def compare(config: dict, family_module, seed: int, samples: list[dict], dtype: str = "bfloat16") -> dict:
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    weights = family_module.seeded_weights(config, seed % 2**32, jnp.dtype(dtype))
    jax.block_until_ready(weights)
    print(f"checker: weights from the seed in {time.perf_counter() - t0:.1f} s", flush=True)
    diffs, per_sample, flips = [], [], 0
    t0 = time.perf_counter()
    all_logits = family_module.forward_many(
        config, weights, [s["prompt"] + s["tokens"][:-1] for s in samples])
    for s, logits in zip(samples, all_logits):
        prompt, answer = s["prompt"], s["tokens"]
        rows = logits[len(prompt) - 1:]
        logp = np.asarray(jax.nn.log_softmax(rows, axis=-1), np.float32)
        ref_top = np.asarray(jnp.argmax(rows, axis=-1))
        d = []
        for j, top in enumerate(s["top_logprobs"]):
            for tok, served in top.items():
                d.append(abs(float(logp[j, int(tok)]) - float(served)))
            flips += int(ref_top[j] != answer[j])
        per_sample.append({"positions": len(answer), "prompt": len(prompt),
                           "mean_abs": float(np.mean(d)), "max_abs": float(np.max(d))})
        diffs += d
    print(f"checker: {len(samples)} sequences through the reference and compared in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    diffs_a = np.asarray(diffs)
    return {
        "compared": int(diffs_a.size),
        "positions": int(sum(len(s["tokens"]) for s in samples)),
        "mean_abs": float(diffs_a.mean()),
        "p50_abs": float(np.percentile(diffs_a, 50)),
        "p90_abs": float(np.percentile(diffs_a, 90)),
        "p99_abs": float(np.percentile(diffs_a, 99)),
        "max_abs": float(diffs_a.max()),
        "worst_sequence_mean_abs": max(p["mean_abs"] for p in per_sample),
        "argmax_differs": int(flips),
        "per_sample": per_sample,
    }


def main(argv: list[str]) -> int:
    src, dst = argv
    with open(src) as f:
        job = json.load(f)
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.dirname(os.path.dirname(here)))
    import jax

    from benchmark.harness.manifest import BENCH_DIR, load_module
    from benchmark.harness.server import NO_CHIP

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < job["chips"]:
        print(f"checker: need {job['chips']} TPU chip(s), JAX has {devs}", flush=True)
        return NO_CHIP
    family = load_module(BENCH_DIR / "reference" / f"{job['family']}.py")
    out = compare(job["config"], family, job["seed"], job["samples"], job.get("dtype", "bfloat16"))
    out["device"] = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                     "count": len(devs)}
    with open(dst, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
