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



def compare(config: dict, family_module, seed: int, samples: list[dict],
            dtype: str = "bfloat16", factors: bool = False) -> dict:
    """The served logprobs against the reference's. With ``factors`` the
    reference runs a second time in the served type (``compute=dtype``,
    where the family's reference has such a pass) and each error is also
    given as a factor over that pass's own distance from the float32 one,
    at the same tokens: what the seed's weights make of rounding divides
    out, what a lower precision adds stays."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    weights = family_module.seeded_weights(config, seed % 2**32, jnp.dtype(dtype))
    jax.block_until_ready(weights)
    print(f"checker: weights from the seed in {time.perf_counter() - t0:.1f} s", flush=True)
    sequences = [s["prompt"] + s["tokens"][:-1] for s in samples]
    # A sequence's numbers in one flat run: (generated position, token id,
    # served logprob) for each of a position's top tokens.
    where = [(np.asarray([j for j, top in enumerate(s["top_logprobs"]) for _ in top]),
              np.asarray([int(t) for top in s["top_logprobs"] for t in top]))
             for s in samples]
    served = [np.asarray([v for top in s["top_logprobs"] for v in top.values()], np.float64)
              for s in samples]

    def at_served(all_logits) -> tuple[list, int]:
        """The pass's logprobs at the tokens the server reported, a sequence
        a run like ``served``'s, and how often its best token is another."""
        out, flips = [], 0
        for s, (pos, ids), logits in zip(samples, where, all_logits):
            rows = logits[len(s["prompt"]) - 1:]
            logp = np.asarray(jax.nn.log_softmax(rows, axis=-1), np.float32)
            out.append(logp[pos, ids].astype(np.float64))
            flips += int(np.sum(np.asarray(jnp.argmax(rows, axis=-1)) != np.asarray(s["tokens"])))
        return out, flips

    t0 = time.perf_counter()
    ref, flips = at_served(family_module.forward_many(config, weights, sequences))
    by_seq = [np.abs(r - v) for r, v in zip(ref, served)]
    print(f"checker: {len(samples)} sequences through the reference and compared in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    diffs_a = np.concatenate(by_seq)
    per_sample = [{"positions": len(s["tokens"]), "prompt": len(s["prompt"]),
                   "mean_abs": float(d.mean()), "max_abs": float(d.max())}
                  for s, d in zip(samples, by_seq)]
    out = {
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
    if factors:
        t0 = time.perf_counter()
        low, _ = at_served(family_module.forward_many(
            config, weights, sequences, compute=jnp.dtype(dtype)))
        low_seq = [np.abs(a - r) for a, r in zip(low, ref)]
        low_a = np.concatenate(low_seq)
        print(f"checker: the reference again in {dtype}, {time.perf_counter() - t0:.1f} s",
              flush=True)
        for p, d in zip(per_sample, low_seq):
            p["lower_mean_abs"] = float(d.mean())
        out.update(
            lower_mean_abs=float(low_a.mean()),
            lower_p50_abs=float(np.percentile(low_a, 50)),
            lower_p90_abs=float(np.percentile(low_a, 90)),
            mean_factor=float(diffs_a.mean() / low_a.mean()),
            p50_factor=float(np.percentile(diffs_a, 50) / np.percentile(low_a, 50)),
            p90_factor=float(np.percentile(diffs_a, 90) / np.percentile(low_a, 90)),
            worst_sequence_factor=max(p["mean_abs"] / p["lower_mean_abs"] for p in per_sample),
        )
    return out


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
    out = compare(job["config"], family, job["seed"], job["samples"],
                  job.get("dtype", "bfloat16"), job.get("factors", False))
    out["device"] = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                     "count": len(devs)}
    with open(dst, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
