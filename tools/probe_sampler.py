"""Device-time probe for the fused sort-free sampling kernel
(``ops/sampler_kernel.py``) vs the XLA reference epilogue
(``sample/sampler.py:sample``).

On TPU: the benchmark's two sampling shapes, (512, 151936) and
(128, 32768), through ``dispatch_sample`` on both paths with the same
seeds — tokens equal, the drawn tokens' probabilities within 1e-6
relative, both times, and the kernel's time against the count of active
rows (one JSON line a point, and ``chiprun_out/probe_sampler.json``);
exits non-zero on a disagreement. This is the on-chip check of the
sampler that the benchmark's ``correct`` does not make. With ``--sweep``:
batch x vocab at serving shapes and, per point, the kernel's (row_block,
logits_tile) grid — wall-clock per call, the sweep that tunes the
dispatch defaults.

On CPU (or ``--smoke``): the kernel runs in Pallas interpret mode at a
tiny shape across the block-size sweep points and must be BIT-EXACT
against the reference (shared primitives) — numerics-only coverage that
``tests/metrics/test_decode_tools.py`` wires into tier-1.
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("VLLM_TPU_LOG_LEVEL", "WARNING")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _case(rng, rows, vocab):
    """A mixed sampling batch: every row non-greedy (the kernel's
    eligibility precondition), params spread over the feature surface."""
    from vllm_tpu.sample.sampler import SamplingMetadata

    logits = jnp.asarray(rng.standard_normal((rows, vocab)) * 3,
                         jnp.float32)
    md = SamplingMetadata(
        temperature=jnp.asarray(
            0.5 + 0.1 * (np.arange(rows) % 9), jnp.float32),
        top_k=jnp.asarray((np.arange(rows) % 4) * 10, jnp.int32),
        top_p=jnp.asarray(
            np.where(np.arange(rows) % 3 == 0, 0.9, 1.0), jnp.float32),
        min_p=jnp.asarray(
            np.where(np.arange(rows) % 5 == 0, 0.02, 0.0), jnp.float32),
        presence_penalty=jnp.zeros(rows, jnp.float32),
        frequency_penalty=jnp.zeros(rows, jnp.float32),
        repetition_penalty=jnp.ones(rows, jnp.float32),
        prng_keys=jnp.asarray(
            np.stack([np.arange(1, rows + 1),
                      np.arange(1001, rows + 1001)], axis=1), jnp.uint32),
        output_token_counts=jnp.zeros((1, 128), jnp.int32),
        prompt_token_mask=jnp.zeros((1, 128), jnp.bool_),
    )
    return logits, md


def _pack_params(md):
    """SamplingMetadata -> the kernel's [R, 128] param blocks (mirrors
    ``dispatch_sample``)."""
    params_f = jnp.pad(
        jnp.stack([md.temperature, md.top_p, md.min_p,
                   md.repetition_penalty, md.frequency_penalty,
                   md.presence_penalty], axis=1),
        ((0, 0), (0, 122)))
    keys_i = jax.lax.bitcast_convert_type(
        md.prng_keys.astype(jnp.uint32), jnp.int32)
    params_i = jnp.pad(
        jnp.stack([md.top_k.astype(jnp.int32), keys_i[:, 0],
                   keys_i[:, 1]], axis=1),
        ((0, 0), (0, 125)))
    return params_f, params_i


def _bench(name, f, logits, md):
    out = f(logits, md)
    out.block_until_ready()
    best = float("inf")
    for _ in range(7):
        t0 = time.monotonic()
        f(logits, md).block_until_ready()
        best = min(best, time.monotonic() - t0)
    print(f"{name:36s} {best * 1e6:9.1f} us/call")
    return out, best


def _per_call(f, *args, calls=20):
    """Seconds a call, the device kept busy: ``calls`` enqueued back to
    back, one wait at the end."""
    jax.block_until_ready(f(*args))
    t0 = time.monotonic()
    for _ in range(calls):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.monotonic() - t0) / calls, out


# The benchmark's two sampling shapes: sdar30b.chat's block pass (request
# bucket 128 x 4 positions, the Qwen family's vocabulary, the drawn token's
# probability returned) and mistral7b.chat's decode step. With each, the
# counts of rows somebody reads that the cells' steps show.
POINTS = ((512, 151936, (512, 256, 192, 128, 96, 64, 8)),
          (128, 32768, (128, 64, 24, 8)))


def tpu_points(out_path="chiprun_out/probe_sampler.json"):
    """Kernel against the XLA epilogue on the chip, as the step programs
    call them (top-p on, no top-k, no penalties, the probability returned):
    tokens equal, probabilities within 1e-6 relative, and both times; then
    the kernel's time against the count of active rows."""
    import json

    from vllm_tpu.sample.sampler import dispatch_sample

    print("device:", jax.devices()[0])
    rng = np.random.default_rng(0)
    flags = dict(needs_top_k=False, needs_top_p_min_p=True, return_prob=True)
    rows_out, bad = [], 0
    for rows, vocab, actives in POINTS:
        logits, md = _case(rng, rows, vocab)

        def path(enable_kernel):
            def f(logits, md, n):
                tok, _, prob = dispatch_sample(
                    logits, md, enable_kernel=enable_kernel, num_active=n,
                    **flags)
                return tok, prob
            return jax.jit(f)

        n_all = jnp.asarray(rows, jnp.int32)
        t_xla, (tok_x, prob_x) = _per_call(path(False), logits, md, n_all)
        kern = path(True)
        t_ker, (tok_k, prob_k) = _per_call(kern, logits, md, n_all)
        tok_x, tok_k = np.asarray(tok_x), np.asarray(tok_k)
        prob_x, prob_k = np.asarray(prob_x), np.asarray(prob_k)
        rel = float(np.max(np.abs(prob_k - prob_x) / prob_x))
        same = int(np.sum(tok_x == tok_k))
        ok = same == rows and rel <= 1e-6
        bad += not ok
        by_active = {}
        for n in actives:
            t, (tok_n, _) = _per_call(kern, logits, md, jnp.asarray(n, jnp.int32))
            tok_n = np.asarray(tok_n)
            cut_ok = (np.array_equal(tok_n[:n], tok_k[:n])
                      and not tok_n[n:].any())
            bad += not cut_ok
            by_active[n] = round(t * 1e3, 4)
        row = dict(rows=rows, vocab=vocab, xla_ms=round(t_xla * 1e3, 4),
                   kernel_ms=round(t_ker * 1e3, 4), tokens_equal=same,
                   prob_max_rel_diff=rel, kernel_ms_by_active_rows=by_active,
                   ok=bool(ok))
        rows_out.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(dict(device=str(jax.devices()[0]), points=rows_out), f,
                  indent=1)
    if bad:
        raise SystemExit(f"kernel and XLA epilogue disagree at {bad} points")


def tpu_sweep():
    import functools

    from vllm_tpu.ops.sampler_kernel import fused_sample
    from vllm_tpu.sample.sampler import sample

    print("device:", jax.devices()[0])
    rng = np.random.default_rng(0)
    # Serving shapes: decode batch x lm_head vocab (Llama-3 128256 pads
    # to 128k lanes; 32000 covers Llama-2-class heads).
    for rows in (16, 64, 256):
        for vocab in (32000, 128256):
            logits, md = _case(rng, rows, vocab)

            @jax.jit
            def ref_fn(logits, md):
                return sample(logits, md)[0]

            ref, t_ref = _bench(
                f"xla ref  R={rows} V={vocab}", ref_fn, logits, md)

            params_f, params_i = _pack_params(md)
            for row_block in (2, 4, 8):
                for tile in (1024, 2048, 4096):

                    @functools.partial(jax.jit, static_argnames=())
                    def kern_fn(logits, md, _rb=row_block, _tl=tile):
                        return fused_sample(
                            logits, params_f, params_i,
                            md.output_token_counts.astype(jnp.int32),
                            md.prompt_token_mask.astype(jnp.int8),
                            needs_penalties=False, needs_top_k=True,
                            needs_top_p_min_p=True,
                            row_block=_rb, logits_tile=_tl,
                        )

                    try:
                        got, t = _bench(
                            f"kernel rb={row_block} tile={tile}",
                            kern_fn, logits, md)
                        match = bool(jnp.all(got == ref))
                        print(f"    vs ref: {t_ref / t:5.2f}x   "
                              f"tokens {'MATCH' if match else 'DIFFER'}")
                    except Exception as e:  # noqa: BLE001
                        print(f"    rb={row_block} tile={tile} failed: "
                              f"{type(e).__name__}: {str(e)[:120]}")


def smoke_sweep():
    """CPU: interpret-mode kernel vs the XLA reference — bit-exact across
    block-shape sweep points on an odd vocab."""
    from vllm_tpu.ops.sampler_kernel import fused_sample
    from vllm_tpu.sample.sampler import sample

    rows, vocab = 5, 333
    rng = np.random.default_rng(0)
    logits, md = _case(rng, rows, vocab)
    print("device:", jax.devices()[0], "(interpret-mode smoke)")
    want = np.asarray(sample(logits, md)[0])
    params_f, params_i = _pack_params(md)

    bad = 0
    for row_block in (2, 3):
        for tile in (128, 256):
            got = np.asarray(fused_sample(
                logits, params_f, params_i,
                md.output_token_counts.astype(jnp.int32),
                md.prompt_token_mask.astype(jnp.int8),
                needs_penalties=False, needs_top_k=True,
                needs_top_p_min_p=True,
                row_block=row_block, logits_tile=tile, interpret=True,
            ))
            match = np.array_equal(got, want)
            bad += not match
            print(f"kernel rb={row_block} tile={tile}  "
                  f"{'MATCH' if match else 'MISMATCH'}")
    if bad:
        raise SystemExit(f"sampler kernel smoke mismatch at {bad} points")
    print("smoke sweep ok")


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if "--smoke" in argv or jax.default_backend() != "tpu":
        smoke_sweep()
    elif "--sweep" in argv:
        tpu_sweep()
    else:
        tpu_points()
    return 0


if __name__ == "__main__":
    sys.exit(main())
