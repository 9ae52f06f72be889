"""Device-time probe of the latent attention kernel (``ops/mla_kernel.py``)
alone on the chip, at the shapes of ``kimilinear48b.long-answers``: 32
heads over latent rows of 640 lanes, 192 rows a step, 768 pages a row.
Cases: 130 decode rows of context 500-4000 (a decode-only step), and the
same rows beside a 382-token chunk at context 2000 (a mixed step). Each
case at every ``--blocks QUERIESxPAGES`` (the kernel's own choice first):
outputs against the first, ms for 3 calls in one program (a step's latent
layers), the least of five timings, and the host's count of live over
computed pairs. One JSON line a point. Without a TPU: one tiny case, the
kernel interpreted.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from vllm_tpu.ops import mla_kernel

PAGE, LANES, VALUE, HEADS, LAYERS = 16, 640, 512, 32, 3


def case(rng, name, q_lens, kv_lens, tokens, rows, pages_per_seq, heads):
    n = len(q_lens)
    need = [-(-kv // PAGE) for kv in kv_lens]
    table = np.zeros((rows, pages_per_seq), np.int32)
    for i in range(n):
        table[i, : need[i]] = 1 + sum(need[:i]) + np.arange(need[i])
    cu = np.concatenate([[0], np.cumsum(q_lens)])
    pad = lambda a, m: jnp.asarray(np.pad(np.asarray(a, np.int32), (0, m - len(a))))
    return dict(
        name=name, q_lens=q_lens, kv_lens=kv_lens, tokens=tokens,
        q=jnp.asarray(rng.standard_normal((tokens, heads, LANES)) * 0.1, jnp.bfloat16),
        cache=jnp.asarray(rng.standard_normal((LAYERS, 1 + sum(need), PAGE, LANES)), jnp.bfloat16),
        kv=pad(kv_lens, rows), table=jnp.asarray(table),
        cu=jnp.asarray(np.pad(cu, (0, rows - n), mode="edge").astype(np.int32)),
        n=jnp.asarray([n], jnp.int32))


def run(c, blocks, interpret):
    kw = dict(num_queries_per_block=blocks[0], num_kv_pages_per_block=blocks[1]) if blocks else {}

    @jax.jit
    def layers(q, cache, kv, table, cu, n):
        def one(_, layer):
            return None, mla_kernel.mla_ragged_paged_attention(
                q, cache, layer.reshape(1), kv, table, cu, n, sm_scale=192 ** -0.5,
                value_dim=VALUE, interpret=interpret, **kw)
        return jax.lax.scan(one, None, jnp.arange(LAYERS, dtype=jnp.int32))[1]

    args = (c["q"], c["cache"], c["kv"], c["table"], c["cu"], c["n"])
    out = jax.block_until_ready(layers(*args))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(layers(*args))
        best = min(best, time.perf_counter() - t0)
    return np.asarray(out, np.float32)[:, : sum(c["q_lens"])], 1e3 * best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", action="append", default=[])
    a = ap.parse_args()
    smoke = jax.default_backend() != "tpu"
    rng = np.random.default_rng(0)
    if smoke:
        cases = [case(rng, "smoke", [1, 1, 20], [40, 300, 90], 32, 8, 32, 4)]
    else:
        ctx = [int(x) for x in rng.integers(500, 4000, 130)]
        cases = [case(rng, "decode.130", [1] * 130, ctx, 192, 192, 768, HEADS),
                 case(rng, "mixed.130+382", [1] * 130 + [382], ctx + [2000], 512, 192, 768, HEADS)]
    bad = 0
    for c in cases:
        want = None
        for spec in [None, *a.blocks]:
            blocks = tuple(int(x) for x in spec.split("x")) if spec else None
            got, ms = run(c, blocks, smoke)
            want = got if want is None else want
            err = float(np.max(np.abs(got - want)))
            q_blk, pages = blocks or mla_kernel.latent_blocks(
                c["q"].shape[1], c["tokens"], c["table"].shape[1], PAGE)
            live, computed = mla_kernel.count_latent_pairs(
                c["q_lens"], c["kv_lens"], min(q_blk, c["tokens"]), pages * PAGE)
            bad += err > 3e-2
            print(json.dumps(dict(
                case=c["name"], blocks=spec or "own", ms_3_layers=ms, max_abs_diff=err,
                live_share=100.0 * live / computed, device=jax.devices()[0].device_kind)),
                flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
