"""Device-time probe of the ragged attention kernel (``ops/rpa_kernel.py``)
alone on the chip, at the benchmark cells' mixed-step shapes: Mistral's 32
q / 8 KV heads with 18 decode rows beside a 490-token chunk at page buckets
64 and 512 (and the rows and the chunk each alone), SDAR's 32 / 4 under
``block_len`` 4 with 16 rows of 4 tokens beside a 400-token chunk. Each
case: outputs against ``ref_ragged_paged_attention`` (on the CPU backend),
ms for 16 calls in one program (a step's layers), the host's count of live
over computed pairs; the same for every ``--variant PAGESxQUERIES[@MIB]``
and for the kernel of ``--parent DIR`` (an unpacked parent commit). One
JSON line a point; exits non-zero on a disagreement. Without a TPU: one
tiny case in interpret mode.
"""

import argparse
import functools
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from vllm_tpu.ops import rpa_kernel
from vllm_tpu.ops.attention import AttentionMetadata, ref_ragged_paged_attention

LAYERS, PAGE, D = 16, 16, 128


def _case(rng, name, kh, q_lens, kv_lens, pages_per_seq, rows, tokens, block_len=1):
    n = len(q_lens)
    need = [-(-kv // PAGE) for kv in kv_lens]
    table = np.zeros((rows, pages_per_seq), np.int32)
    table[:n] = [np.pad(1 + sum(need[:i]) + np.arange(need[i]), (0, pages_per_seq - need[i]))
                 for i in range(n)]
    kv = jnp.asarray(rng.standard_normal((2, 1 + sum(need), PAGE, 2 * kh, D)), jnp.bfloat16)
    pad = lambda a, m: jnp.asarray(np.pad(np.asarray(a, np.int32), (0, m - len(a))))
    cu = np.concatenate([[0], np.cumsum(q_lens)])
    return dict(
        name=name, block_len=block_len, q_lens=q_lens, kv_lens=kv_lens, kv=kv,
        q=jnp.asarray(rng.standard_normal((tokens, 32, D)), jnp.bfloat16),
        kv_lens_a=pad(kv_lens, rows), table=jnp.asarray(table),
        cu=jnp.asarray(np.pad(cu, (0, rows - n), mode="edge").astype(np.int32)),
        num_seqs=jnp.asarray([n], jnp.int32))


def cases(rng, smoke):
    if smoke:
        return [_case(rng, "smoke", 2, [1, 1, 4, 58], [40, 300, 16, 200], 32, 8, 64, 1)]
    dec = lambda hi: [int(x) for x in rng.integers(200, hi, 18)]
    return [
        _case(rng, "mistral.p64", 8, [1] * 18 + [490], dec(1000) + [490], 64, 128, 512),
        _case(rng, "mistral.p512", 8, [1] * 18 + [490], dec(1500) + [2000], 512, 128, 512),
        _case(rng, "mistral.p512.rows", 8, [1] * 18, dec(1500), 512, 128, 512),
        _case(rng, "mistral.p512.chunk", 8, [490], [2000], 512, 128, 512),
        _case(rng, "sdar.p128", 4, [4] * 16 + [400], [4 * int(x) for x in rng.integers(50, 375, 16)]
              + [1200], 128, 128, 512, block_len=4),
    ]


def reference(c):
    """ref_ragged_paged_attention on the CPU backend, the table cut to the pages in use."""
    n, t = len(c["q_lens"]), sum(c["q_lens"])
    req = np.repeat(np.arange(n), c["q_lens"])
    pos = np.concatenate([np.arange(kv - ql, kv) for ql, kv in zip(c["q_lens"], c["kv_lens"])])
    width = -(-max(c["kv_lens"]) // PAGE)
    cpu = lambda a: jnp.asarray(np.asarray(a))
    with jax.default_device(jax.devices("cpu")[0]):
        md = AttentionMetadata(
            positions=cpu(pos.astype(np.int32)), slot_mapping=cpu(np.zeros(t, np.int32)),
            block_tables=cpu(np.asarray(c["table"])[:n, :width]), seq_lens=cpu(c["kv_lens_a"][:n]),
            query_start_loc=cpu(c["cu"][: n + 1]), token_req_idx=cpu(req.astype(np.int32)),
            logits_indices=cpu(np.zeros(n, np.int32)), num_seqs=cpu(c["num_seqs"]))
        return np.asarray(ref_ragged_paged_attention(
            cpu(np.asarray(c["q"][:t], np.float32)), cpu(np.asarray(c["kv"], np.float32)),
            jnp.int32(1), md, D**-0.5, block_len=c["block_len"]), np.float32)


def run(fn, c, interpret, **blocks):
    kernel = functools.partial(fn, sm_scale=D**-0.5, block_len=c["block_len"],
                               interpret=interpret, **blocks)
    args = (c["kv"], c["kv_lens_a"], c["table"], c["cu"], c["num_seqs"])
    @jax.jit
    def step(q, *rest):  # 16 calls in one program, as a step's layers
        def layer(i, acc):
            return acc + kernel(q, rest[0], jnp.reshape(i % 2, (1,)), *rest[1:]).astype(jnp.float32)
        return jax.lax.fori_loop(0, LAYERS, layer, jnp.zeros(q.shape, jnp.float32))

    out = np.asarray(kernel(c["q"], c["kv"], jnp.asarray([1], jnp.int32), *args[1:]), np.float32)
    if interpret:
        return out, None
    step(c["q"], *args).block_until_ready()
    times = []
    for _ in range(5):  # the least of five timings of four steps
        t0 = time.perf_counter()
        for _ in range(4):
            done = step(c["q"], *args)
        done.block_until_ready()
        times.append((time.perf_counter() - t0) / 4 * 1e3)
    return out, min(times)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent")
    ap.add_argument("--variant", action="append", default=[])
    a = ap.parse_args()
    smoke = jax.default_backend() != "tpu"
    small = dict(num_kv_pages_per_block=8, num_queries_per_block=16) if smoke else {}
    kernels = {"change": (rpa_kernel.ragged_paged_attention, small)}
    if a.parent:
        spec = importlib.util.spec_from_file_location(
            "parent_rpa_kernel", os.path.join(a.parent, "vllm_tpu/ops/rpa_kernel.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        kernels["parent"] = (parent.ragged_paged_attention, small)
    for v in a.variant:
        shape, _, mib = v.partition("@")
        pages, queries = (int(x) for x in shape.split("x"))
        kernels[v] = (rpa_kernel.ragged_paged_attention, dict(
            num_kv_pages_per_block=pages, num_queries_per_block=queries,
            vmem_limit_bytes=int(mib) << 20 if mib else None))
    bad = 0
    for c in cases(np.random.default_rng(0), smoke):
        want = reference(c)
        t = want.shape[0]
        for name, (fn, blocks) in kernels.items():
            got, ms = run(fn, c, smoke, **blocks)
            err = float(np.max(np.abs(got[:t] - want)))
            point = dict(case=c["name"], kernel=name, ms_16_calls=ms, max_abs_err=err,
                         device=jax.devices()[0].device_kind)
            if name != "parent":
                tiling = rpa_kernel.ragged_tiling(
                    32, c["kv"].shape[3], D, PAGE, jnp.bfloat16, jnp.bfloat16, c["q"].shape[0],
                    c["table"].shape[1], block_len=c["block_len"], **blocks)
                live, computed = rpa_kernel.count_ragged_pairs(
                    c["q_lens"], c["kv_lens"], tiling, page_size=PAGE, block_len=c["block_len"])
                point.update(tiling=list(tiling), live_share=100.0 * live / computed)
            bad += err > 3e-2
            print(json.dumps(point), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
