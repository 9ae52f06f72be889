"""Device-time probe of the grouped matmul of the experts alone on the chip
(``ops/gmm_kernel.py`` against megablox ``gmm`` as the parent called it), at
the shapes of the five cells that run experts. A case is one matrix shape
``[k, n]`` of a configuration under one step's ``group_sizes``, run as the
layer scan runs it: ``layers`` calls in one program over the ``[L * E]``
stack, call ``l`` with only layer ``l``'s groups filled.

Group sizes: ``step`` = a step's rows dealt over the groups hit (a
Dirichlet draw, every hit group at least one row; ``mimo.step.17`` is a
decode-only step, every group in one row tile); ``aligned`` = every hit
group exactly 128 rows (no visit is resident: what megablox reaches when no
group straddles a row tile); ``one`` = all rows in one group (every visit
but the first resident: the matmul's own time a visit).

A variant: ``megablox`` at the tiles the parent gave it (``parent_tiling``,
``layers/moe.py::_gmm_tiling`` as PR 53 left it), or the new kernel at a column tile and a number of slots (``new.<tn>x<slots>``;
``new`` alone = as ``layers/moe.py`` calls it). Each: the least of five
timings of the chain by the host's clock (``chain_us``, a call's share,
with the metadata's XLA operations), the device time of the ``gmm`` events
of one traced chain (``kernel_us`` a call, what the benchmark's readers
sum), the bytes' time of the blocks hit at the chip's bandwidth
(``benchmark/peaks.json``) and the share of it the kernel reaches
(``roofline``). Outputs are checked against megablox's on the rows of the
groups. One JSON line a variant; exits non-zero on a disagreement.

``python tools/probe_moe.py`` runs every case with ``megablox`` and ``new``;
``--sweep`` adds every column tile and 2 to 4 slots on the ``step`` cases;
``--cases lfm2,sdar`` keeps the cases whose name starts so. Without a TPU:
one tiny case, the kernels interpreted, no times.
"""

import argparse
import functools
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox import gmm as megablox_gmm

from benchmark.harness import trace_reduce
from vllm_tpu.layers import moe
from vllm_tpu.ops import gmm_kernel

ON_TPU = jax.default_backend() == "tpu"


def parent_tiling(k, n):
    """``layers/moe.py::_gmm_tiling`` as PR 53 left it, what megablox ran
    at in the cells: a depth of at most 2048 a tile, blocks of 4 MiB."""
    def tile(dim, cap):
        if dim <= cap:
            return dim
        return next((t for t in range(cap // 128 * 128, 0, -128) if dim % t == 0), 128)

    if k % 128 or n % 128:
        return (128, 128, 128)
    tk = tile(k, 2048)
    return (128, tk, tile(n, max(128, (4 << 20) // (2 * tk))))


def dealt(rng, rows, groups, hit):
    """``rows`` over ``hit`` of ``groups`` groups, each hit group a row at
    least, the rest by a Dirichlet draw (a router's skew)."""
    sizes = np.zeros(groups, np.int64)
    which = np.sort(rng.permutation(groups)[:hit])
    sizes[which] = 1 + rng.multinomial(rows - hit, rng.dirichlet(np.full(hit, 2.0)))
    return sizes


def cases(rng, smoke):
    """``(name, experts, layers, m, sizes [experts], shapes)``."""
    if smoke:
        yield "smoke.step", 4, 2, 256, dealt(rng, 200, 4, 3), {"gate": (128, 256)}
        return
    lfm2 = {"gate": (2048, 1792), "down": (1792, 2048)}
    yield "lfm2.step.1088", 32, 14, 2048, dealt(rng, 1088, 32, 32), lfm2
    yield "lfm2.step.2048", 32, 14, 2048, dealt(rng, 2048, 32, 32), lfm2
    yield "lfm2.aligned", 32, 14, 4096, np.full(32, 128), lfm2
    yield "lfm2.one", 32, 14, 2048, np.eye(32, dtype=np.int64)[5] * 2048, lfm2
    sdar = {"gate": (2048, 768), "down": (768, 2048)}
    yield "sdar.step.1120", 128, 7, 4096, dealt(rng, 1120, 128, 94), sdar
    hit = np.zeros(128, np.int64)
    hit[np.sort(rng.permutation(128)[:94])] = 128
    yield "sdar.aligned", 128, 7, 94 * 128, hit, sdar
    share = dealt(rng, 512, 32, 32)
    yield "kimi.step.512", 32, 12, 4096, share, {"gate": (2304, 1024), "down": (1024, 2304)}
    yield "laguna.step.512", 32, 39, 4096, share, {"gate": (2048, 512), "down": (512, 2048)}
    mimo = {"gate": (4096, 2048), "down": (2048, 4096)}
    yield "mimo.step.512", 16, 9, 4096, dealt(rng, 512, 16, 16), mimo
    # A decode-only step of the same share: 35 rows' pairs, a sixteenth
    # of them held here, on 10 of the 16 experts, all in one row tile.
    yield "mimo.step.17", 16, 9, 1024, dealt(rng, 17, 16, 10), mimo


def variants(k, n, sweep):
    """``name -> call(lhs, rhs, sizes)``; ``megablox`` first (the oracle)."""
    interpret = not ON_TPU
    out = {
        "megablox": functools.partial(
            megablox_gmm, preferred_element_type=jnp.float32,
            tiling=parent_tiling(k, n), interpret=interpret),
        "new": functools.partial(moe._gmm, interpret=interpret),
    }
    if sweep:
        tk = parent_tiling(k, n)[1]
        for tn in range(n, 127, -128):
            block = 2 * k * tn
            if n % tn or not gmm_kernel.takes(k, n, tk, tn) or not (
                    (1 << 20) <= block <= (8 << 20) or tn == n):
                continue
            for slots in (2, 3, 4):
                if gmm_kernel.vmem_bytes(k, tn, slots) <= (48 << 20):
                    out[f"new.{tn}x{slots}"] = functools.partial(
                        gmm_kernel.gmm, tk=tk, tn=tn, slots=slots, interpret=interpret)
    return out


def chain(call, layers, experts):
    """``layers`` calls in one program, call ``l`` on layer ``l``'s groups
    of the stack; returns the first call's output and a scalar that needs
    every call."""

    @jax.jit
    def run(lhs, rhs, sizes):
        def one(carry, layer):
            stack = jnp.zeros((layers, experts), jnp.int32).at[layer].set(sizes)
            out = call(lhs, rhs, stack.reshape(-1))
            return carry + out[0, :128], jnp.where(layer == 0, out[:, :128], 0.0)

        carry, outs = jax.lax.scan(
            one, jnp.zeros(128, jnp.float32), jnp.arange(layers, dtype=jnp.int32))
        return carry, outs[0]

    return run


def least_us(step, calls=4):
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        outs = [step() for _ in range(calls)]
        jax.block_until_ready(outs)
        best = min(best, (time.perf_counter() - t0) / calls)
    return 1e6 * best


def kernel_us(step):
    """Device time of the ``gmm`` events of one traced run, and how many."""
    where = tempfile.mkdtemp(prefix="probe_moe_")
    try:
        with jax.profiler.trace(where):
            jax.block_until_ready(step())
        trace = trace_reduce.reduce_planes(trace_reduce.read_planes(where))
    finally:
        shutil.rmtree(where, ignore_errors=True)
    return 1e6 * trace["kernel_seconds"].get("gmm", 0.0), trace["kernel_calls"].get("gmm", 0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--cases", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    bandwidth = None
    if ON_TPU:
        bandwidth = peaks["device_kinds"][jax.devices()[0].device_kind]["hbm_bytes_per_s"]
    rng = np.random.default_rng(args.seed)
    keep = tuple(p for p in args.cases.split(",") if p)
    bad = 0
    for name, experts, layers, m, sizes, shapes in cases(rng, not ON_TPU):
        if keep and not name.startswith(keep):
            continue
        live = int(sizes.sum())
        hit = int(np.count_nonzero(sizes))
        for which, (k, n) in shapes.items():
            lhs, rhs = jax.jit(lambda key: (
                jax.random.normal(key, (m, k), jnp.bfloat16),
                (jax.random.normal(key, (layers * experts, k, n)) * k ** -0.5
                 ).astype(jnp.bfloat16)))(jax.random.PRNGKey(args.seed))
            gs = jnp.asarray(sizes, jnp.int32)
            bytes_us = None if bandwidth is None else 1e6 * hit * k * n * 2 / bandwidth
            want = step = None
            for vname, call in variants(k, n, args.sweep and ".step" in name).items():
                line = dict(case=name, matrix=which, k=k, n=n, rows=live, m=m,
                            groups_hit=hit, variant=vname)
                _, tk, tn = (parent_tiling if vname == "megablox" else moe._gmm_tiling)(k, n)
                if "." in vname:
                    tn = int(vname.split(".")[1].split("x")[0])
                line.update(tk=tk, tn=tn)
                first, resident = gmm_kernel.count_visits(sizes, n // tn)
                line.update(visits_first=first, visits_resident=resident)
                try:
                    step = functools.partial(chain(call, layers, experts), lhs, rhs, gs)
                    _, got = jax.block_until_ready(step())
                    got = np.asarray(got)[:live]
                    if want is None:
                        want = got
                    err = float(np.abs(got - want).max())
                    line["max_diff"] = err
                    line["equal"] = bool(np.array_equal(got, want))
                    if not err <= 2e-2 * max(1.0, float(np.abs(want).max())):
                        bad += 1
                        line["disagrees"] = True
                    if ON_TPU:
                        line["chain_us"] = least_us(step) / layers
                        us, calls = kernel_us(step)
                        line["kernel_us"] = us / max(calls, 1)
                        line["kernel_calls"] = calls
                        line["bytes_us"] = bytes_us
                        if us:
                            line["roofline"] = bytes_us / line["kernel_us"]
                except Exception as e:  # a variant the compiler refuses
                    line["failed"] = f"{type(e).__name__}: {str(e)[:300]}"
                    if vname in ("megablox", "new"):
                        bad += 1
                print(json.dumps(line), flush=True)
            del lhs, rhs, step
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
