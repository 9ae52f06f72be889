"""Device-time probe of the delta rule with a decay a key channel
(``ops/kda.py``) alone on the chip, at the shapes of
``kimilinear48b.long-answers``: 32 heads of 128, 193 slots of 10 layers.
The one-token form at 130 and at 192 live rows of a 192-row step; the
chunked form at a 382-token chunk, at a 512-token chunk, and at eight
rows of 48 tokens; both beside each other as a mixed step runs them (130
decode rows and a 382-token chunk). Each case: outputs and states against
the recurrence a token (``ops/gdn.py``'s scan, given the decay a channel),
and the least of five timings of 10 calls in one program (a step's KDA
layers). One JSON line a point; exits non-zero on a disagreement. Without
a TPU: one tiny case, the kernel interpreted.
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from vllm_tpu.ops import kda
from vllm_tpu.ops.gdn import ragged_gated_delta_rule


def case(rng, name, qlens, t_pad, r_pad, heads, d, slots, layers, decode_only=False):
    n, live = len(qlens), sum(qlens)
    qsl = np.full(r_pad + 1, live, np.int32)
    qsl[0] = 0
    qsl[1: n + 1] = np.cumsum(qlens)
    owner = np.zeros(t_pad, np.int32)
    for i in range(n):
        owner[qsl[i]: qsl[i + 1]] = i
    f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    rows = np.full(r_pad, slots, np.int32)
    rows[:n] = rng.permutation(slots)[:n]
    return dict(
        name=name, n=n, live=live, decode_only=decode_only, layers=layers,
        q=f32(t_pad, heads, d), k=f32(t_pad, heads, d), v=f32(t_pad, heads, d),
        # Log-decays a token from -4 down to -0.0004, the dummy load's range.
        a=-jnp.exp(f32(t_pad, heads, d) * 1.5 - 3.5),
        beta=jax.nn.sigmoid(f32(t_pad, heads)),
        state=f32(layers, slots + 1, heads, d, d) * 0.1,
        slots=jnp.asarray(rows), fresh=jnp.zeros(r_pad, bool).at[n - 1].set(len(qlens) > 1),
        qsl=jnp.asarray(qsl), owner=jnp.asarray(owner))


def cases(rng, smoke):
    """One at a time: a case's state is 4 GB of the chip's 16."""
    if smoke:
        yield case(rng, "smoke", [1, 1, 20], 32, 8, 2, 16, 6, 2)
        return
    big = dict(heads=32, d=128, slots=192, layers=10)
    yield case(rng, "decode.130", [1] * 130, 192, 192, decode_only=True, **big)
    yield case(rng, "decode.192", [1] * 192, 192, 192, decode_only=True, **big)
    yield case(rng, "chunk.382", [382], 512, 192, **big)
    yield case(rng, "chunk.512", [512], 512, 192, **big)
    yield case(rng, "chunks.8x48", [48] * 8, 512, 192, **big)
    yield case(rng, "mixed.130+382", [1] * 130 + [382], 512, 192, **big)


def run(c):
    d = c["q"].shape[-1]

    @functools.partial(jax.jit, donate_argnums=(5,))
    def layers(q, k, v, a, beta, state, slots, fresh, qsl):
        qn, kn = kda.l2norm(q) * d ** -0.5, kda.l2norm(k)

        def one(state, layer):
            o, state = kda.kda_attention(
                qn, kn, v, a, beta, state, layer, slots, fresh, qsl, jnp.int32(c["n"]),
                decode_only=c["decode_only"])
            return state, o

        state, outs = jax.lax.scan(one, state, jnp.arange(c["layers"], dtype=jnp.int32))
        return outs, state

    # The state (4 GB at the cell's size) is donated and fed back: the
    # timed calls run on the states the calls before them left.
    args = (c["q"], c["k"], c["v"], c["a"], c["beta"])
    rest = (c["slots"], c["fresh"], c["qsl"])
    c["state_host"] = np.asarray(c["state"])
    outs, state = jax.block_until_ready(layers(*args, c.pop("state"), *rest))
    first = np.asarray(outs), np.asarray(state)
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        outs, state = jax.block_until_ready(layers(*args, state, *rest))
        best = min(best, time.perf_counter() - t0)
    del outs, state
    return *first, 1e3 * best


def reference(c, layer):
    slots = np.asarray(c["slots"])
    h0 = np.where(np.asarray(c["fresh"])[:, None, None, None], 0,
                  c["state_host"][layer][slots])
    with jax.default_matmul_precision("highest"):
        y, st = ragged_gated_delta_rule(c["q"], c["k"], c["v"], c["a"], c["beta"],
                                        jnp.asarray(h0), c["owner"], c["qsl"])
    return np.asarray(y), np.asarray(st)


def main():
    smoke = jax.default_backend() != "tpu"
    if smoke:
        os.environ.setdefault("VLLM_TPU_PALLAS_INTERPRET", "1")
        kda.CHUNK, kda.SUB = 8, 4
    bad = 0
    for c in cases(np.random.default_rng(0), smoke):
        outs, state, ms = run(c)
        layer = c["layers"] - 1
        want, want_state = reference(c, layer)
        slots = np.asarray(c["slots"])[: c["n"]]
        err = float(np.max(np.abs(outs[layer][: c["live"]] - want[: c["live"]])))
        err_state = float(np.max(np.abs(state[layer][slots] - want_state[: c["n"]])))
        bad += not (err < 2e-4 and err_state < 2e-4)
        print(json.dumps(dict(
            case=c["name"], ms_10_layers=ms, max_abs_err=err, max_abs_err_state=err_state,
            rows=c["n"], tokens=c["live"], device=jax.devices()[0].device_kind)), flush=True)
        c.clear()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
