"""Device-time probe of the Mamba-2 rule over state slots (``ops/mamba.py``)
and of the shared convolution as the state-space mixer calls it, alone on
the chip at the shapes of ``falconh1-34b.long-answers``: 32 heads of 128
over a state of 256, 2 groups, the cell's slots of 6 layers, 5120
convolution channels with a bias (``tools/probe_kda.py``'s form).

Cases: the one-token form at a third, two thirds and all of the cell's rows
live (``decode.N``); one chunk row of 128, 384 and 512 tokens and a mixed
step of two thirds of the rows beside a 384-token chunk. Each case: outputs
and the states left against the recurrence a token (a scan, float32, at
``Precision.HIGHEST``), then the least of five timings of 6 calls in one
program (a step's layers). For the one-token form and the convolution the
least bytes by ``benchmark/kernels/ssd.py`` and ``ssd_conv.py`` and the
share of the chip's bandwidth (``benchmark/peaks.json``) at them: what
``ssd_decode_roofline`` and ``ssd_conv_roofline`` would read of the kernel
alone, which may not pass 100. One JSON line a point; exits non-zero on a
disagreement or a share over 100. Without a TPU: one tiny case, the kernels
interpreted. ``python tools/probe_ssd.py decode.64,conv.mixed`` runs the
cases named.
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

from benchmark.harness import manifest as M
from vllm_tpu.ops import kda, mamba

CELL = "falconh1-34b.long-answers"


def case(rng, name, qlens, t_pad, r_pad, h, p, g, n, slots, layers, decode_only=False):
    rows_n, live = len(qlens), sum(qlens)
    qsl = np.full(r_pad + 1, live, np.int32)
    qsl[0] = 0
    qsl[1: rows_n + 1] = np.cumsum(qlens)
    owner = np.zeros(t_pad, np.int32)
    for i in range(rows_n):
        owner[qsl[i]: qsl[i + 1]] = i
    f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    rows = np.full(r_pad, slots, np.int32)
    rows[:rows_n] = rng.permutation(slots)[:rows_n]
    # Steps 0.001-0.1 a head and exp(A_log) 1-32, the dummy load's range.
    dt = jnp.exp(f32(t_pad, h) * 0.5) * jnp.exp(jnp.linspace(np.log(1e-3), np.log(1e-1), h))
    return dict(
        name=name, n=rows_n, live=live, decode_only=decode_only, layers=layers,
        x=f32(t_pad, h, p), dt=dt, ln_a=-jnp.linspace(1.0, 32.0, h) * dt,
        b=f32(t_pad, g, n), c=f32(t_pad, g, n),
        state_shape=(layers, slots + 1, h, p, n),
        slots=jnp.asarray(rows), fresh=jnp.zeros(r_pad, bool).at[rows_n - 1].set(len(qlens) > 1),
        qsl=jnp.asarray(qsl), owner=jnp.asarray(owner))


def cases(rng, smoke, rows):
    if smoke:
        yield case(rng, "smoke", [1, 1, 20], 32, 8, 4, 8, 2, 16, 6, 2)
        return
    big = dict(h=32, p=128, g=2, n=256, slots=rows, layers=6)
    for live in (rows // 3, 2 * rows // 3, rows):
        yield case(rng, f"decode.{live}", [1] * live, rows, rows, decode_only=True, **big)
    for n in (128, 384, 512):
        yield case(rng, f"chunk.{n}", [n], 512, rows, **big)
    yield case(rng, "mixed", [1] * (2 * rows // 3) + [384], 512, rows, **big)


def least_ms(step, state, repeats=5):
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        state = jax.block_until_ready(step(state))
        best = min(best, time.perf_counter() - t0)
    return 1e3 * best


def recurrence(c, state, layer):
    """The rule a token a row from ``state[layer]``, in one scan over the
    step's live tokens: ``(y [live, H, P], the states the rows leave)``."""
    h, g = c["x"].shape[1], c["b"].shape[1]
    heads = lambda v: jnp.repeat(v, h // g, axis=1)
    owner, live = c["owner"][: c["live"]], c["live"]
    first = jnp.arange(live) == c["qsl"][owner]
    s0 = jnp.where(c["fresh"][:, None, None, None], 0.0, state[layer][c["slots"]])

    def token(held, xs):
        row, is_first, x, dt, ln_a, b, cc = xs
        s = jnp.where(is_first, s0[row], held)
        s = jnp.exp(ln_a)[:, None, None] * s + (dt[:, None] * x)[:, :, None] * b[:, None, :]
        return s, (jnp.einsum("hpn,hn->hp", s, cc, precision="highest"), s)

    _, (y, states) = jax.lax.scan(
        token, jnp.zeros(state.shape[2:], jnp.float32),
        (owner, first, c["x"][:live], c["dt"][:live], c["ln_a"][:live],
         heads(c["b"])[:live], heads(c["c"])[:live]))
    last = jnp.clip(c["qsl"][1: c["n"] + 1] - 1, 0, live - 1)
    return y, states[last]


def run_rule(c, counts, config, peaks):
    @functools.partial(jax.jit, donate_argnums=(0,))
    def layers(state):
        def one(state, layer):
            y, state = mamba.ssd_attention(
                c["x"], c["dt"], c["ln_a"], c["b"], c["c"], state, layer, c["slots"],
                c["fresh"], c["qsl"], jnp.int32(c["n"]), decode_only=c["decode_only"])
            return state, y
        state, ys = jax.lax.scan(one, state, jnp.arange(c["layers"], dtype=jnp.int32))
        return ys, state

    state0 = jax.random.normal(jax.random.PRNGKey(1), c["state_shape"], jnp.float32) * 0.1
    want_y, want_s = jax.jit(lambda state: recurrence(c, state, 1))(state0)
    want_y, want_s = np.asarray(want_y), np.asarray(want_s)
    ys, state = jax.block_until_ready(layers(state0))
    got_y = np.asarray(ys[1])[: c["live"]]
    got_s = np.asarray(state[1][c["slots"][: c["n"]]])
    err_y = float(np.abs(got_y - want_y).max() / max(1.0, np.abs(want_y).max()))
    err_s = float(np.abs(got_s - want_s).max() / max(1.0, np.abs(want_s).max()))
    ms = least_ms(lambda st: layers(st)[1], state)
    point = {"case": c["name"], "rows": c["n"], "tokens": c["live"], "ms_6_layers": ms,
             "out_err": err_y, "state_err": err_s}
    if c["decode_only"]:
        least = counts.least_seconds(config, c["n"] * c["layers"], peaks)
        point |= {"least_ms": 1e3 * least, "share_of_bandwidth": 100 * 1e3 * least / ms}
    return point, max(err_y, err_s) < 5e-4  # three bfloat16 passes a matmul in the chunks


def run_conv(c, counts, config, peaks, dtype):
    t = c["x"].shape[0]
    km1, ch = 3, counts.channels(config) if c["x"].shape[1] == 32 else 5 * 16
    lanes = 128 if ch % 128 == 0 else 16
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((t, ch)), dtype)
    taps = jnp.asarray(rng.standard_normal((4, ch)) / 2, dtype)
    bias = jnp.asarray(rng.standard_normal((ch,)) / 10, dtype)
    shape = (c["layers"], c["state_shape"][1], km1, ch // lanes, lanes)

    def form(kernel):
        """The layers through ``kda_short_conv`` as the Pallas kernel, or as
        the XLA form it takes off the TPU (the choice is read at the trace)."""
        @functools.partial(jax.jit, donate_argnums=(0,))
        def layers(stack):
            def one(stack, layer):
                y, stack = kda.kda_short_conv(
                    (x,), stack, (taps,), layer, c["slots"], c["fresh"], c["owner"],
                    c["qsl"], jnp.int32(c["n"]), decode_only=c["decode_only"], bias=bias)
                return stack, y
            stack, ys = jax.lax.scan(one, stack, jnp.arange(c["layers"], dtype=jnp.int32))
            return ys, stack

        def run(stack):
            real = kda._pallas_form
            if not kernel:
                kda._pallas_form = lambda state=None: (False, False)
            try:
                return layers(stack)
            finally:
                kda._pallas_form = real
        return run

    stack0 = jnp.asarray(rng.standard_normal(shape) * 0.5, dtype)
    (want_y, want_s) = jax.block_until_ready(form(False)(stack0 + 0))
    kernel = form(True)
    got_y, got_s = jax.block_until_ready(kernel(stack0 + 0))
    err = float(np.abs(np.asarray(got_y, np.float32) - np.asarray(want_y, np.float32)).max())
    same = bool(np.array_equal(np.asarray(got_s, np.float32), np.asarray(want_s, np.float32)))
    ms = least_ms(lambda st: kernel(st)[1], got_s)
    point = {"case": "conv." + c["name"], "rows": c["n"], "tokens": c["live"],
             "kernel_ms_6_layers": ms, "out_err": err, "tails_equal": same}
    if ch == counts.channels(config):
        least = counts.least_seconds(config, c["live"] * c["layers"], c["n"] * c["layers"], peaks)
        point |= {"least_ms": 1e3 * least, "share_of_bandwidth": 100 * 1e3 * least / ms}
    return point, err < 1e-3 and same


def main(argv):
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        os.environ.setdefault("VLLM_TPU_PALLAS_INTERPRET", "1")
    only = set(argv[0].split(",")) if argv else None
    cell = M.Cell(M.load_manifest(), CELL)
    config = cell.hf_config
    peaks = M.peaks_for("TPU v5 lite")
    rows = int(cell.engine_args["max_num_seqs"])
    ssd, conv = M.kernel_counts("ssd"), M.kernel_counts("ssd_conv")
    ok = True
    for c in cases(np.random.default_rng(0), not on_tpu, rows):
        runs = []
        if only is None or c["name"] in only:
            runs.append(lambda: run_rule(c, ssd, config, peaks))
        if (only is None or "conv." + c["name"] in only) and not c["name"].startswith("chunk"):
            runs.append(lambda: run_conv(
                c, conv, config, peaks, jnp.bfloat16 if on_tpu else jnp.float32))
        for run in runs:
            point, good = run()
            over = point.get("share_of_bandwidth", 0) > 100
            ok = ok and good and not over
            print(json.dumps(point | {"ok": good and not over}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
