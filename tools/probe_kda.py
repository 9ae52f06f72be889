"""Device-time probe of the delta rule with a decay a key channel
(``ops/kda.py``) alone on the chip, at the shapes of
``kimilinear48b.long-answers``: 32 heads of 128, 193 slots of 10 layers.
The one-token form at 130 and at 192 live rows of a 192-row step; the
chunked form at one chunk row of 64, 128, 256, 382 and 512 tokens, at
eight rows of 48 tokens and at twelve of 40 (two rounds of nine places);
both beside each other as a mixed step runs them (130 decode rows and a
382-token chunk). Each case: outputs and states
against the recurrence a token (``ops/gdn.py``'s scan, given the decay a
channel), and the least of five timings of 10 calls in one program (a
step's KDA layers): ``ms_10_layers`` through ``kda_attention`` as a step
calls it, and for the chunk cases ``chunks_ms`` / ``xla_chunks_ms``, the
chunked form alone as the Pallas kernel and as the XLA form (each checked
the same way). At 512 tokens the XLA form's parts are timed apart once
(``xla_parts_ms``). One JSON line a point; exits non-zero on a
disagreement. Without a TPU: one tiny case, the kernels interpreted.
``python tools/probe_kda.py chunk.64,chunk.512`` runs the cases named.

The ``conv.*`` cases are the short convolution of a layer
(``kda_short_conv``) at the cell's shapes, 10 layers over 193 slots of 3 x
96 x 128 bf16: 192 one-token rows of which 72 and 130 live, and the same
rows beside a chunk of 440 and of 382 tokens. Both forms, the Pallas kernel
(``kernel_ms``) and ``read_slot_rows`` -> ``ragged_short_conv`` ->
``write_slot_rows`` (``xla_ms``), each also with what the mixer does next
(SiLU, the two norms, q, k and v a head a row: ``*_scope_ms``), since the
kernel hands its output over as ``[T, heads, 128]`` and the XLA form as
``[T, channels]``; the least bytes (a token's inputs in and float32 out, a
live row's tail in and out) and the kernel's share of the chip's bandwidth
(``benchmark/peaks.json``) at them.
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
    for n in (64, 128, 256):
        yield case(rng, f"chunk.{n}", [n], 512, 192, **big)
    yield case(rng, "chunk.382", [382], 512, 192, **big)
    yield case(rng, "chunk.512", [512], 512, 192, **big)
    yield case(rng, "chunks.8x48", [48] * 8, 512, 192, **big)
    # Twelve chunks where a round of the kernel's grid has nine places.
    yield case(rng, "chunks.12x40", [40] * 12, 512, 192, **big)
    yield case(rng, "mixed.130+382", [1] * 130 + [382], 512, 192, **big)


def attention(c):
    return functools.partial(kda.kda_attention, decode_only=c["decode_only"])


def xla_chunks(*args):
    return kda._chunks_xla(*args, n_chunks=-(-args[0].shape[0] // kda.CHUNK) + 1)


def run(c, form):
    """``form(qn, kn, v, a, beta, state, layer, slots, fresh, qsl, n)``
    over the case's layers: the first call's outputs and state, and the
    least of five timings of the calls after it."""
    d = c["q"].shape[-1]

    @functools.partial(jax.jit, donate_argnums=(5,))
    def layers(q, k, v, a, beta, state, slots, fresh, qsl):
        qn, kn = kda.l2norm(q) * d ** -0.5, kda.l2norm(k)

        def one(state, layer):
            o, state = form(qn, kn, v, a, beta, state, layer, slots, fresh, qsl,
                            jnp.int32(c["n"]))
            return state, o

        state, outs = jax.lax.scan(one, state, jnp.arange(c["layers"], dtype=jnp.int32))
        return outs, state

    # The state (4 GB at the cell's size) is donated and fed back: the
    # timed calls run on the states the calls before them left.
    args = (c["q"], c["k"], c["v"], c["a"], c["beta"])
    rest = (c["slots"], c["fresh"], c["qsl"])
    outs, state = jax.block_until_ready(layers(*args, jnp.asarray(c["state_host"]), *rest))
    first = np.asarray(outs), np.asarray(state)
    return *first, least_ms(lambda st: layers(*args, st, *rest)[1], state)


def least_ms(step, x=None, calls=4):
    """The least of five timings of ``x = step(x)`` (``x`` may be donated:
    each call is fed what the call before returned). A timing is ``calls``
    calls sent one behind the other and waited for together, a call's
    share of it: the host's millisecond to send a program and to hear of
    its end is paid once, not beside every few ms of device time."""
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(calls):
            x = step(x)
        jax.block_until_ready(x)
        best = min(best, (time.perf_counter() - t0) / calls)
    return 1e3 * best


def xla_parts(c):
    """The XLA form's one round of nine places at the case's shapes, its
    parts each alone, 10 times in one program: ms, least of five."""
    h, d = c["q"].shape[1:]
    t, cs = c["q"].shape[0], kda.CHUNK
    nc = -(-t // cs) + 1
    qlen, per_row, ends, total = kda._chunk_places(c["qsl"], jnp.int32(c["n"]), len(c["slots"]), cs)
    g = jnp.arange(nc, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(ends, g, side="right"), 0, len(c["slots"]) - 1)
    j = g - (ends[row] - per_row[row])
    n_valid = jnp.where(g < total, jnp.clip(qlen[row] - j * cs, 0, cs), 0)
    ok = jnp.arange(cs)[None, :] < n_valid[:, None]
    idx = jnp.clip(c["qsl"][row][:, None] + j[:, None] * cs + jnp.arange(cs), 0, t - 1)
    where = c["slots"][row]

    def operands(q, k, v, a, beta):
        take = lambda x: jnp.where(ok.reshape(ok.shape + (1,) * (x.ndim - 1)), x[idx], 0.0)
        return kda._chunk_operands(take(q), take(k), take(v), take(a), take(beta))

    def scan(ops, s_in):
        def step(s0, xs):
            w, wk, qp, aq, kend, dend = xs
            u = w - jnp.einsum("hik,hkv->hiv", wk, s0, precision=kda._HI)
            o = jnp.einsum("hik,hkv->hiv", qp, s0, precision=kda._HI) + jnp.einsum(
                "hij,hjv->hiv", aq, u, precision=kda._HI)
            s1 = dend[..., None] * s0 + jnp.einsum("hjk,hjv->hkv", kend, u, precision=kda._HI)
            return s1, (o, s1)

        return jax.lax.scan(step, s_in, ops)[1]

    def ten(fn):
        """``fn(i, *xs)`` for the 10 layers in one program; every result
        is folded into what is returned, so nothing is dead."""
        def program(*xs):
            def one(acc, i):
                out = fn(i, *xs)
                return acc + sum(jnp.sum(leaf[..., :1]) for leaf in jax.tree.leaves(out)), None
            return jax.lax.scan(one, jnp.float32(0), jnp.arange(10, dtype=jnp.int32))[0]
        return jax.jit(program)

    def least(program, *xs):
        jax.block_until_ready(program(*xs))
        return least_ms(lambda _: program(*xs))

    qkv = (c["q"], c["k"], c["v"], c["a"], c["beta"])
    ops = jax.jit(operands)(*qkv)
    s_in = jnp.zeros((h, d, d), jnp.float32)
    state = jnp.asarray(c["state_host"])
    o_all = jnp.zeros((nc * cs, h, d), jnp.float32)
    s_all = jnp.zeros((nc, h, d, d), jnp.float32)
    out = dict(
        # +i so that the ten are ten (no common subexpression).
        operands=least(ten(lambda i, *xs: operands(*(x + i for x in xs))), *qkv),
        scan=least(ten(lambda i, ops, s: scan(ops, s + i)), ops, s_in),
        state_gather=least(ten(lambda i, st: st[i, where]), state),
        output_scatter=least(ten(lambda i, o: jnp.zeros((t, h, d), jnp.float32).at[
            jnp.where(ok, idx, t).reshape(-1)].set(o + i, mode="drop")), o_all),
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scatter(st, s_all):
        def one(st, i):
            return st.at[i, where].set(s_all + i), None
        return jax.lax.scan(one, st, jnp.arange(10, dtype=jnp.int32))[0]

    state = jax.block_until_ready(scatter(state, s_all))
    out["state_scatter"] = least_ms(lambda st: scatter(st, s_all), state)
    return out


def hbm_bytes_per_s():
    """The chip's bandwidth by ``benchmark/peaks.json``; None for a device
    the table does not hold (the CPU's smoke case)."""
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmark", "peaks.json")
    with open(path) as f:
        kinds = json.load(f)["device_kinds"]
    return kinds.get(jax.devices()[0].device_kind, {}).get("hbm_bytes_per_s")


def conv_cases(smoke):
    if smoke:
        yield "conv.smoke", [1, 1, 9, 1], 16, 8, False, dict(heads=2, d=16, slots=6, layers=2)
        return
    big = dict(heads=32, d=128, slots=192, layers=10)
    yield "conv.decode.72", [1] * 72, 192, 192, True, big
    yield "conv.decode.130", [1] * 130, 192, 192, True, big
    yield "conv.mixed.72+440", [1] * 72 + [440], 512, 192, False, big
    yield "conv.mixed.130+382", [1] * 130 + [382], 512, 192, False, big


def conv_point(rng, name, qlens, t_pad, r_pad, decode_only, heads, d, slots, layers):
    """Both forms of the convolution over ``layers`` layers in one program,
    a layer's inputs its own (nothing is hoisted out of the scan): the last
    layer's output and the stack of the one against the other, and the
    least of five timings of each, alone and with the mixer's next steps."""
    n, live, pj, taps_n = len(qlens), sum(qlens), heads * d, 4
    qsl = np.full(r_pad + 1, live, np.int32)
    qsl[0] = 0
    qsl[1: n + 1] = np.cumsum(qlens)
    if decode_only:
        qsl = np.arange(r_pad + 1, dtype=np.int32)
    owner = np.zeros(t_pad, np.int32)
    for i in range(n):
        owner[qsl[i]: qsl[i + 1]] = i
    rows = np.full(r_pad, slots, np.int32)
    rows[:n] = rng.permutation(slots)[:n]
    bf16 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    xs, taps = [bf16(t_pad, pj) for _ in range(3)], [bf16(taps_n, pj) for _ in range(3)]
    stack_host = np.asarray(bf16(layers, slots + 1, taps_n - 1, 3 * heads, d))
    rest = (jnp.asarray(rows), jnp.zeros(r_pad, bool).at[n - 1].set(len(qlens) > 1 and not decode_only),
            jnp.asarray(owner), jnp.asarray(qsl), jnp.int32(n))

    def kernel(xs, stack, layer, slots, fresh, owner, qsl, n):
        return kda.kda_short_conv(xs, stack, taps, layer, slots, fresh, owner, qsl, n,
                                  decode_only=decode_only)

    def xla(xs, stack, layer, slots, fresh, owner, qsl, n):
        r = slots.shape[0]
        tail = jnp.where(fresh[:, None, None], 0, kda.read_slot_rows(
            stack, layer, slots).reshape(r, taps_n - 1, 3 * pj))
        y, new = kda.ragged_short_conv(
            jnp.concatenate(xs, -1), tail, jnp.concatenate(taps, -1), owner, qsl,
            decode_only=decode_only)
        return y, kda.write_slot_rows(stack, layer, slots, new.reshape(r, -1), n)

    def scope(y):
        """What ``_kda_mixer`` does with the convolution's output."""
        y = jax.nn.silu(y).reshape(t_pad, 3, heads, d)
        return kda.l2norm(y[:, 0]) * d ** -0.5, kda.l2norm(y[:, 1]), y[:, 2]

    def program(form, with_scope):
        @functools.partial(jax.jit, donate_argnums=(1,))
        def run(xs, stack, *rest):
            def one(stack, layer):
                mine = [x + layer.astype(x.dtype) for x in xs]
                y, stack = form(mine, stack, layer, *rest)
                return stack, scope(y) if with_scope else y

            stack, outs = jax.lax.scan(one, stack, jnp.arange(layers, dtype=jnp.int32))
            return jax.tree.map(lambda o: o[-1], outs), stack

        return run

    point, got = dict(case=name, rows=n, tokens=live, device=jax.devices()[0].device_kind), {}
    for label, form, shape in (("kernel", kernel, stack_host.shape),
                               ("xla", xla, (layers, slots + 1, -1))):
        for with_scope in (False, True):
            run = program(form, with_scope)
            out, stack = jax.block_until_ready(
                run(xs, jnp.asarray(stack_host.reshape(shape)), *rest))
            if not with_scope:
                got[label] = (np.asarray(out).reshape(t_pad, -1)[:live],
                              np.asarray(stack, np.float32).reshape(layers, slots + 1, -1))
            point[label + ("_scope_ms" if with_scope else "_ms")] = least_ms(
                lambda st: run(xs, st, *rest)[1], stack)
    err = float(np.abs(got["kernel"][0] - got["xla"][0]).max())
    tails_equal = bool(np.array_equal(got["kernel"][1], got["xla"][1]))
    least = layers * (live * 3 * pj * (2 + 4) + n * 2 * (taps_n - 1) * 3 * pj * 2)
    point.update(max_abs_err=err, tails_equal=tails_equal, least_bytes=least)
    if hbm_bytes_per_s():
        point["kernel_share_of_hbm"] = least / (point["kernel_ms"] * 1e-3) / hbm_bytes_per_s()
    print(json.dumps(point), flush=True)
    return not (err < 1e-4 and tails_equal)


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
    only = set(sys.argv[1].split(",")) if len(sys.argv) > 1 else None  # case names
    for name, *shape, sizes in conv_cases(smoke):
        if not only or name in only:
            bad += conv_point(np.random.default_rng(1), name, *shape, **sizes)
    for c in cases(np.random.default_rng(0), smoke):
        if only and c["name"] not in only:
            continue
        c["state_host"] = np.asarray(c.pop("state"))
        layer = c["layers"] - 1
        want, want_state = reference(c, layer)
        slots = np.asarray(c["slots"])[: c["n"]]
        point = dict(case=c["name"], rows=c["n"], tokens=c["live"],
                     device=jax.devices()[0].device_kind)
        forms = {"": attention(c)}
        if c["name"].startswith(("chunk", "smoke")):
            forms.update(chunks_=kda.kda_chunks, xla_chunks_=xla_chunks)
        qlens = np.diff(np.asarray(c["qsl"]))[: c["n"]]
        for name, form in forms.items():
            outs, state, ms = run(c, form)
            # The chunked form alone takes the rows of several tokens.
            mine = qlens > 1 if name else qlens > 0
            diff = np.abs(outs[layer][: c["live"]] - want[: c["live"]])[np.repeat(mine, qlens)]
            diff_state = np.abs(state[layer][slots[mine]] - want_state[: c["n"]][mine])
            err, err_state = float(diff.max()), float(diff_state.max())
            bad += not (err < 2e-4 and err_state < 2e-4)
            point.update({
                name + ("ms" if name else "ms_10_layers"): ms,
                name + "max_abs_err": err, name + "max_abs_err_state": err_state})
        if c["name"] == "chunk.512":
            point["xla_parts_ms"] = xla_parts(c)
        print(json.dumps(point), flush=True)
        c.clear()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
