"""The Mamba-2 rule over state slots (``ops/mamba.py``: ``ssd_decode``,
``ssd_chunks``, ``ssd_attention``) against the recurrence a token written
out in numpy, and the convolution both slot families share
(``ops/kda.py::kda_short_conv``) with and without a bias."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from vllm_tpu.ops import kda, mamba

H, P, G, N, SLOTS, LAYERS, LAYER = 4, 8, 2, 16, 16, 2, 1
FORMS = ["xla", "kernel"]


def _form(monkeypatch, form):
    """``kernel``: the one-token form as its Pallas kernel, interpreted
    (what ``conftest.py`` sets); ``xla``: as what runs off the TPU."""
    from vllm_tpu import envs

    assert envs.VLLM_TPU_PALLAS_INTERPRET
    monkeypatch.setitem(envs._cache, "VLLM_TPU_DISABLE_PALLAS", form == "xla")
    assert mamba._pallas_form(jnp.zeros(1, jnp.float32)) == (form == "kernel", True)


def _batch(qlens, fresh, t_pad, r_pad, seed):
    rng = np.random.default_rng(seed)
    n, live = len(qlens), sum(qlens)
    qsl = np.full(r_pad + 1, live, np.int32)
    qsl[0] = 0
    qsl[1: n + 1] = np.cumsum(qlens)
    f32 = lambda *shape: rng.normal(size=shape).astype(np.float32)
    x, b, c = f32(t_pad, H, P), f32(t_pad, G, N), f32(t_pad, G, N)
    # Steps from 0.002 to 2 and decays from exp(-20) (gone in one token) to
    # exp(-0.0003) a token.
    dt = np.exp(f32(t_pad, H) * 1.5 - 3).astype(np.float32)
    ln_a = (-np.exp(f32(H) * 1.5) * dt).astype(np.float32)
    state = f32(LAYERS, SLOTS + 1, H, P, N)
    slots = np.full(r_pad, SLOTS, np.int32)
    slots[:n] = rng.permutation(SLOTS)[:n]
    is_fresh = np.zeros(r_pad, bool)
    is_fresh[:n] = fresh
    return dict(x=x, dt=dt, ln_a=ln_a, b=b, c=c, state=state, slots=slots,
                fresh=is_fresh, qsl=qsl, n=n, live=live)


def recurrence(bt):
    """``H_t = a_t H_{t-1} + dt_t x_t B_t^T``, ``y_t = H_t C_t`` a token a
    row in float64: ``(y [live, H, P], the state each row leaves)``."""
    y = np.zeros((bt["live"], H, P))
    left = []
    heads = lambda v: np.repeat(v, H // G, axis=0)  # [G, N] -> [H, N]
    for i in range(bt["n"]):
        s = np.zeros((H, P, N)) if bt["fresh"][i] else bt["state"][LAYER, bt["slots"][i]].astype(np.float64)
        for t in range(bt["qsl"][i], bt["qsl"][i + 1]):
            s = np.exp(bt["ln_a"][t].astype(np.float64))[:, None, None] * s + (
                (bt["dt"][t][:, None] * bt["x"][t])[:, :, None] * heads(bt["b"][t])[:, None, :])
            y[t] = np.einsum("hpn,hn->hp", s, heads(bt["c"][t]))
        left.append(s)
    return y, left


RAGGED = {
    "decode rows only": ([1, 1, 1], [0, 0, 0], 5, 5, True),
    "decode rows, one starts its request": ([1, 1, 1, 1], [0, 1, 0, 0], 8, 8, True),
    "a chunk beside decode rows": ([1, 1, 20, 1], [0, 1, 0, 0], 32, 8, False),
    "chunks that start and continue requests": ([3, 9, 1, 17, 2], [1, 0, 0, 1, 0], 40, 8, False),
    "one request over four chunks": ([30], [0], 32, 2, False),
    "more chunks than one round holds": ([2] * 14, [0, 1] * 7, 32, 16, False),
    "a mixed step with no chunk": ([1, 1], [0, 0], 8, 4, False),
    "a mixed step with no decode row": ([5, 11], [1, 0], 16, 4, False),
    "a row of exactly one chunk": ([8, 1, 3], [0, 0, 1], 16, 4, False),
    "decode rows between two chunk rows": ([13, 1, 1, 18], [1, 0, 0, 0], 40, 8, False),
    "a last chunk of one token at the step's end": ([7, 9], [0, 1], 16, 4, False),
}


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("case", list(RAGGED))
def test_both_forms_equal_the_recurrence_a_token(case, form, monkeypatch):
    """``ssd_attention`` (chunks of 8, so that the tiny rows cross chunk
    boundaries) against the recurrence: outputs at the live tokens, the
    state each row leaves in its slot, and nothing else touched: the other
    slots, the other layer, and the scratch slot where no padding place
    parked (the XLA forms park dead rows and dead places there; the kernel
    moves nothing for them). Float32 sums of a few hundred products against
    float64: 2e-5 of the largest output; a bfloat16 anywhere reads 1e-2."""
    _form(monkeypatch, form)
    monkeypatch.setattr(mamba, "SSD_CHUNK", 8)
    qlens, fresh, t_pad, r_pad, decode_only = RAGGED[case]
    bt = _batch(qlens, fresh, t_pad, r_pad, seed=len(case))
    dev = {k: jnp.asarray(v) for k, v in bt.items() if isinstance(v, np.ndarray)}
    y, new_state = mamba.ssd_attention(
        dev["x"], dev["dt"], dev["ln_a"], dev["b"], dev["c"], dev["state"], LAYER,
        dev["slots"], dev["fresh"], dev["qsl"], jnp.int32(bt["n"]), decode_only=decode_only)
    want, left = recurrence(bt)
    y, new_state = np.asarray(y), np.asarray(new_state)
    scale = max(1.0, np.abs(want).max())
    np.testing.assert_allclose(y[: bt["live"]], want, atol=2e-5 * scale)
    assert not y[bt["live"]:].any()
    for i in range(bt["n"]):
        np.testing.assert_allclose(new_state[LAYER, bt["slots"][i]], left[i],
                                   atol=2e-5 * max(1.0, np.abs(left[i]).max()))
    idle = [s for s in range(SLOTS) if s not in bt["slots"][: bt["n"]]]
    np.testing.assert_array_equal(new_state[LAYER, idle], bt["state"][LAYER, idle])
    np.testing.assert_array_equal(new_state[1 - LAYER], bt["state"][1 - LAYER])


@pytest.mark.parametrize("form", FORMS)
def test_the_one_token_form_leaves_the_scratch_slot_to_the_padding_rows(form, monkeypatch):
    """Live rows never write the scratch slot; the kernel writes it for
    nobody (its steps past the live rows hold the last live block), the
    einsum form parks the padding rows there."""
    _form(monkeypatch, form)
    bt = _batch([1, 1, 1], [0, 0, 1], 8, 8, seed=3)
    dev = {k: jnp.asarray(v) for k, v in bt.items() if isinstance(v, np.ndarray)}
    y, new = mamba.ssd_decode(
        dev["x"][:8], dev["dt"][:8], dev["ln_a"][:8], dev["b"][:8], dev["c"][:8],
        dev["state"], LAYER, dev["slots"], dev["fresh"], jnp.int32(3))
    new = np.asarray(new)
    changed = (new != bt["state"]).any(axis=(2, 3, 4))
    assert changed[1 - LAYER].sum() == 0
    assert set(np.flatnonzero(changed[LAYER][:SLOTS])) == set(bt["slots"][:3])
    if form == "kernel":
        np.testing.assert_array_equal(new[LAYER, SLOTS], bt["state"][LAYER, SLOTS])
    assert not np.asarray(y)[3:].any()


@pytest.mark.parametrize("places", [1, 3, 9])
def test_a_rows_chunks_over_several_rounds_of_places(places):
    """Two rows of 4 and 3 chunks taken 1, 3 or 9 places a round: a row that
    a round's end cuts goes on from what the round left in the carry."""
    bt = _batch([29, 20], [0, 1], 56, 4, seed=11)
    dev = {k: jnp.asarray(v) for k, v in bt.items() if isinstance(v, np.ndarray)}
    y, new_state = mamba.ssd_chunks(
        dev["x"], dev["dt"], dev["ln_a"], dev["b"], dev["c"], dev["state"], LAYER,
        dev["slots"], dev["fresh"], dev["qsl"], jnp.int32(2), n_chunks=places, chunk=8)
    want, left = recurrence(bt)
    np.testing.assert_allclose(np.asarray(y)[: bt["live"]], want,
                               atol=2e-5 * max(1.0, np.abs(want).max()))
    for i in range(2):
        np.testing.assert_allclose(np.asarray(new_state)[LAYER, bt["slots"][i]], left[i],
                                   atol=2e-5 * max(1.0, np.abs(left[i]).max()))


def test_a_state_kept_in_bfloat16_is_not_this_rule():
    """The tolerance above is float32's: the same step from a state rounded
    to bfloat16 misses it a hundredfold."""
    bt = _batch([1, 1], [0, 0], 4, 4, seed=5)
    dev = {k: jnp.asarray(v) for k, v in bt.items() if isinstance(v, np.ndarray)}
    rounded = dev["state"].astype(jnp.bfloat16).astype(jnp.float32)
    y, _ = mamba.ssd_decode(dev["x"][:4], dev["dt"][:4], dev["ln_a"][:4], dev["b"][:4],
                            dev["c"][:4], rounded, LAYER, dev["slots"], dev["fresh"],
                            jnp.int32(2))
    want, _ = recurrence(bt)
    assert np.abs(np.asarray(y)[:2] - want).max() > 2e-3 * np.abs(want).max()


# The shared convolution, one stream with a bias: the state-space mixer's
# call. Steps as in tests/models/test_kimi_linear.py::CONV_STEPS.
CONV_STEPS = {
    "one row as steps of 2, 1, 9, 1, 7": [
        ([(2, 1)], 4, 2), ([(1, 0)], 4, 2), ([(9, 0)], 12, 2), ([(1, 0)], 4, 2),
        ([(7, 0)], 8, 2)],
    "a mixed step": [([(1, 0), (1, 0), (11, 0), (1, 1), (1, 0)], 24, 8)],
    "decode only, fewer live rows than the bucket": [
        ([(1, 0), (1, 1), (1, 0), (1, 0), (1, 0)], 8, 8)],
}


@pytest.mark.parametrize("bias", [False, True], ids=["no bias", "bias"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(CONV_STEPS))
def test_the_shared_convolution_takes_one_stream_and_a_bias(case, dtype, bias, monkeypatch):
    """``kda_conv_kernel`` interpreted against the XLA form, and both
    against the convolution written out over the row's whole history: one
    stream of 5 lane tiles (a count that 32 does not divide, as the 40 of
    5,120 channels), taps ``[K, C]``, a bias ``[C]`` added to every live
    token's sum and to nothing else."""
    from vllm_tpu import envs

    monkeypatch.setattr(kda, "CONV_TILE", 4)
    rng = np.random.default_rng(17)
    g, dk, k, slots_n, layers = 5, 16, 4, 9, 2
    taps = jnp.asarray(rng.normal(size=(k, g * dk)), dtype)
    b = jnp.asarray(rng.normal(size=(g * dk,)), dtype) if bias else None
    stack = jnp.zeros((layers, slots_n + 1, k - 1, g, dk), dtype)
    decode_only = case.startswith("decode only")
    history: dict[int, list] = {}
    for rows, t_pad, r_pad in CONV_STEPS[case]:
        n = len(rows)
        qlens = [q for q, _ in rows]
        qsl = np.full(r_pad + 1, sum(qlens), np.int32)
        qsl[0] = 0
        qsl[1: n + 1] = np.cumsum(qlens)
        owner = np.zeros(t_pad, np.int32)
        for i in range(n):
            owner[qsl[i]: qsl[i + 1]] = i
        slots = np.full(r_pad, slots_n, np.int32)
        slots[:n] = (3 + 2 * np.arange(n)) % slots_n
        fresh = np.zeros(r_pad, bool)
        fresh[:n] = [f for _, f in rows]
        x = jnp.asarray(rng.normal(size=(t_pad, g * dk)), dtype)
        if decode_only:
            qsl = np.arange(r_pad + 1, dtype=np.int32)
        args = ((x,), stack, (taps,), jnp.int32(1), jnp.asarray(slots), jnp.asarray(fresh),
                jnp.asarray(owner), jnp.asarray(qsl), jnp.int32(n))
        got = {}
        for form in FORMS:
            monkeypatch.setitem(envs._cache, "VLLM_TPU_DISABLE_PALLAS", form == "xla")
            got[form] = kda.kda_short_conv(*args, decode_only=decode_only, bias=b)
        (want_y, want_stack), (y, new) = got["xla"], got["kernel"]
        np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(new, np.float32),
                                      np.asarray(want_stack, np.float32))
        # Against the whole history of each row (a row starts from zeros).
        xf, wf = np.asarray(x, np.float32), np.asarray(taps, np.float32)
        for i in range(n):
            seen = [] if fresh[i] else history.get(int(slots[i]), [])
            at = int(qsl[i]) if not decode_only else i
            for t in range(qlens[i]):
                seen = seen + [xf[at + t]]
                past = [np.zeros(g * dk, np.float32)] * (k - 1) + seen
                want = sum(past[-k + j] * wf[j] for j in range(k))
                if bias:
                    want = want + np.asarray(b, np.float32)
                np.testing.assert_allclose(
                    np.asarray(y)[at + t].reshape(-1), want, rtol=2e-6, atol=2e-6)
            history[int(slots[i])] = seen
        assert not np.asarray(y)[sum(qlens):].any()  # the bias too stays off the padding
        stack = new
