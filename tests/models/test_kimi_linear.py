"""Kimi-Linear on the CPU at a tiny size: the two forms of the delta rule
with a decay a key channel against the recurrence a token, the expert
share against the uncut layer, and the counters the runner keeps."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.benchmark.tiny_kimi_linear import TINY_KIMI, model_dir, share
from vllm_tpu.ops import kda
from vllm_tpu.ops.gdn import ragged_gated_delta_rule

H, D, SLOTS, LAYERS, LAYER = 2, 16, 16, 2, 1


def _batch(qlens, fresh, t_pad, r_pad, seed):
    rng = np.random.default_rng(seed)
    n, live = len(qlens), sum(qlens)
    qsl = np.full(r_pad + 1, live, np.int32)
    qsl[0] = 0
    qsl[1: n + 1] = np.cumsum(qlens)
    owner = np.zeros(t_pad, np.int32)
    for i in range(n):
        owner[qsl[i]: qsl[i + 1]] = i
    f32 = lambda *shape: rng.normal(size=shape).astype(np.float32)
    q, k, v = f32(t_pad, H, D), f32(t_pad, H, D), f32(t_pad, H, D)
    # Log-decays from -20 (gone in one token) to -0.007 a token.
    a = -np.exp(f32(t_pad, H, D) * 2 - 1)
    beta = 1 / (1 + np.exp(-f32(t_pad, H)))
    state = f32(LAYERS, SLOTS + 1, H, D, D)
    slots = np.full(r_pad, SLOTS, np.int32)
    slots[:n] = rng.permutation(SLOTS)[:n]
    is_fresh = np.zeros(r_pad, bool)
    is_fresh[:n] = fresh
    return dict(q=q, k=k, v=v, a=a, beta=beta, state=state, slots=slots,
                fresh=is_fresh, qsl=qsl, owner=owner, n=n, live=live)


RAGGED = {
    "decode rows only": ([1, 1, 1], [0, 0, 0], 5, 5, True),
    "a chunk beside decode rows": ([1, 1, 20, 1], [0, 1, 0, 0], 32, 8, False),
    "chunks that start and continue requests": ([3, 9, 1, 17, 2], [1, 0, 0, 1, 0], 40, 8, False),
    "one request over four chunks": ([30], [0], 32, 2, False),
    "more chunks than one round holds": ([2] * 14, [0, 1] * 7, 32, 16, False),
    "a mixed step with no chunk": ([1, 1], [0, 0], 8, 4, False),
    "a mixed step with no decode row": ([5, 11], [1, 0], 16, 4, False),
    "a row of exactly one chunk": ([8, 1, 3], [0, 0, 1], 16, 4, False),
    "decode rows between two chunk rows": ([13, 1, 1, 18], [1, 0, 0, 0], 40, 8, False),
    "one short chunk in nine places": ([1, 5], [0, 0], 64, 4, False),
    "a last chunk of one token at the step's end": ([7, 9], [0, 1], 16, 4, False),
}


def _form(monkeypatch, form, chunk=8, sub=4):
    """``kernel``: both forms as Pallas kernels, interpreted (what
    ``conftest.py`` sets); ``xla``: both as what runs off the TPU. Chunks
    of 8 in blocks of 4 unless told."""
    from vllm_tpu import envs

    assert envs.VLLM_TPU_PALLAS_INTERPRET
    monkeypatch.setitem(envs._cache, "VLLM_TPU_DISABLE_PALLAS", form == "xla")
    assert kda._pallas_form(jnp.zeros(1, jnp.float32)) == (form == "kernel", True)
    monkeypatch.setattr(kda, "CHUNK", chunk)
    monkeypatch.setattr(kda, "SUB", sub)


FORMS = ["xla", "kernel"]


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("case", list(RAGGED))
def test_both_forms_equal_the_recurrence_a_token(case, form, monkeypatch):
    """``kda_attention`` against ``ops/gdn.py``'s scan a token (given the
    decay a channel): outputs at the live tokens, the state each row
    leaves in its slot, and nothing else touched: other slots, the other
    layer, the padding rows; the kernels leave the scratch slot alone too
    (the XLA forms park their dead places there). Chunks of 8 in blocks of
    4, so that the tiny rows cross chunk and block boundaries."""
    _form(monkeypatch, form)
    qlens, fresh, t_pad, r_pad, decode_only = RAGGED[case]
    b = _batch(qlens, fresh, t_pad, r_pad, seed=len(case))
    dev = {k: jnp.asarray(v) for k, v in b.items() if isinstance(v, np.ndarray)}
    qn = kda.l2norm(dev["q"]) * D ** -0.5
    kn = kda.l2norm(dev["k"])
    o, new_state = kda.kda_attention(
        qn, kn, dev["v"], dev["a"], dev["beta"], dev["state"], LAYER, dev["slots"],
        dev["fresh"], dev["qsl"], jnp.int32(b["n"]), decode_only=decode_only)
    h0 = np.where(b["fresh"][:, None, None, None], 0, b["state"][LAYER][b["slots"]])
    want, want_state = ragged_gated_delta_rule(
        dev["q"], dev["k"], dev["v"], dev["a"], dev["beta"], jnp.asarray(h0),
        dev["owner"], dev["qsl"])
    o, new_state = np.asarray(o), np.asarray(new_state)
    np.testing.assert_allclose(o[: b["live"]], np.asarray(want)[: b["live"]], atol=3e-6)
    assert not o[b["live"]:].any()
    for i in range(b["n"]):
        np.testing.assert_allclose(new_state[LAYER, b["slots"][i]],
                                   np.asarray(want_state)[i], atol=3e-6)
    idle = [s for s in range(SLOTS + (form == "kernel")) if s not in b["slots"][: b["n"]]]
    np.testing.assert_array_equal(new_state[LAYER, idle], b["state"][LAYER, idle])
    np.testing.assert_array_equal(new_state[1 - LAYER], b["state"][1 - LAYER])


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("places", [1, 3, 9])
def test_a_rows_chunks_over_several_rounds_of_places(places, form, monkeypatch):
    """Two rows of 4 and 3 chunks taken 1, 3 or 9 places a round: a row
    that a round's end cuts goes on from what the round left, and the
    result is the recurrence's whatever the places."""
    _form(monkeypatch, form)
    b = _batch([30, 1, 20], [0, 0, 1], 56, 4, seed=places)
    dev = {k: jnp.asarray(v) for k, v in b.items() if isinstance(v, np.ndarray)}
    o, new_state = kda.kda_chunks(
        kda.l2norm(dev["q"]) * D ** -0.5, kda.l2norm(dev["k"]), dev["v"], dev["a"],
        dev["beta"], dev["state"], LAYER, dev["slots"], dev["fresh"], dev["qsl"],
        jnp.int32(b["n"]), n_chunks=places)
    h0 = np.where(b["fresh"][:, None, None, None], 0, b["state"][LAYER][b["slots"]])
    want, want_state = ragged_gated_delta_rule(
        dev["q"], dev["k"], dev["v"], dev["a"], dev["beta"], jnp.asarray(h0),
        dev["owner"], dev["qsl"])
    o, new_state, want = np.asarray(o), np.asarray(new_state), np.asarray(want)
    np.testing.assert_allclose(o[:30], want[:30], atol=3e-6)
    np.testing.assert_allclose(o[31:51], want[31:51], atol=3e-6)
    assert not o[30].any() and not o[51:].any()  # the decode row's token, the padding
    for i in (0, 2):
        np.testing.assert_allclose(new_state[LAYER, b["slots"][i]],
                                   np.asarray(want_state)[i], atol=3e-6)
    idle = [s for s in range(SLOTS + (form == "kernel")) if s not in b["slots"][[0, 2]]]
    np.testing.assert_array_equal(new_state[LAYER, idle], b["state"][LAYER, idle])


def test_the_kernels_matmuls_hold_what_three_bfloat16_passes_hold():
    """Off the interpreter the kernel spells ``Precision.HIGH`` out: two
    bfloat16 parts a side and the three products that hold a leading one
    (2**-16 of a product), and sums of one side's own numbers from three
    parts (float32's 2**-24)."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(128, 128)), jnp.float32)
    want = np.asarray(x, np.float64) @ np.asarray(y, np.float64)
    high = np.abs(np.asarray(kda._dot_high(x, y, kda._NN)) - want).max()
    one_pass = np.abs(np.asarray(jnp.dot(
        x.astype(jnp.bfloat16), y.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32)) - want).max()
    assert high < 5e-4 and high < one_pass / 100  # of sums near 11
    np.testing.assert_array_equal(
        np.asarray(kda._dot_high(x, y, kda._NN, exact=True)),
        np.asarray(jnp.dot(x, y, precision="highest")))
    tril = jnp.tril(jnp.ones((64, 64), jnp.float32))
    sums = np.asarray(kda._dot_ones(tril, x, kda._NN, ones=0))
    np.testing.assert_allclose(sums, np.cumsum(np.asarray(x, np.float64), axis=0), atol=4e-6)
    ends = np.asarray(kda._dot_ones(x, jnp.ones((64, 8), jnp.float32), kda._TN, ones=1))
    np.testing.assert_allclose(ends, np.tile(sums[-1][:, None], (1, 8)), atol=4e-6)


@pytest.mark.parametrize("form", FORMS)
def test_a_strong_decay_neither_overflows_nor_loses_the_weak_channels(form, monkeypatch):
    """Log-decays of -60 a token beside -1e-4: a cumulated decay over a
    chunk of 64 is exp(-3840), and nothing divides by it."""
    _form(monkeypatch, form, kda.CHUNK, kda.SUB)
    rng = np.random.default_rng(0)
    t = 150
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k, v = f32(t, H, D), f32(t, H, D), f32(t, H, D)
    a = jnp.where(jnp.arange(D) % 2 == 0, -60.0, -1e-4) * jnp.ones((t, H, D))
    beta = jax.nn.sigmoid(f32(t, H))
    state = f32(1, 3, H, D, D)
    qsl = jnp.asarray([0, t, t], jnp.int32)
    o, new = kda.kda_chunks(
        kda.l2norm(q) * D ** -0.5, kda.l2norm(k), v, a, beta, state, 0,
        jnp.asarray([1, 2]), jnp.asarray([False, False]), qsl, jnp.int32(1))
    want, want_state = ragged_gated_delta_rule(
        q, k, v, a, beta, state[0, 1:2], jnp.zeros(t, jnp.int32), qsl)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), atol=3e-6)
    np.testing.assert_allclose(np.asarray(new)[0, 1], np.asarray(want_state)[0], atol=3e-6)


def test_the_short_convolution_continues_from_a_rows_tail():
    rng = np.random.default_rng(1)
    c, k = 6, 4
    x = rng.normal(size=(20, c)).astype(np.float32)
    taps = rng.normal(size=(k, c)).astype(np.float32)
    whole = sum(np.concatenate([np.zeros((k - 1, c), np.float32), x])[j: j + 20] * taps[j]
                for j in range(k))
    tail = jnp.zeros((1, k - 1, c))
    got = []
    # 20 tokens as steps of 2, 1, 9, 1, 7: tails shorter and longer than a step.
    for lo, hi in ((0, 2), (2, 3), (3, 12), (12, 13), (13, 20)):
        y, tail = kda.ragged_short_conv(
            jnp.asarray(x[lo:hi]), tail, jnp.asarray(taps),
            jnp.zeros(hi - lo, jnp.int32), jnp.asarray([0, hi - lo], jnp.int32))
        got.append(np.asarray(y))
    np.testing.assert_allclose(np.concatenate(got), whole, atol=1e-5)


def test_the_decode_only_convolution_is_the_ragged_one_a_token_a_row():
    rng = np.random.default_rng(2)
    r, c, k = 5, 6, 4
    x = jnp.asarray(rng.normal(size=(r, c)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(r, k - 1, c)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(k, c)), jnp.float32)
    args = (x, tail, taps, jnp.arange(r, dtype=jnp.int32), jnp.arange(r + 1, dtype=jnp.int32))
    y, new = kda.ragged_short_conv(*args, decode_only=True)
    want_y, want_new = kda.ragged_short_conv(*args)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(want_new))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_slot_rows_are_read_and_written_exactly_and_only_for_live_rows(dtype):
    rng = np.random.default_rng(3)
    stack = jnp.asarray(rng.normal(size=(2, 6, 8)), dtype)  # slot 5 is the scratch
    slots = jnp.asarray([3, 0, 5, 5], jnp.int32)  # two live rows, two padding rows
    np.testing.assert_array_equal(
        np.asarray(kda.read_slot_rows(stack, jnp.int32(1), slots), np.float32),
        np.asarray(stack[1, slots], np.float32))
    rows = jnp.asarray(rng.normal(size=(4, 8)), dtype).at[1, 2].set(jnp.inf)
    new = np.asarray(kda.write_slot_rows(stack, jnp.int32(1), slots, rows, jnp.int32(2)), np.float32)
    want = np.asarray(stack, np.float32).copy()
    want[1, 3] = np.asarray(rows[0], np.float32)
    want[1, 0] = np.asarray(rows[1].at[2].set(0), np.float32)  # not finite: stored as 0
    np.testing.assert_array_equal(new, want)  # the scratch slot and layer 0 untouched


# The convolution's kernel against the XLA form: a list of steps, each the
# rows' (tokens, starts its request) and the token and row buckets.
CONV_STEPS = {
    # 20 tokens of one request: tails shorter and longer than a step.
    "one row as steps of 2, 1, 9, 1, 7": [
        ([(2, 1)], 4, 2), ([(1, 0)], 4, 2), ([(9, 0)], 12, 2), ([(1, 0)], 4, 2),
        ([(7, 0)], 8, 2)],
    # Rows of one token, a chunk that crosses a tile of 4 tokens, a row that
    # starts its request, padding rows on the scratch slot.
    "a mixed step": [([(1, 0), (1, 0), (11, 0), (1, 1), (1, 0)], 24, 8)],
    "rows shorter than the tail across a tile's edge": [
        ([(3, 0), (2, 0), (2, 1), (1, 0), (2, 0)], 12, 8)],
    "decode only, fewer live rows than the bucket": [
        ([(1, 0), (1, 1), (1, 0), (1, 0), (1, 0)], 8, 8)],
    "a step with no live row": [([], 8, 4)],
    "every token of the bucket live": [([(1, 0), (6, 0), (1, 1)], 8, 3)],
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(CONV_STEPS))
def test_the_convolutions_kernel_equals_the_xla_form_and_touches_live_slots_only(
        case, dtype, monkeypatch):
    """``kda_conv_kernel`` interpreted, four tokens a step of its grid,
    against ``read_slot_rows`` -> ``ragged_short_conv`` ->
    ``write_slot_rows``: ``y`` to a rounding of the sum, the live rows' new
    tails and every other slot and layer bit for bit."""
    monkeypatch.setattr(kda, "CONV_TILE", 4)
    rng = np.random.default_rng(7)
    g, dk, k, slots_n, layers = 6, 16, 4, 9, 2
    widths = (2 * dk, 3 * dk, dk)  # three inputs side by side
    taps = [jnp.asarray(rng.normal(size=(k, w)), dtype) for w in widths]
    stack = jnp.asarray(rng.normal(size=(layers, slots_n + 1, k - 1, g, dk)), dtype)
    decode_only = case.startswith("decode only")
    for rows, t_pad, r_pad in CONV_STEPS[case]:
        n = len(rows)
        qlens = [q for q, _ in rows]
        qsl = np.full(r_pad + 1, sum(qlens), np.int32)
        qsl[0] = 0
        qsl[1: n + 1] = np.cumsum(qlens)
        owner = np.zeros(t_pad, np.int32)
        for i in range(n):
            owner[qsl[i]: qsl[i + 1]] = i
        slots = np.full(r_pad, slots_n, np.int32)  # padding rows: the scratch slot
        slots[:n] = (3 + 2 * np.arange(n)) % slots_n  # the same row, the same slot
        fresh = np.zeros(r_pad, bool)
        fresh[:n] = [f for _, f in rows]
        xs = [jnp.asarray(rng.normal(size=(t_pad, w)), dtype) for w in widths]
        if decode_only:
            qsl = np.arange(r_pad + 1, dtype=np.int32)  # as the model gives them
        args = (xs, stack, taps, jnp.int32(1), jnp.asarray(slots), jnp.asarray(fresh),
                jnp.asarray(owner), jnp.asarray(qsl), jnp.int32(n))
        got = {}
        for form in FORMS:
            _form(monkeypatch, form)
            assert kda._pallas_form() == (form == "kernel", True)
            got[form] = kda.kda_short_conv(*args, decode_only=decode_only)
        (want_y, want_stack), (y, new) = got["xla"], got["kernel"]
        assert y.dtype == jnp.float32 and y.shape == (t_pad, g, dk)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(new, np.float32),
                                      np.asarray(want_stack, np.float32))
        changed = (np.asarray(new, np.float32) != np.asarray(stack, np.float32)).any(axis=(2, 3, 4))
        assert changed[0].sum() == 0 and set(np.flatnonzero(changed[1])) == set(slots[:n])
        assert not np.asarray(y)[sum(qlens):].any()  # nothing past the live tokens
        stack = new


def _model(config, dtype=jnp.float32):
    from transformers import PretrainedConfig

    from vllm_tpu.models.registry import get_model_class

    cfg = PretrainedConfig.from_dict(config)
    return get_model_class(cfg)(cfg, dtype=dtype)


def test_the_layers_run_as_scans_over_whole_periods():
    from vllm_tpu.models.kimi_linear import layer_plan

    k, m = ("kda", "moe"), ("mla", "moe")
    first = ("kda", "dense")
    assert layer_plan([first] + [k, k, m, k] * 3) == ([0], 4, 3, [])
    # The published 27 layers: the last latent layer comes a layer early.
    assert layer_plan([first] + [k, k, m, k] * 6 + [k, m]) == ([0], 4, 6, [25, 26])
    assert layer_plan([first, k, m]) == ([0, 1, 2], 0, 0, [])
    model = _model(TINY_KIMI)
    assert (model.head_layers, model.period, model.reps, model.tail_layers) == ([0], 4, 2, [])
    assert model.kinds[3] == ("mla", "moe") and model.kinds[0] == ("kda", "dense")


def test_the_period_scan_carries_the_period_index_and_no_weight():
    """``apply`` holds ONE scan over the periods, and what that scan steps
    over (its ``xs``) is the period's index alone: the ``[P, n, ...]``
    stacks enter it whole, as constants, and each layer slices its own
    leaf ``[period, place]`` out of them. Handed over as ``xs`` they reach
    an iteration as ``[n, ...]`` slabs that n layers index, which the
    TPU's compiler copies out of the stack twice an iteration (PR 40;
    ``tests/test_tpu_aot_compile.py`` reads the compiled body where that
    compiler is installed)."""
    from vllm_tpu.ops.attention import AttentionMetadata

    model = _model(TINY_KIMI)
    model.max_state_slots = 4
    t, r = 8, 4
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    md = AttentionMetadata(
        positions=i32(t), slot_mapping=i32(t), block_tables=i32(r, 2),
        seq_lens=i32(r), query_start_loc=i32(r + 1), token_req_idx=i32(t),
        logits_indices=i32(r), num_seqs=i32(1), state_slots=i32(r),
    )
    args = (
        jax.eval_shape(model.init_dummy_params, jax.random.PRNGKey(0)),
        jax.eval_shape(lambda: model.alloc_kv_cache(8, 16, jnp.float32)),
        i32(t), md,
    )
    jaxpr = jax.make_jaxpr(model.apply)(*args).jaxpr
    paths = [path for path, _ in jax.tree_util.tree_flatten_with_path(args)[0]]
    assert len(paths) == len(jaxpr.invars)
    in_periods = lambda path: (
        path[0].idx == 0 and getattr(path[1], "key", None) == "periods")
    stacks = {v for v, path in zip(jaxpr.invars, paths) if in_periods(path)}
    assert len(stacks) == len(jax.tree.leaves(args[0]["periods"]))

    scans = [e for e in jaxpr.eqns
             if e.primitive.name == "scan" and stacks & set(e.invars)]
    assert len(scans) == 1
    (scan,) = scans
    assert scan.params["length"] == model.reps == 2
    first_x = scan.params["num_consts"] + scan.params["num_carry"]
    assert stacks <= set(scan.invars[: scan.params["num_consts"]])
    assert [(x.aval.shape, x.aval.dtype) for x in scan.invars[first_x:]] == [
        ((model.reps,), jnp.int32)
    ]


def test_the_shares_routed_parts_and_the_shared_expert_once_make_the_uncut_layer():
    """The guide's share test, on the program's own expert layer: four
    shares of four experts each, given the slices of the uncut layer's
    sixteen, route over all sixteen and compute their own part; the four
    parts, with the shared expert counted once, are the uncut layer."""
    whole = _model(TINY_KIMI)
    rng = np.random.default_rng(2)
    d, e, f = 64, 16, 32
    w = lambda *shape: jnp.asarray(rng.normal(size=shape) / 8, jnp.float32)
    lp = {"router": w(d, e), "router_bias": w(e) / 4, "ws_gate": w(d, f),
          "ws_up": w(d, f), "ws_down": w(f, d)}
    experts = {"we_gate": w(e, d, f), "we_up": w(e, d, f), "we_down": w(e, f, d)}
    h = w(24, d) * 8
    live = jnp.arange(24) < 20
    full, counts = whole._moe(h, lp, experts, None, live)
    assert int(counts.sum()) == 20 * 4
    shared = whole._moe(h, lp, jax.tree.map(lambda x: x[:0], experts), None, live)
    parts, hits = [], []
    for rank in range(4):
        part = _model(share(rank))
        assert part.router_width == 16 and part.first_expert == 4 * rank
        mine = jax.tree.map(lambda x: x[4 * rank: 4 * rank + 4], experts)
        out, c = part._moe(h, lp, mine, None, live)
        parts.append(out)
        hits.append(c)
    np.testing.assert_array_equal(np.concatenate(hits), np.asarray(counts))
    only_shared = _model({**share(0), "num_experts": 0, "ep_size": 16})._moe(
        h, lp, jax.tree.map(lambda x: x[:0], experts), None, live)[0]
    del shared
    np.testing.assert_allclose(
        np.asarray(sum(parts) - 3 * only_shared), np.asarray(full), atol=2e-5)


@pytest.mark.parametrize("stacked", [False, True], ids=["one layer", "the scan's stack"])
def test_the_grouped_matmul_sees_the_held_pairs_only(stacked):
    """The megablox path under a share (interpreted here): pairs routed to
    experts held elsewhere belong to no group; the result is the dense
    path's, which gives them no column."""
    from vllm_tpu.layers.moe import _dense_moe, _grouped_moe

    rng = np.random.default_rng(3)
    t, d, f, held, first, k = 40, 128, 128, 4, 8, 4
    w = lambda *shape: jnp.asarray(rng.normal(size=shape) / 8, jnp.float32)
    hidden, weights = w(t, d) * 8, jnp.abs(w(t, k))
    ids = jnp.asarray(rng.integers(0, 16, (t, k)), jnp.int32)
    wg, wu, wd = w(3, held, d, f), w(3, held, d, f), w(3, held, f, d)
    want = _dense_moe(hidden, wg[1], wu[1], wd[1], weights, ids, first_expert=first)
    if stacked:
        flat = lambda x: x.reshape(3 * held, *x.shape[2:])
        got = _grouped_moe(hidden, flat(wg), flat(wu), flat(wd), weights,
                           ids - first + held, interpret=True, held=(held, held))
    else:
        got = _grouped_moe(hidden, wg[1], wu[1], wd[1], weights, ids - first,
                           interpret=True, held=(0, held))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_the_runner_counts_state_rows_tokens_by_form_and_latent_context(tmp_path):
    from vllm_tpu import LLM, SamplingParams

    llm = LLM(model=model_dir(tmp_path, **share(1)), load_format="dummy", seed=5,
              dtype="float32", max_model_len=128, block_size=16,
              num_gpu_blocks_override=64, max_num_seqs=8, max_num_batched_tokens=32)
    prompts = [list(range(3, 3 + n)) for n in (40, 7)]
    llm.generate([{"prompt_token_ids": p} for p in prompts],
                 SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True))
    runner = llm.llm_engine.engine_core.engine_core.executor.worker.runner
    kda_layers, generated = 7, 2 * 6
    tokens = runner.linear_attn_tokens_by_kind
    # Every token a step held went through one of the two forms (the
    # scheduler runs a step ahead of the answers, so a few more than the
    # 47 prompt tokens and the 10 answers fed back).
    fed = sum(runner.step_tokens_by_kind.values())
    assert 47 + generated - 2 <= fed <= 47 + generated + 4
    assert tokens["chunk"] + tokens["decode"] == fed * kda_layers
    assert tokens["decode"] >= (generated - 2) * kda_layers
    assert tokens["chunk"] >= 45 * kda_layers
    steps = sum(runner.steps_by_kind.values())
    assert steps * kda_layers <= runner.linear_attn_state_rows <= 2 * steps * kda_layers
    assert runner.attn_context_tokens_by_kind["latent"] == sum(
        runner.attn_context_tokens_by_kind[k] for k in ("decode", "mixed"))
    # A share counts the pairs routed HERE: about a quarter of them all.
    every = fed * 4 * 8
    assert 0.1 * every < runner.moe_assignments < 0.45 * every
    assert 0 < runner.moe_experts_hit <= steps * 8 * 4
    # A finished request's slot comes back with the next step's notice.
    assert set(runner._state_slot_of.values()) <= {0, 1}
    llm.shutdown()


def test_slots_that_leave_no_page_fail_with_the_numbers():
    """``determine_num_kv_blocks`` with a budget the slots alone exceed."""
    from types import SimpleNamespace

    from vllm_tpu.worker.worker import Worker

    model = _model(TINY_KIMI)
    per_slot = model.state_bytes_per_slot()
    assert per_slot == 7 * (2 * 16 * 16 * 4 + 3 * 96 * 4)
    assert model.fixed_state_bytes(8) == 9 * per_slot
    worker = object.__new__(Worker)
    worker.model, worker.runner, worker.draft_model = model, None, None
    worker.device = SimpleNamespace(
        device_kind="fake", memory_stats=lambda: {"bytes_limit": 220_000, "bytes_in_use": 0})
    worker.config = SimpleNamespace(
        cache_config=SimpleNamespace(num_gpu_blocks_override=None, cache_dtype="auto",
                                     block_size=16, gpu_memory_utilization=0.9),
        parallel_config=SimpleNamespace(context_parallel_size=1, enable_eplb=False),
        model_config=SimpleNamespace(jax_dtype=jnp.float32),
        scheduler_config=SimpleNamespace(max_num_seqs=8),
    )
    with pytest.raises(RuntimeError, match=r"per-request state \d+ bytes for 8 slots"):
        worker.determine_num_kv_blocks()
    worker.device = SimpleNamespace(
        device_kind="fake", memory_stats=lambda: {"bytes_limit": 3_000_000, "bytes_in_use": 0})
    assert worker.determine_num_kv_blocks() > 8
