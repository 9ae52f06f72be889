"""Plain reference of the LFM2-MoE decoder (``model_type`` ``lfm2_moe``,
huggingface.co/LiquidAI/LFM2-8B-A1B): the forward pass in float32
``jax.numpy``, one sequence at a time, the short convolution as a sum of
shifted products over the whole sequence, full causal attention with keys
and values expanded a head, the experts one at a time over every row; no
cache, no chunking, no kernel, no batching. Imports nothing from
``vllm_tpu``.

Token states ``x`` [T, D]. ``rms`` has a learned scale, eps ``norm_eps``; no
bias anywhere (``conv_bias`` false). A layer ``l``::

    x <- x + OP_l(rms_op(x));   x <- x + FF_l(rms_ff(x))

going in ``x = embed[ids]``, coming out ``logits = rms_out(x) embed^T``
(the family's ``embedding_norm`` stands AFTER the last layer; the head is
the embedding, ``tie_embedding``). The first ``num_hidden_layers`` entries
of ``layer_types`` say which ``OP_l`` a layer has.

``conv`` (``Lfm2ShortConv``): ``[B, C, u] = h W_in`` split in three of D in
THAT order; ``g = B * u``; a depthwise causal convolution of K =
``conv_L_cache`` taps over time, no bias, no activation: ``c_t = sum_j w_j
g_{t - K + 1 + j}`` (tap K - 1 on the current token), ``g`` zero before
the first token; ``(C * c) W_out``.

``full_attention`` (``Lfm2Attention``): ``q = h Wq`` as A heads of dh = D /
A, ``k = h Wk`` and ``v = h Wv`` as KH heads; an RMSNorm over each head's
dh lanes of q and of k (a weight ``[dh]`` each, shared by the heads), THEN
rope, rotate-half over all dh lanes, theta ``rope_theta``, no scaling;
causal softmax of ``q k / sqrt(dh)``; ``Wo``.

``FF_l`` for ``l < num_dense_layers``: ``(SiLU(h W1) * (h W3)) W2`` of
width ``intermediate_size``. After them: ``s = sigmoid(h W_r)`` in float32;
the ``num_experts_per_tok`` experts are the largest of ``s + b``
(``use_expert_bias``; ``b [E]`` float32, for the CHOICE alone); their
weights are ``s`` at the chosen, divided by their sum + 1e-6
(``norm_topk_prob``), times ``routed_scaling_factor``; ``FF = sum_k w_k
(SiLU(h W1_e) * (h W3_e)) W2_e`` of width ``moe_intermediate_size``; no
shared expert, no groups.

Departures from the published description: none in the arithmetic. The
mixers and the layer's wiring are the family's dense sibling's, letter for
letter (``transformers.models.lfm2``; ``tests/test_lfm2_moe.py`` holds this
file to it at all-dense depth). The sparse block has no such oracle on this
machine and rests on the published config's keys; what they do not say is
listed under ``assumed`` in ``benchmark/configs/lfm2-8b-a1b.json``: the
guard 1e-6, the router and ``b`` in float32.

Weights. ``seeded_weights`` is the benchmark's own copy of how
``load_format=dummy`` draws this family (``vllm_tpu/models/lfm2_moe.py``):
leaf ``name`` of layer ``l`` is ``normal(fold_in(fold_in(key(seed),
id(name)), l)) / sqrt(fan_in)`` in the served dtype, stacked over the layers
of its kind in their order. Not so: the norms are ones, but the query's
norm a head is ``Q_NORM_DRAW`` (a norm a head leaves every score one wide
whatever ``Wq`` is drawn at, and a softmax over a thousand such keys is
nearly flat; at 2 some 18 of a thousand keys carry a row, as in a trained
model; at 4 ONE does, and bfloat16's rounding of q and k alone then moves
the logprobs by 0.2); ``b`` is
``ROUTER_BIAS_DRAW``, a mean plus a spread of a unit normal, in float32 (at
zeros the choice by ``s + b`` is the choice by ``s``, and nothing could tell
which of the two weighs the experts; the spread, 0.1, changes the choice
and starves no expert; the mean, -0.6, changes no choice and is what the
comparison sees, because the chosen scores lie near 0.8 a few hundredths
apart: ``s / sum(s)`` and ``(s + b) / sum(s + b)`` differ by a fifth with it);
the taps' fan-in is K. No width changes a shape, a count or a step's time.

On a TPU a float32 matmul runs in lower precision unless told otherwise, so
everything here runs under ``jax.default_matmul_precision("highest")``.

``compute``. ``forward`` and ``forward_many`` take the type the activations
are kept in, float32 unless said: the reference. With ``compute`` the served
type (bfloat16) the SAME formulae run with weights and activations in that
type, every product of matrices rounded to it as it is stored, the logits
too, and in float32 only the norms', the softmax's, the convolution's sum
and the router's arithmetic. That pass is not the reference and is compared
with nothing served (``harness/checker.py`` reads the served error as a
factor over its distance from the float32 pass). In float32 every cast
below is the identity.

Memory. Sixteen layers at the published widths are 10.8 GB of bfloat16
beside which ONE layer is cast to float32 at a time (a sparse layer 1.4 GB,
its experts one at a time under the scan); the head is the embedding, 0.5
GB in float32, and the logits are computed a block of ``HEAD_BLOCK``
vocabulary rows at a time.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

LEAF_IDS = {
    name: i for i, name in enumerate((
        "embed", "w_in", "conv_w", "w_out", "wq", "wk", "wv", "wo",
        "w1", "w3", "w2", "router", "router_bias",
        "we_gate", "we_up", "we_down",
    ))
}
Q_NORM_DRAW = 2.0
ROUTER_BIAS_DRAW = (-0.6, 0.1)  # mean, spread
RENORM_GUARD = 1e-6
HEAD_BLOCK = 32768  # vocabulary rows of the head a matmul
KINDS = ("norms", "conv", "attn", "dense", "moe", "experts")


def sizes(config: dict) -> dict:
    n, A = config["num_hidden_layers"], config["num_attention_heads"]
    types = list(config["layer_types"])[:n]
    if len(types) != n or set(types) - {"conv", "full_attention"}:
        raise ValueError(f"layer_types must name {n} layers, got {config['layer_types']}")
    if config.get("conv_bias", False):
        raise NotImplementedError("conv_bias with this family")
    dense = config.get("num_dense_layers", 0)
    return dict(
        n=n, D=config["hidden_size"], V=config["vocab_size"],
        F=config["intermediate_size"], Fm=config["moe_intermediate_size"],
        eps=config["norm_eps"], A=A, KH=config.get("num_key_value_heads") or A,
        dh=config.get("head_dim") or config["hidden_size"] // A,
        theta=float(config["rope_theta"]), K=config["conv_L_cache"],
        E=config["num_experts"], top_k=config["num_experts_per_tok"],
        renorm=bool(config.get("norm_topk_prob", True)),
        scaling=float(config.get("routed_scaling_factor", 1.0)),
        bias=bool(config.get("use_expert_bias", True)),
        kinds=tuple(("conv" if t == "conv" else "attn", "dense" if i < dense else "moe")
                    for i, t in enumerate(types)),
    )


def layers_of(s: dict, kind: str) -> list[int]:
    """The layers that hold leaves of ``kind``, in order: a layer's row in
    the kind's stacks is its place in this list."""
    if kind == "norms":
        return list(range(s["n"]))
    col = 0 if kind in ("conv", "attn") else 1
    want = "moe" if kind == "experts" else kind
    return [i for i, k in enumerate(s["kinds"]) if k[col] == want]


def leaf_shapes(s: dict, kind: str) -> dict:
    """Leaf -> (shape, fan_in; 0 = not drawn from a plain normal)."""
    D, A, KH, dh = s["D"], s["A"], s["KH"], s["dh"]
    return {
        "norms": {"op_norm": ((D,), 0), "ffn_norm": ((D,), 0)},
        "conv": {"w_in": ((D, 3 * D), D), "conv_w": ((s["K"], D), s["K"]),
                 "w_out": ((D, D), D)},
        "attn": {"wq": ((D, A * dh), D), "wk": ((D, KH * dh), D), "wv": ((D, KH * dh), D),
                 "q_norm": ((dh,), 0), "k_norm": ((dh,), 0), "wo": ((A * dh, D), A * dh)},
        "dense": {"w1": ((D, s["F"]), D), "w3": ((D, s["F"]), D), "w2": ((s["F"], D), s["F"])},
        "moe": {"router": ((D, s["E"]), D),
                **({"router_bias": ((s["E"],), 0)} if s["bias"] else {})},
        "experts": {"we_gate": ((s["E"], D, s["Fm"]), D), "we_up": ((s["E"], D, s["Fm"]), D),
                    "we_down": ((s["E"], s["Fm"], D), s["Fm"])},
    }[kind]


def seeded_weights(config: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    s = sizes(config)
    rng = jax.random.PRNGKey(seed)

    @partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(key, shape, scale, out_dtype):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(out_dtype)

    def draw(name, layer, shape, fan_in):
        if name == "q_norm":
            return jnp.full(shape, Q_NORM_DRAW, dtype)
        if name.endswith("norm"):
            return jnp.ones(shape, dtype)
        key = jax.random.fold_in(jax.random.fold_in(rng, LEAF_IDS[name]), layer)
        if name == "router_bias":
            return ROUTER_BIAS_DRAW[0] + normal(
                key, shape, ROUTER_BIAS_DRAW[1], jnp.float32)
        return normal(key, shape, 1.0 / math.sqrt(fan_in), dtype)

    @partial(jax.jit, donate_argnums=(0,))
    def put(stack, w, i):
        return jax.lax.dynamic_update_index_in_dim(stack, w, i, 0)

    D = s["D"]
    w: dict = {"embed": draw("embed", 0, (s["V"], D), D), "final_norm": jnp.ones((D,), dtype)}
    for kind in KINDS:
        layers = layers_of(s, kind)
        if not layers:
            continue
        w[kind] = {}
        for name, (shape, fan_in) in leaf_shapes(s, kind).items():
            first = draw(name, layers[0], shape, fan_in)
            stack = jnp.zeros((len(layers),) + shape, first.dtype)
            for row, layer in enumerate(layers):
                stack = put(stack, first if row == 0 else draw(name, layer, shape, fan_in),
                            row)
            w[kind][name] = stack
    return w


def layer_weights(weights: dict, s: dict, layer: int, compute=jnp.float32) -> dict:
    """Layer ``layer``'s leaves in ``compute`` (the selection bias in float32)."""
    out = {}
    for kind in ("norms", *s["kinds"][layer],
                 *(("experts",) if s["kinds"][layer][1] == "moe" else ())):
        row = layers_of(s, kind).index(layer)
        for name, stack in weights[kind].items():
            out[name] = stack[row].astype(jnp.float32 if name == "router_bias" else compute)
    return out


def to_f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, weight, eps):
    y = to_f32(x)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps) * to_f32(weight)
    return y.astype(x.dtype)


def short_conv(h, lw, s):
    """The gated short convolution: the sum of K shifted products, zeros
    before the first token."""
    t, D, K = h.shape[0], s["D"], s["K"]
    p = h @ lw["w_in"]
    b, c, u = p[:, :D], p[:, D: 2 * D], p[:, 2 * D:]
    g = to_f32(b * u)
    padded = jnp.concatenate([jnp.zeros((K - 1, D), jnp.float32), g])
    conv = sum(padded[j: j + t] * to_f32(lw["conv_w"][j]) for j in range(K))
    return (to_f32(c) * conv).astype(h.dtype) @ lw["w_out"]


def rope(x, theta):
    """``x [T, heads, dh]`` at positions 0..T-1, rotate-half over all dh."""
    t, _, dh = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = to_f32(x[..., : dh // 2]), to_f32(x[..., dh // 2:])
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def attention(h, lw, s):
    t, A, KH, dh = h.shape[0], s["A"], s["KH"], s["dh"]
    q = rms_norm((h @ lw["wq"]).reshape(t, A, dh), lw["q_norm"], s["eps"])
    k = rms_norm((h @ lw["wk"]).reshape(t, KH, dh), lw["k_norm"], s["eps"])
    q, k = rope(q, s["theta"]), rope(k, s["theta"])
    v = (h @ lw["wv"]).reshape(t, KH, dh)
    k, v = jnp.repeat(k, A // KH, axis=1), jnp.repeat(v, A // KH, axis=1)
    scores = jnp.einsum("iad,jad->aij", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(dh)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    out = jnp.einsum("aij,jad->iad", jax.nn.softmax(scores, axis=-1).astype(h.dtype), v)
    return out.reshape(t, A * dh) @ lw["wo"]


def swiglu(h, gate, up, down):
    act = (jax.nn.silu(to_f32(h @ gate)) * to_f32(h @ up)).astype(h.dtype)
    return act @ down


def router_weights(h, lw, s):
    """``[T, E]`` float32: an expert's weight for a token, 0 where the
    token did not choose it."""
    scores = jax.nn.sigmoid(to_f32(h) @ to_f32(lw["router"]))
    choice = scores + lw["router_bias"] if s["bias"] else scores
    _, ids = jax.lax.top_k(choice, s["top_k"])
    top = jnp.take_along_axis(scores, ids, axis=-1)
    if s["renorm"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + RENORM_GUARD)
    top = top * s["scaling"]
    return jnp.zeros_like(scores).at[jnp.arange(h.shape[0])[:, None], ids].set(top)


def experts(h, lw, s):
    """Every expert over every row, weighed by exactly 0 where the router
    did not pick it: the sum is the sparse one, nothing is gathered."""
    weight = router_weights(h, lw, s)

    def one(y, e):
        gate, up, down, w = e
        return y + w[:, None] * to_f32(swiglu(h, gate, up, down)), None

    y, _ = jax.lax.scan(one, jnp.zeros(h.shape, jnp.float32),
                        (lw["we_gate"], lw["we_up"], lw["we_down"], weight.T))
    return y.astype(h.dtype)


def layer(x, lw, s, kind):
    mixer, ffn = kind
    h = rms_norm(x, lw["op_norm"], s["eps"])
    x = x + (short_conv(h, lw, s) if mixer == "conv" else attention(h, lw, s))
    h = rms_norm(x, lw["ffn_norm"], s["eps"])
    return x + (swiglu(h, lw["w1"], lw["w3"], lw["w2"]) if ffn == "dense"
                else experts(h, lw, s))


def hidden(config: dict, weights: dict, tokens, compute=jnp.float32):
    """The last norm's output ``[T, D]`` in ``compute``."""
    s = _frozen(sizes(config))
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(tokens, jnp.int32)].astype(compute)
        for i in range(s["n"]):
            x = _layer_jit(x, layer_weights(weights, s, i, compute), s, s["kinds"][i])
        return rms_norm(x, weights["final_norm"], s["eps"])


def logit_blocks(weights: dict, x, compute=jnp.float32):
    """The logits of ``x [T, D]`` a block of vocabulary rows of the
    embedding at a time, float32, each block rounded to ``compute`` first
    as it would be stored."""
    embed = weights["embed"]
    for lo in range(0, embed.shape[0], HEAD_BLOCK):
        yield _head_jit(x, embed[lo: lo + HEAD_BLOCK], jnp.dtype(compute))


def forward(config: dict, weights: dict, tokens, compute=jnp.float32) -> jnp.ndarray:
    """Logits ``[T, V]`` in float32 for one sequence, computed in ``compute``."""
    x = hidden(config, weights, tokens, compute)
    return jnp.concatenate(list(logit_blocks(weights, x, compute)), axis=-1)


def forward_many(config: dict, weights: dict, sequences: list, compute=jnp.float32) -> list:
    """``forward`` of each sequence, one at a time. Every sequence is run at
    the length of the longest, filled up with token 0 behind its end and cut
    back: nothing here looks ahead, so the rows are those of the sequence
    alone, and the layers compile for one length."""
    import numpy as np

    longest = max(len(seq) for seq in sequences)
    out = []
    for seq in sequences:
        x = hidden(config, weights, list(seq) + [0] * (longest - len(seq)), compute)
        out.append(np.concatenate(
            [np.asarray(block[: len(seq)]) for block in logit_blocks(weights, x, compute)],
            axis=-1))
    return out


class _frozen(dict):
    """The sizes as a static argument of the layer's jit."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


@partial(jax.jit, static_argnums=(2, 3))
def _layer_jit(x, lw, s, kind):
    with jax.default_matmul_precision("highest"):
        return layer(x, lw, s, kind)


@partial(jax.jit, static_argnums=(2,))
def _head_jit(x, rows, compute):
    with jax.default_matmul_precision("highest"):
        return to_f32((x @ rows.astype(compute).T).astype(compute))
