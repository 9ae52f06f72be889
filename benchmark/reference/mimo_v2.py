"""Plain reference of the MiMo-V2 decoder's language model (``model_type``
``mimo_v2``, huggingface.co/XiaomiMiMo/MiMo-V2.5): the forward pass in
float32 ``jax.numpy``, one sequence at a time, full ``[T, T]`` masks, keys
and values expanded a head; no cache, no pages, no kernel, no batching.
Imports nothing from ``vllm_tpu``.

No implementation of this family is on this machine. The equations are
written from the published ``config.json``; what its keys do not say is
the family's convention and is listed under ``assumed`` in
``benchmark/configs/mimo-v2.5.json`` (which value of
``hybrid_layer_pattern`` is the window, the rotated lanes, the scale, the
sink's form, where the value scale multiplies, no q/k norm).

Token states ``x`` [T, D] (D 4096). ``rms`` has a learned scale, eps
``layernorm_epsilon`` 1e-5. Every layer is ``x = x + attention(rms(x))``,
``x = x + ffn(rms(x))``; after the last, ``rms(x)`` and the untied head.

Attention of layer ``l``, ``h = rms(x)``. ``hybrid_layer_pattern[l]`` 0 is
a FULL layer (4 KV heads, ``num_key_value_heads``), 1 a WINDOW layer (8,
``swa_num_key_value_heads``); 64 query heads in both:

    q = h Wq as [T, 64, 192];  k = h Wk as [T, KH, 192];
    v = 0.707 (h Wv) as [T, KH, 128]   (``attention_value_scale``)
    rope, rotate-half, on the first r = int(0.334 x 192) = 64 lanes of q
    and k (the other 128 pass), theta 10,000,000 in a full layer and
    10,000 in a window layer (``swa_rope_theta``), no scaling
    query head a reads KV head a // (64 / KH)
    scores q k^T / sqrt(192), causal; a window layer keeps key j for
    query i only where i - j < 128 (``sliding_window``), and its head a
    has a learned logit s_a (``add_swa_attention_sink_bias``):
        p_ij = exp(score_ij) / (exp(s_a) + sum_k exp(score_ik))
    the sink joins the denominator and has no value; a full layer has
    none. o_i = sum_j p_ij v_j (128 lanes a head), then Wo [64 x 128, D].

FFN. Where ``moe_layer_freq[l]`` is 0 (layer 0): ``Wdown(silu(Wgate h) *
(Wup h))`` of width 16,384. Elsewhere: ``s = sigmoid(h Wr)`` in float32
over ALL the router's outputs (``n_routed_experts * ep_size``); the 8
largest of ``s + b`` (``b`` the selection bias, ``noaux_tc``; one group:
no limit); weights = the chosen ``s`` WITHOUT ``b`` divided by their sum
(``norm_topk_prob``), times ``routed_scaling_factor`` (null: 1); ``y =
sum_e w_e expert_e(h)``, each a SwiGLU of width 2048; no shared expert.
Under a share (``ep_size`` > 1) only the experts ``e`` in ``[ep_rank *
n_routed_experts, + n_routed_experts)`` are summed; the weights are still
normalised over all 8. That partial sum is what goes on to the next layer,
as in the program. The held experts are a ``lax.scan`` over their stack,
each over every row, cast to the computing type one at a time.

Weights. ``seeded_weights`` is the benchmark's own copy of how
``load_format=dummy`` draws this family: leaf ``name`` of layer ``i`` is
``normal(fold_in(fold_in(key(seed), id(name)), i)) / sqrt(fan_in)`` in the
served dtype, an expert stack drawn a layer at a time; norms are ones. Two
matrices are drawn at another width (``DRAW_WIDTHS``), as
``reference/laguna.py``'s and for its reasons: ``Wq`` at 2 / sqrt(fan_in),
because with unit-variance scores a softmax over many keys is nearly flat,
a layer returns the mean of its values and a window reads nearly as the
whole context; the routed experts' ``W_down`` at 0.25 / sqrt(fan_in),
because bf16 chooses another expert than float32 where two scores are
close and at unit width one such choice moves every later layer's. Two
leaves are no matrices and are kept in float32: a sink logit is uniform in
[4.5, 7) (at that width of ``Wq`` a window row's 128 scores have a
log-sum-exp near 6.85: the sink takes a tenth to a half of the row), the
selection bias normal at 0.01 (near the eighth of 256 sigmoid scores
neighbours stand some 0.007 apart: it changes some tenth of the choices).
The tree has the program's own layout (norms by layer, the attention
weights stacked BY KIND because the kinds differ in shape, the dense
layers, the routers, the experts in one stack), so that the CPU test
compares leaf by leaf; ``layer_weights`` reads one layer out of it.

On a TPU a float32 matmul runs in lower precision unless told otherwise,
so everything here runs under ``jax.default_matmul_precision("highest")``.
Attention runs a KV head at a time (its 8 or 16 query heads against one
``[T, T]`` mask), the experts one at a time and the head's logits a slice
of the vocabulary at a time: the same sums, in blocks, so that four
sequences of up to 2,048 positions fit beside 12.0 GB of weights.

``compute``. ``forward`` and ``forward_many`` take the type the activations
are kept in, float32 unless said: the reference. With ``compute`` the served
type (bfloat16) the SAME formulae run with weights and activations in that
type, every product of matrices rounded to it as it is stored, and in
float32 only what the program keeps in float32: the norms' and the softmax's
arithmetic, the sink, the router's scores and the routed sum, the logits.
That pass is not the reference and decides nothing: ``harness/checker.py``
reads it for a cell whose limits name a factor. In float32 every cast below
is the identity.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

LEAF_IDS = {
    name: i for i, name in enumerate((
        "embed", "lm_head", "wq", "wk", "wv", "wo", "sinks",
        "wgate", "wup", "wdown", "router", "router_bias",
        "we_gate", "we_up", "we_down",
    ))
}

# Leaves drawn at another width than 1 / sqrt(fan_in), times that, and the
# two that are no matrices (the docstring says why).
DRAW_WIDTHS = {"wq": 2.0, "we_down": 0.25}
SINK_RANGE = (4.5, 7.0)
ROUTER_BIAS_WIDTH = 0.01

FULL, WINDOW = "full", "window"


def sizes(config: dict) -> dict:
    L = config["num_hidden_layers"]
    kinds = tuple(WINDOW if p else FULL for p in config["hybrid_layer_pattern"][:L])
    sparse = tuple(bool(f) for f in config["moe_layer_freq"][:L])
    ep = int(config.get("ep_size", 1) or 1)
    held = config["n_routed_experts"]
    swa = lambda key: config.get("swa_" + key, config[key])
    return {
        "L": L, "D": config["hidden_size"], "V": config["vocab_size"],
        "eps": config["layernorm_epsilon"], "kinds": kinds, "sparse": sparse,
        "H": {FULL: config["num_attention_heads"], WINDOW: swa("num_attention_heads")},
        "KH": {FULL: config["num_key_value_heads"], WINDOW: swa("num_key_value_heads")},
        "Dk": config["head_dim"], "Dv": config["v_head_dim"],
        "window": config["sliding_window"],
        "sink": {FULL: bool(config.get("add_full_attention_sink_bias")),
                 WINDOW: bool(config.get("add_swa_attention_sink_bias"))},
        "value_scale": float(config.get("attention_value_scale") or 1.0),
        "rotary": int(config.get("partial_rotary_factor", 1.0) * config["head_dim"]),
        "theta": {FULL: config["rope_theta"],
                  WINDOW: config.get("swa_rope_theta", config["rope_theta"])},
        "F": config["intermediate_size"], "Fm": config["moe_intermediate_size"],
        "E": held * ep, "held": held,
        "first": int(config.get("ep_rank", 0) or 0) * held,
        "top_k": config["num_experts_per_tok"],
        "scaling": config.get("routed_scaling_factor") or 1.0,
        "renormalize": config.get("norm_topk_prob", True),
    }


def leaf_shapes(s: dict, kind: str) -> dict:
    """Leaf -> (shape, fan_in; 0 = no normal at 1 / sqrt(fan_in))."""
    D, Dk, Dv = s["D"], s["Dk"], s["Dv"]
    if kind in (FULL, WINDOW):
        H, KH = s["H"][kind], s["KH"][kind]
        out = {"wq": ((D, H * Dk), D), "wk": ((D, KH * Dk), D),
               "wv": ((D, KH * Dv), D), "wo": ((H * Dv, D), H * Dv)}
        if s["sink"][kind]:
            out["sinks"] = ((H,), 0)
        return out
    if kind == "dense":
        return {"wgate": ((D, s["F"]), D), "wup": ((D, s["F"]), D),
                "wdown": ((s["F"], D), s["F"])}
    if kind == "moe":
        return {"router": ((D, s["E"]), D), "router_bias": ((s["E"],), 0)}
    if kind == "norms":
        return {"input_norm": ((D,), 0), "post_norm": ((D,), 0)}
    if kind == "experts":
        E, Fm = s["held"], s["Fm"]
        return {"we_gate": ((E, D, Fm), D), "we_up": ((E, D, Fm), D),
                "we_down": ((E, Fm, D), Fm)}
    raise KeyError(kind)


def seeded_weights(config: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    s = sizes(config)
    rng = jax.random.PRNGKey(seed)

    @partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(key, shape, scale, out_dtype):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(out_dtype)

    def draw(name, layer, shape, fan_in):
        key = jax.random.fold_in(jax.random.fold_in(rng, LEAF_IDS.get(name, 0)), layer)
        if name == "sinks":
            return jax.random.uniform(key, shape, jnp.float32, *SINK_RANGE)
        if name == "router_bias":
            return normal(key, shape, ROUTER_BIAS_WIDTH, jnp.float32)
        if not fan_in:
            return jnp.ones(shape, dtype)
        return normal(key, shape, DRAW_WIDTHS.get(name, 1.0) / math.sqrt(fan_in), dtype)

    def stack_of(kind, layers):
        return {name: jnp.stack([draw(name, i, shape, fan_in) for i in layers])
                for name, (shape, fan_in) in leaf_shapes(s, kind).items()}

    @partial(jax.jit, donate_argnums=(0,))
    def put(stack, w, i):
        return jax.lax.dynamic_update_index_in_dim(stack, w, i, 0)

    D, L = s["D"], s["L"]
    w: dict = {
        "embed": draw("embed", 0, (s["V"], D), D),
        "final_norm": jnp.ones((D,), dtype),
        "norms": stack_of("norms", list(range(L))),
        "lm_head": draw("lm_head", 0, (D, s["V"]), D),
    }
    for kind in (FULL, WINDOW):
        layers = [i for i in range(L) if s["kinds"][i] == kind]
        if layers:
            w[kind] = stack_of(kind, layers)
    dense = [i for i in range(L) if not s["sparse"][i]]
    if dense:
        w["dense"] = stack_of("dense", dense)
    sparse = [i for i in range(L) if s["sparse"][i]]
    if sparse:
        w["moe"] = stack_of("moe", sparse)
        w["experts"] = {}
        for name, (shape, fan_in) in leaf_shapes(s, "experts").items():
            stack = jnp.zeros((len(sparse),) + shape, dtype)
            for row, layer in enumerate(sparse):
                stack = put(stack, draw(name, layer, shape, fan_in), row)
            w["experts"][name] = stack
    return w


def layer_weights(config: dict, weights: dict, layer: int, compute=jnp.float32) -> dict:
    """Layer ``layer``'s leaves as ``{"norms", "attn", "ffn", "experts"}``:
    all in ``compute`` but the experts, which the routed sum casts one at a
    time (a layer's sixteen are 1.6 GB in float32)."""
    s = sizes(config)
    cast = lambda tree: jax.tree.map(lambda x: x.astype(compute), tree)
    kind, sparse = s["kinds"][layer], s["sparse"][layer]
    at = sum(k == kind for k in s["kinds"][:layer])
    ffn = sum(sp == sparse for sp in s["sparse"][:layer])
    pick = lambda tree, i: jax.tree.map(lambda x: x[i], tree)
    attn = pick(weights[kind], at)
    out = {"norms": cast(pick(weights["norms"], layer)),
           "attn": {**cast(attn), **({"sinks": attn["sinks"]} if "sinks" in attn else {})},
           "experts": None}
    if sparse:
        moe = pick(weights["moe"], ffn)
        out["ffn"] = {"router": moe["router"].astype(compute),
                      "router_bias": moe["router_bias"]}
        out["experts"] = pick(weights["experts"], ffn)
    else:
        out["ffn"] = cast(pick(weights["dense"], ffn))
    return out


def to_f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, weight, eps):
    y = to_f32(x)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps) * to_f32(weight)
    return y.astype(x.dtype)


def rope_tables(config: dict, kind: str, length: int):
    """``(cos, sin) [length, r / 2]`` and ``r``, the lanes rotated."""
    s = sizes(config)
    r = s["rotary"]
    freq = float(s["theta"][kind]) ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    angle = np.outer(np.arange(length, dtype=np.float64), freq)
    return jnp.asarray(np.cos(angle), jnp.float32), jnp.asarray(np.sin(angle), jnp.float32), r


def rotate(x, cos, sin, r):
    """Rotate-half over the first ``r`` lanes of ``x [T, heads, Dk]``."""
    x1, x2, rest = to_f32(x[..., : r // 2]), to_f32(x[..., r // 2: r]), x[..., r:]
    c, s_ = cos[:, None, :], sin[:, None, :]
    turned = jnp.concatenate([x1 * c - x2 * s_, x2 * c + x1 * s_], axis=-1)
    return jnp.concatenate([turned.astype(x.dtype), rest], axis=-1)


def attention(h, lw, kind, s, cos, sin, r):
    t = h.shape[0]
    H, KH, Dk, Dv = s["H"][kind], s["KH"][kind], s["Dk"], s["Dv"]
    g = H // KH
    q = rotate((h @ lw["wq"]).reshape(t, H, Dk), cos, sin, r)
    k = rotate((h @ lw["wk"]).reshape(t, KH, Dk), cos, sin, r)
    v = (h @ lw["wv"]).reshape(t, KH, Dv) * jnp.asarray(s["value_scale"], h.dtype)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = j <= i
    if kind == WINDOW:
        mask = mask & (i - j < s["window"])
    sinks = lw.get("sinks")
    sinks = (jnp.full((KH, g), -jnp.inf, jnp.float32) if sinks is None
             else to_f32(sinks).reshape(KH, g))

    def one_kv_head(args):  # its H / KH query heads against one mask
        qg, kh, vh, sink = args  # [T, g, Dk], [T, Dk], [T, Dv], [g]
        scores = jnp.einsum("tgd,sd->gts", qg, kh,
                            preferred_element_type=jnp.float32) / math.sqrt(Dk)
        scores = jnp.where(mask[None], scores, -jnp.inf)
        # The sink: one more term of the denominator, with no value.
        m = jnp.maximum(scores.max(-1), sink[:, None])  # [g, T]
        e = jnp.exp(scores - m[..., None])
        p = e / (e.sum(-1) + jnp.exp(sink[:, None] - m))[..., None]
        return jnp.einsum("gts,sd->tgd", p.astype(vh.dtype), vh)

    qg = q.reshape(t, KH, g, Dk).transpose(1, 0, 2, 3)
    o = jax.lax.map(one_kv_head, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2), sinks))
    o = o.transpose(1, 0, 2, 3).reshape(t, H * Dv)  # [KH, T, g, Dv] -> heads in order
    return o.astype(h.dtype) @ lw["wo"]


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(h, lw, s):
    """The routing weights ``[T, E]`` over the whole router: the chosen
    scores without the bias, renormalised and scaled, 0 elsewhere."""
    scores = jax.nn.sigmoid(to_f32(h) @ to_f32(lw["router"]))  # [T, E]
    _, ids = jax.lax.top_k(scores + to_f32(lw["router_bias"])[None], s["top_k"])
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    if s["renormalize"]:
        picked = picked / picked.sum(-1, keepdims=True)
    picked = picked * s["scaling"]
    return jnp.zeros_like(scores).at[jnp.arange(h.shape[0])[:, None], ids].set(picked)


def routed(h, lw, experts, s):
    """The held experts' part of the routed sum."""
    weight = route(h, lw, s)

    def one(y, x):  # one expert at a time over every row
        w_e, gate, up_, down = x
        c = lambda m: m.astype(h.dtype)
        return y + w_e[:, None] * to_f32(swiglu(h, c(gate), c(up_), c(down))), None

    held = weight[:, s["first"]: s["first"] + s["held"]].T  # [held, T]
    y, _ = jax.lax.scan(one, jnp.zeros(h.shape, jnp.float32), (
        held, experts["we_gate"], experts["we_up"], experts["we_down"]))
    return y.astype(h.dtype)


def layer(x, lw, kind, s, cos, sin, r):
    h = rms_norm(x, lw["norms"]["input_norm"], s["eps"])
    x = x + attention(h, lw["attn"], kind, s, cos, sin, r)
    h = rms_norm(x, lw["norms"]["post_norm"], s["eps"])
    if lw["experts"] is None:
        f = lw["ffn"]
        return x + swiglu(h, f["wgate"], f["wup"], f["wdown"])
    return x + routed(h, lw["ffn"], lw["experts"], s)


def forward(config: dict, weights: dict, tokens, compute=jnp.float32) -> np.ndarray:
    """Logits ``[T, V]`` in float32 for one sequence, computed in
    ``compute``, on the host."""
    s = sizes(config)
    t = len(tokens)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(tokens, jnp.int32)].astype(compute)
        ropes = {kind: rope_tables(config, kind, t) for kind in (FULL, WINDOW)}
        for i, kind in enumerate(s["kinds"]):
            x = _layer_jit(x, layer_weights(config, weights, i, compute), kind, _frozen(s),
                           *ropes[kind])
        x = rms_norm(x, weights["final_norm"], s["eps"])
        # The head a slice of the vocabulary at a time, cast as it is used.
        step = -(-s["V"] // 8)
        return np.concatenate([
            np.asarray(_head_jit(x, weights["lm_head"][:, lo: lo + step].astype(compute)))
            for lo in range(0, s["V"], step)], axis=-1)


def forward_many(config: dict, weights: dict, sequences: list, compute=jnp.float32) -> list:
    """``forward`` of each sequence, one at a time. Every sequence is run
    at the length of the longest, filled up with token 0 behind its end
    and cut back: nothing here looks ahead, so the rows are those of the
    sequence alone, and the layers compile for one length, not for each.
    Each result is on the host as it is made."""
    longest = max(len(seq) for seq in sequences)
    return [forward(config, weights, list(seq) + [0] * (longest - len(seq)),
                    compute)[: len(seq)]
            for seq in sequences]


class _frozen(dict):
    """The sizes as a static argument of the layer's jit."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


@partial(jax.jit, static_argnums=(2, 3, 6))
def _layer_jit(x, lw, kind, s, cos, sin, r):
    with jax.default_matmul_precision("highest"):
        return layer(x, lw, kind, s, cos, sin, r)


@jax.jit
def _head_jit(x, head):
    with jax.default_matmul_precision("highest"):
        return jnp.matmul(x, head, preferred_element_type=jnp.float32)
