"""Plain reference of the Laguna decoder (``model_type`` ``laguna``,
huggingface.co/poolside/Laguna-XS.2): the forward pass in float32
``jax.numpy``, one sequence at a time, full ``[T, T]`` masks, keys and
values expanded a head; no cache, no pages, no kernel, no batching. Imports
nothing from ``vllm_tpu``.

No implementation of this family is on this machine. The equations are
written from the published ``config.json``; what its keys do not say is
the family's convention and is listed under ``assumed`` in
``benchmark/configs/laguna-xs.2.json`` (the gate's form, the routing's
renormalisation, no q/k norm, rotate-half lanes).

Token states ``x`` [T, D] (D 2048). ``rms`` has a learned scale, eps 1e-6.
Every layer is ``x = x + attention(rms(x))``, ``x = x + ffn(rms(x))``;
after the last, ``rms(x)`` and the untied head.

Attention of layer ``l``, ``h = rms(x)``, ``n = num_attention_heads_per_
layer[l]`` (48 where ``layer_types[l]`` is ``full_attention``, 64 where it
is ``sliding_attention``), 8 KV heads of 128 in every layer:

    q = h Wq as [T, n, 128];  k = h Wk, v = h Wv as [T, 8, 128]
    rope, rotate-half, on the first ``r`` lanes of q and k (the others
    pass): a full layer r = 64 (``partial_rotary_factor`` 0.5) with YaRN,
    a window layer r = 128 with plain rope, theta 10000.
      YaRN (theta 500000, factor 64, original 4096, beta_fast 64,
      beta_slow 1) over the r / 2 frequencies f_i = theta^(-2i / r):
      d(b) = r ln(original / (2 pi b)) / (2 ln theta); lo = floor(d(64)),
      hi = ceil(d(1)), both clipped to [0, r - 1]; ramp_i = clip((i - lo) /
      (hi - lo), 0, 1); f_i <- f_i / factor * ramp_i + f_i * (1 - ramp_i);
      cos and sin times ``attention_factor`` (0.1 ln 64 + 1 = 1.4158883).
    query head a reads KV head a // (n / 8)
    scores q k^T / sqrt(128), causal; a window layer keeps key j for query
    i only where i - j < 512 (``sliding_window``)
    g = sigmoid(h Wg) [T, n]: head a's output times g[:, a]; then Wo.

FFN. Layer 0: ``Wdown(silu(Wgate h) * (Wup h))`` of width 8192. Every other
layer: ``s = sigmoid(h Wr)`` in float32 over ALL the router's outputs
(``num_experts * ep_size``); the 8 largest; weights = those 8 divided by
their sum, times ``moe_routed_scaling_factor`` 2.5; ``y = sum_e w_e
expert_e(h) + shared(h)``, each a SwiGLU of width 512 (the weight
multiplies the expert's OUTPUT). Under a share (``ep_size`` > 1) only the
experts ``e`` in ``[ep_rank * num_experts, + num_experts)`` are summed; the
weights are still normalised over all 8. That partial sum is what goes on
to the next layer, as in the program. The held experts are a ``lax.scan``
over their stack, each over every row.

Weights. ``seeded_weights`` is the benchmark's own copy of how
``load_format=dummy`` draws this family: leaf ``name`` of layer ``i`` is
``normal(fold_in(fold_in(key(seed), id(name)), i)) / sqrt(fan_in)`` in the
served dtype, an expert stack drawn a layer at a time; norms are ones. Two
leaves are drawn at another width (``DRAW_WIDTHS``), as
``reference/kimi_linear.py``'s and for its reasons: ``Wq`` at 2 /
sqrt(fan_in), because with unit-variance scores a softmax over a thousand
keys is nearly flat, a layer returns the mean of its values and a window of
512 keys reads nearly as the whole context (served as full attention, the
window layers moved the median error by 1.9 times at unit width and 2.6 at
2, eight layers on the CPU); not at Kimi-Linear's 4, where one key carries
a row in every one of the 40 layers, bf16's rounding flips which, and the
served logprobs stood 1.1 from these on the chip (PERF.md, PR 43); the
routed experts' ``W_down`` at 0.25 / sqrt(fan_in), because bf16 chooses
another expert than float32 where two scores are close and at unit width
one such choice moves every later layer's. The tree has the
program's own layout (layer 0 alone, the periods stacked BY KIND because
the kinds differ in shape, the tail, the experts in one stack), so that the
CPU test compares leaf by leaf; ``layer_weights`` reads one layer out of
it, cast to float32 as it is used: the 11.9 GB of bf16 stay as they are.

On a TPU a float32 matmul runs in lower precision unless told otherwise,
so everything here runs under ``jax.default_matmul_precision("highest")``.
Attention runs a KV head at a time (its 6 or 8 query heads against one
``[T, T]`` mask) and the head's logits a slice of the vocabulary at a time:
the same sums, in blocks, so that the scores and the logits of 3,000 tokens
fit beside the weights.

``compute``. ``forward`` and ``forward_many`` take the type the activations
are kept in, float32 unless said: the reference. With ``compute`` the served
type (bfloat16) the SAME formulae run with weights and activations in that
type, every product of matrices rounded to it as it is stored, and in
float32 only what the program keeps in float32: the norms' and the softmax's
arithmetic, the gate, the router's scores and the routed sum, the logits.
That pass is not the reference and decides nothing: its distance from the
float32 pass at the served tokens is what rounding to the served type costs
a seed's weights, the WITNESS of why ``Wq`` is not drawn at Kimi-Linear's 4
(PERF.md section 4), and ``harness/checker.py`` reads it for a cell whose
limits name a factor. In float32 every cast below is the identity, so the
reference is what it was to the bit.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

LEAF_IDS = {
    name: i for i, name in enumerate((
        "embed", "lm_head", "wq", "wk", "wv", "wg", "wo",
        "wgate", "wup", "wdown",
        "router", "ws_gate", "ws_up", "ws_down",
        "we_gate", "we_up", "we_down",
    ))
}

# Leaves drawn at another width than 1 / sqrt(fan_in), times that (the
# docstring says why).
DRAW_WIDTHS = {"wq": 2.0, "we_down": 0.25}

FULL, WINDOW = "full_attention", "sliding_attention"


def sizes(config: dict) -> dict:
    kinds = list(config["layer_types"])
    heads = list(config["num_attention_heads_per_layer"])
    fulls = [i for i, kind in enumerate(kinds) if kind == FULL]
    k = fulls[1] - 1 if len(fulls) > 1 else len(kinds) - 1
    periods = len(fulls) - 1
    ep = int(config.get("ep_size", 1) or 1)
    held = config["num_experts"]
    return {
        "L": config["num_hidden_layers"], "D": config["hidden_size"],
        "V": config["vocab_size"], "Dh": config["head_dim"],
        "KH": config["num_key_value_heads"], "eps": config["rms_norm_eps"],
        "kinds": tuple(kinds), "heads": tuple(heads),
        "H": {kind: heads[kinds.index(kind)] for kind in (FULL, WINDOW)},
        "window": config["sliding_window"],
        "k": k, "periods": periods, "tail": len(kinds) - 1 - periods * (k + 1),
        "F": config["intermediate_size"], "Fm": config["moe_intermediate_size"],
        "Fs": config.get("shared_expert_intermediate_size", 0) or 0,
        "E": held * ep, "held": held,
        "first": int(config.get("ep_rank", 0) or 0) * held,
        "top_k": config["num_experts_per_tok"],
        "scaling": config.get("moe_routed_scaling_factor", 1.0),
        "max_pos": config["max_position_embeddings"],
    }


def leaf_shapes(s: dict, kind: str) -> dict:
    """Leaf -> (shape, fan_in; 0 = ones)."""
    D, Dh, KH = s["D"], s["Dh"], s["KH"]
    if kind in (FULL, WINDOW):
        H = s["H"][kind]
        return {"wq": ((D, H * Dh), D), "wk": ((D, KH * Dh), D),
                "wv": ((D, KH * Dh), D), "wg": ((D, H), D),
                "wo": ((H * Dh, D), H * Dh)}
    if kind == "dense":
        return {"wgate": ((D, s["F"]), D), "wup": ((D, s["F"]), D),
                "wdown": ((s["F"], D), s["F"])}
    if kind == "moe":
        out = {"router": ((D, s["E"]), D)}
        if s["Fs"]:
            out |= {"ws_gate": ((D, s["Fs"]), D), "ws_up": ((D, s["Fs"]), D),
                    "ws_down": ((s["Fs"], D), s["Fs"])}
        return out
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
        if not fan_in:
            return jnp.ones(shape, dtype)
        key = jax.random.fold_in(jax.random.fold_in(rng, LEAF_IDS[name]), layer)
        return normal(key, shape, DRAW_WIDTHS.get(name, 1.0) / math.sqrt(fan_in), dtype)

    def leaves(layer, kind):
        return {name: draw(name, layer, shape, fan_in)
                for name, (shape, fan_in) in leaf_shapes(s, kind).items()}

    def stacked(rows):
        if isinstance(rows, dict):
            return rows
        below = [stacked(row) for row in rows]
        return {name: jnp.stack([b[name] for b in below]) for name in below[0]}

    @partial(jax.jit, donate_argnums=(0,))
    def put(stack, w, i):
        return jax.lax.dynamic_update_index_in_dim(stack, w, i, 0)

    D, k, P = s["D"], s["k"], s["periods"]
    w: dict = {
        "embed": draw("embed", 0, (s["V"], D), D),
        "final_norm": jnp.ones((D,), dtype),
        "lm_head": draw("lm_head", 0, (D, s["V"]), D),
        "first": {"norms": leaves(0, "norms"), "attn": leaves(0, FULL),
                  "dense": leaves(0, "dense")},
    }
    first_of = lambda rep: 1 + rep * (k + 1)
    if P:
        w["periods"] = {
            "norms": stacked([[leaves(first_of(r) + o, "norms") for o in range(k + 1)]
                              for r in range(P)]),
            "window": stacked([[leaves(first_of(r) + o, WINDOW) for o in range(k)]
                               for r in range(P)]),
            "full": stacked([leaves(first_of(r) + k, FULL) for r in range(P)]),
            "moe": stacked([[leaves(first_of(r) + o, "moe") for o in range(k + 1)]
                            for r in range(P)]),
        }
    if s["tail"]:
        start = first_of(P)
        w["tail"] = {kind: stacked([leaves(start + o, name) for o in range(s["tail"])])
                     for kind, name in (("norms", "norms"), ("window", WINDOW),
                                        ("moe", "moe"))}
    w["experts"] = {}
    for name, (shape, fan_in) in leaf_shapes(s, "experts").items():
        stack = jnp.zeros((s["L"] - 1,) + shape, dtype)
        for layer in range(1, s["L"]):
            stack = put(stack, draw(name, layer, shape, fan_in), layer - 1)
        w["experts"][name] = stack
    return w


def layer_weights(config: dict, weights: dict, layer: int, compute=jnp.float32) -> dict:
    """Layer ``layer``'s leaves as ``{"norms", "attn", "ffn", "experts"}`` in
    ``compute``."""
    s = sizes(config)
    f32 = lambda tree: jax.tree.map(lambda x: x.astype(compute), tree)
    if layer == 0:
        one = weights["first"]
        return f32({"norms": one["norms"], "attn": one["attn"], "ffn": one["dense"],
                    "experts": None})
    rep, off = divmod(layer - 1, s["k"] + 1)
    if rep < s["periods"]:
        at = lambda kind, *idx: jax.tree.map(
            lambda x: x[(rep, *idx)], weights["periods"][kind])
        attn = at("full") if s["kinds"][layer] == FULL else at("window", off)
        out = {"norms": at("norms", off), "attn": attn, "ffn": at("moe", off)}
    else:
        at = lambda kind: jax.tree.map(lambda x: x[off], weights["tail"][kind])
        out = {"norms": at("norms"), "attn": at("window"), "ffn": at("moe")}
    out["experts"] = jax.tree.map(lambda x: x[layer - 1], weights["experts"])
    return f32(out)


def to_f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, weight, eps):
    y = to_f32(x)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps) * to_f32(weight)
    return y.astype(x.dtype)


def rope_tables(config: dict, kind: str, length: int):
    """``(cos, sin) [length, r / 2]`` and ``r``, the lanes rotated."""
    p = config["rope_parameters"][kind]
    r = int(config["head_dim"] * p.get("partial_rotary_factor", 1))
    theta = float(p["rope_theta"])
    freq = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    factor = 1.0
    if p.get("rope_type", "default") == "yarn":
        orig = p["original_max_position_embeddings"]
        dim = lambda b: r * math.log(orig / (2 * math.pi * b)) / (2 * math.log(theta))
        lo = max(math.floor(dim(p["beta_fast"])), 0)
        hi = min(math.ceil(dim(p["beta_slow"])), r - 1)
        ramp = np.clip((np.arange(r // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
        freq = freq / p["factor"] * ramp + freq * (1 - ramp)
        factor = p["attention_factor"]
    angle = np.outer(np.arange(length, dtype=np.float64), freq)
    return (jnp.asarray(np.cos(angle) * factor, jnp.float32),
            jnp.asarray(np.sin(angle) * factor, jnp.float32), r)


def rotate(x, cos, sin, r):
    """Rotate-half over the first ``r`` lanes of ``x [T, heads, Dh]``."""
    x1, x2, rest = to_f32(x[..., : r // 2]), to_f32(x[..., r // 2: r]), x[..., r:]
    c, s_ = cos[:, None, :], sin[:, None, :]
    turned = jnp.concatenate([x1 * c - x2 * s_, x2 * c + x1 * s_], axis=-1)
    return jnp.concatenate([turned.astype(x.dtype), rest], axis=-1)


def attention(h, lw, kind, s, cos, sin, r):
    t = h.shape[0]
    H, KH, Dh = s["H"][kind], s["KH"], s["Dh"]
    q = rotate((h @ lw["wq"]).reshape(t, H, Dh), cos, sin, r)
    k = rotate((h @ lw["wk"]).reshape(t, KH, Dh), cos, sin, r)
    v = (h @ lw["wv"]).reshape(t, KH, Dh)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = j <= i
    if kind == WINDOW:
        mask = mask & (i - j < s["window"])

    def one_kv_head(args):  # its H / KH query heads against one mask
        qg, kh, vh = args  # [T, H / KH, Dh], [T, Dh], [T, Dh]
        scores = jnp.einsum("tgd,sd->gts", qg, kh,
                            preferred_element_type=jnp.float32) / math.sqrt(Dh)
        p = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->tgd", p.astype(vh.dtype), vh)

    qg = q.reshape(t, KH, H // KH, Dh).transpose(1, 0, 2, 3)
    o = jax.lax.map(one_kv_head, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2, 3).reshape(t, H, Dh)  # [KH, T, g, Dh] -> heads in order
    gate = jax.nn.sigmoid(to_f32(h @ lw["wg"]))  # [T, H]
    return (to_f32(o) * gate[..., None]).astype(h.dtype).reshape(t, H * Dh) @ lw["wo"]


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def routed(h, lw, experts, s):
    """``(the held experts' part of the routed sum + the shared expert,
    the routing weights [T, E] over the whole router)``."""
    scores = jax.nn.sigmoid(to_f32(h) @ to_f32(lw["router"]))  # [T, E]
    picked, ids = jax.lax.top_k(scores, s["top_k"])
    picked = picked / picked.sum(-1, keepdims=True) * s["scaling"]
    weight = jnp.zeros_like(scores).at[jnp.arange(h.shape[0])[:, None], ids].set(picked)

    def one(y, x):  # one expert at a time over every row
        w_e, gate, up_, down = x
        return y + w_e[:, None] * to_f32(swiglu(h, gate, up_, down)), None

    held = weight[:, s["first"]: s["first"] + s["held"]].T  # [held, T]
    y, _ = jax.lax.scan(one, jnp.zeros(h.shape, jnp.float32), (
        held, experts["we_gate"], experts["we_up"], experts["we_down"]))
    y = y.astype(h.dtype)
    if s["Fs"]:
        y = y + swiglu(h, lw["ws_gate"], lw["ws_up"], lw["ws_down"])
    return y, weight


def layer(x, lw, kind, s, cos, sin, r):
    h = rms_norm(x, lw["norms"]["input_norm"], s["eps"])
    x = x + attention(h, lw["attn"], kind, s, cos, sin, r)
    h = rms_norm(x, lw["norms"]["post_norm"], s["eps"])
    if lw["experts"] is None:
        f = lw["ffn"]
        return x + swiglu(h, f["wgate"], f["wup"], f["wdown"])
    return x + routed(h, lw["ffn"], lw["experts"], s)[0]


def forward(config: dict, weights: dict, tokens, compute=jnp.float32) -> np.ndarray:
    """Logits ``[T, V]`` in float32 for one sequence, computed in
    ``compute``, on the host: 3,000 rows of this vocabulary are 1.3 GB,
    which the device does not have twice beside the weights."""
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
