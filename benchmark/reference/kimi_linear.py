"""Plain reference of the Kimi-Linear decoder (``model_type``
``kimi_linear``, huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct):
the forward pass in float32 ``jax.numpy``, one sequence at a time, the
recurrence a token at a time, full causal attention with keys and values
expanded a head; no cache, no chunking, no kernel, no batching. Imports
nothing from ``vllm_tpu``.

Token states ``x`` [T, D] (D 2304). ``rms`` has a learned scale, eps 1e-5.
Every layer is ``x = x + mixer(rms(x))``, ``x = x + ffn(rms(x))``; after
the last, ``rms(x)`` and the untied head. ``linear_attn_config`` names the
layers of each kind, counted from 1.

KDA mixer (H = 32 heads of d = 128), ``h = rms(x)``:

    q', k', v' = h Wq, h Wk, h Wv                       [T, 4096] each
    a depthwise causal convolution of 4 taps over the sequence on each
    (zeros before the first token), then SiLU; split in heads
    q = l2norm(q) / sqrt(128);  k = l2norm(k)            over the head's 128
    a = -exp(A_log[h]) * softplus((h Wf1) Wf2 + dt_bias) [T, H, 128], < 0:
        the log of a decay in (0, 1) for EVERY key channel
    beta = sigmoid(h Wb)                                 [T, H]
    S [128 keys, 128 values] a head, float32, zero at the start:
        S'  = diag(exp(a_t)) S_{t-1}
        S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
        o_t = S_t^T q_t
    o = rms_128(o) * g_o * sigmoid((h Wg1) Wg2 + bg)     a head
    out = o Wo

Latent mixer (32 heads; no rotation anywhere, ``mla_use_nope``):

    q = h Wq                                  as [T, 32, 128 + 64]
    [c, r] = h Wkv_a;  c = rms(c) [T, 512];  r [T, 64] NOT rotated
    [k_n, v] = c Wkv_b                        as [T, 32, 128 + 128]
    k = [k_n, r]   (r shared by the heads)
    causal softmax of q k^T / sqrt(192), times v;  out = . Wo

FFN. The first ``first_k_dense_replace`` layers: ``Wdown(silu(Wgate h) *
(Wup h))``. The others: ``s = sigmoid(h Wr)`` in float32 over ALL the
router's outputs (``num_experts * ep_size``); the 8 largest of ``s +
bias`` (one group, so a plain top-8); weights = those 8 of ``s`` (without
the bias) divided by their sum, times ``routed_scaling_factor``; ``y =
sum_e w_e expert_e(h) + shared(h)``, each a SwiGLU. Under a share
(``ep_size`` > 1) only the experts ``e`` in ``[ep_rank * num_experts, +
num_experts)`` are summed; the weights are still normalised over all 8.
That partial sum is what goes on to the next layer, as in the program.

Weights. ``seeded_weights`` is the benchmark's own copy of how
``load_format=dummy`` draws this family: leaf ``name`` of layer ``i`` is
``normal(fold_in(fold_in(key(seed), id(name)), i)) / sqrt(fan_in)`` in the
served dtype, an expert stack drawn a layer at a time; norms are ones; the
selection bias is ``0.05 normal`` and the gate's bias ``0.5 normal``.
Two leaves are drawn at another width (``DRAW_WIDTHS``), so that the
comparison reads every mechanism and not one of them. The latent
mixer's ``Wq`` at 4 / sqrt(fan_in): with unit-variance scores a softmax
over a thousand keys is nearly flat, the mixer returns the mean of its
values, next to nothing beside a KDA layer's output, and latent rows in
float8 read as sound; at a deviation of 4 a few keys carry a row, as in
a trained model. The routed experts' ``W_down`` at 0.25 / sqrt(fan_in):
the 8th and 9th of 256 scores lie some 0.06 deviations apart, so bf16
chooses another expert than float32 in a fifth of the (token, layer)
pairs; at unit width one such choice moves the residual by a tenth and
the later layers' choices with it, and the comparison read that cascade
alone (a median of 0.06 whatever either cache did). The shared expert
and the dense layer keep unit width.
``A_log`` and ``dt_bias`` stay float32 and are NOT random: ``exp(A_log)``
runs from 1 to 16 over the heads and ``softplus(dt_bias)`` from 0.001 to
0.1 over a head's channels (the family's initialisation ranges), so with
the projection's unit-variance input the decay a token, ``exp(a)``, lies
between about ``exp(-12)`` and ``exp(-1.4e-4)`` with its median near
``exp(-0.08)``: most channels forget over tens of tokens, some within
one, some hold for thousands. A state that forgot everything at once, or
nothing in 2,000 tokens, would compare nothing. The tree has the
program's own layout (the layers outside the repeating pattern one by
one, the periods stacked), so that the CPU test compares leaf by leaf;
``layer_weights`` reads one layer out of it, cast to float32.

On a TPU a float32 matmul runs in lower precision unless told otherwise,
so everything here runs under ``jax.default_matmul_precision("highest")``.

``compute``. ``forward`` and ``forward_many`` take the type the activations
are kept in, float32 unless said: the reference. With ``compute`` the served
type (bfloat16) the SAME formulae run with weights and activations in that
type, every product of matrices rounded to it as it is stored, the logits
too, and in float32 only what the configuration states in float32: the
recurrent state and its step, the convolution's sum, the norms' and the
softmax's arithmetic, the router's scores, the decay. That pass is not the
reference and is compared with nothing served: its distance from the
float32 pass, at the served tokens, is what rounding to the served type
costs THIS seed's weights on THESE tokens, and the checker reads the
served error as a factor over it (``harness/checker.py``). In float32 every
cast below is the identity, so the reference is what it was to the bit.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

LEAF_IDS = {
    name: i for i, name in enumerate((
        "embed", "lm_head",
        "wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "f_a", "f_b",
        "b_proj", "g_a", "g_b",
        "g_bias", "wo",
        "mla_wq", "wkv_a", "wkv_b", "mla_wo",
        "wgate", "wup", "wdown",
        "router", "router_bias", "ws_gate", "ws_up", "ws_down",
        "we_gate", "we_up", "we_down",
    ))
}


# Leaves drawn at another width than 1 / sqrt(fan_in), times that (the
# docstring says why).
DRAW_WIDTHS = {"mla_wq": 4.0, "we_down": 0.25}


def sizes(config: dict) -> dict:
    la = config["linear_attn_config"]
    n = config["num_hidden_layers"]
    kda = {i - 1 for i in la["kda_layers"]}
    dense = config.get("first_k_dense_replace", 0)
    ep = int(config.get("ep_size", 1) or 1)
    held = config["num_experts"]
    return dict(
        n=n, D=config["hidden_size"], V=config["vocab_size"],
        eps=config["rms_norm_eps"], H=la["num_heads"], d=la["head_dim"],
        K=la["short_conv_kernel_size"], A=config["num_attention_heads"],
        DC=config["kv_lora_rank"], DN=config["qk_nope_head_dim"],
        DR=config["qk_rope_head_dim"], DV=config["v_head_dim"],
        F=config["intermediate_size"], Fm=config["moe_intermediate_size"],
        Fs=config["moe_intermediate_size"] * config.get("num_shared_experts", 0),
        held=held, E=held * ep,
        first=int(config.get("ep_rank", 0) or 0) * held if ep > 1 else 0,
        top_k=config["num_experts_per_token"],
        scaling=config.get("routed_scaling_factor", 1.0),
        kinds=[("kda" if i in kda else "mla", "dense" if i < dense else "moe")
               for i in range(n)],
    )


def plan(kinds: list) -> tuple:
    """Leading single layers, (period, repeats), trailing single layers:
    the earliest start from which a pattern of at most 8 layers repeats,
    with the pattern that covers most."""
    n = len(kinds)
    for start in range(n):
        best = (0, 0, 0)
        for period in range(1, 9):
            reps = 1
            while (start + (reps + 1) * period <= n
                   and kinds[start + reps * period: start + (reps + 1) * period]
                   == kinds[start: start + period]):
                reps += 1
            if reps > 1 and reps * period > best[0]:
                best = (reps * period, period, reps)
        if best[0]:
            covered, period, reps = best
            return (list(range(start)), start, period, reps,
                    list(range(start + covered, n)))
    return list(range(n)), n, 0, 0, []


def leaf_shapes(s: dict, kind: str) -> dict:
    """Leaf -> (shape, fan_in; 0 = not drawn from a plain normal)."""
    D, H, d, pj = s["D"], s["H"], s["d"], s["H"] * s["d"]
    if kind == "kda":
        return {
            "wq": ((D, pj), D), "wk": ((D, pj), D), "wv": ((D, pj), D),
            **{f"conv_{x}": ((s["K"], pj), s["K"]) for x in "qkv"},
            "f_a": ((D, d), D), "f_b": ((d, pj), d),
            "dt_bias": ((pj,), 0), "a_log": ((H,), 0), "b_proj": ((D, H), D),
            "g_a": ((D, d), D), "g_b": ((d, pj), d), "g_bias": ((pj,), 0),
            "o_norm": ((d,), 0), "wo": ((pj, D), pj),
        }
    if kind == "mla":
        A, DC, DR, DN, DV = s["A"], s["DC"], s["DR"], s["DN"], s["DV"]
        return {
            "wq": ((D, A * (DN + DR)), D), "wkv_a": ((D, DC + DR), D),
            "kv_a_norm": ((DC,), 0), "wkv_b": ((DC, A * (DN + DV)), DC),
            "wo": ((A * DV, D), A * DV),
        }
    if kind == "dense":
        F = s["F"]
        return {"wgate": ((D, F), D), "wup": ((D, F), D), "wdown": ((F, D), F)}
    if kind == "moe":
        out = {"router": ((D, s["E"]), D), "router_bias": ((s["E"],), 0)}
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

    def draw(name, key_name, layer, shape, fan_in):
        if name == "a_log":
            return jnp.log(jnp.linspace(1.0, 16.0, shape[0], dtype=jnp.float32))
        if name == "dt_bias":
            dt = jnp.exp(jnp.linspace(math.log(1e-3), math.log(1e-1), s["d"],
                                      dtype=jnp.float32))
            return jnp.tile(jnp.log(jnp.expm1(dt)), s["H"])
        if name.endswith("norm"):
            return jnp.ones(shape, dtype)
        key = jax.random.fold_in(jax.random.fold_in(rng, LEAF_IDS[key_name]), layer)
        if name == "router_bias":
            return normal(key, shape, 0.05, jnp.float32)
        if name == "g_bias":
            return normal(key, shape, 0.5, dtype)
        return normal(key, shape, DRAW_WIDTHS.get(key_name, 1.0) / math.sqrt(fan_in), dtype)

    def leaves(layer, kind):
        return {
            name: draw(name, "mla_" + name if kind == "mla" and name in ("wq", "wo")
                       else name, layer, shape, fan_in)
            for name, (shape, fan_in) in leaf_shapes(s, kind).items()
        }

    @partial(jax.jit, donate_argnums=(0,))
    def put(stack, w, i):
        return jax.lax.dynamic_update_index_in_dim(stack, w, i, 0)

    D, kinds = s["D"], s["kinds"]
    head, start, period, reps, tail = plan(kinds)
    w: dict = {
        "embed": draw("embed", "embed", 0, (s["V"], D), D),
        "final_norm": jnp.ones((D,), dtype),
        "lm_head": draw("lm_head", "lm_head", 0, (D, s["V"]), D),
        "single": {},
    }
    for layer in head + tail:
        mixer, ffn = kinds[layer]
        one = {"norms": leaves(layer, "norms"), mixer: leaves(layer, mixer),
               ffn: leaves(layer, ffn)}
        if ffn == "moe":
            one["experts"] = leaves(layer, "experts")
        w["single"][str(layer)] = one
    if reps:
        pattern = kinds[start: start + period]
        groups = {}
        for kind in ["norms"] + sorted({k for pair in pattern for k in pair}):
            col = 0 if kind in ("kda", "mla") else 1
            offs = [o for o in range(period)
                    if kind == "norms" or pattern[o][col] == kind]
            rows = [[leaves(start + rep * period + o, kind) for o in offs]
                    for rep in range(reps)]
            groups[kind] = {
                name: jnp.stack([jnp.stack([lv[name] for lv in row]) for row in rows])
                for name in leaf_shapes(s, kind)
            }
        w["periods"] = groups
        moe_layers = [start + rep * period + o for rep in range(reps)
                      for o in range(period) if pattern[o][1] == "moe"]
        if moe_layers:
            w["experts"] = {}
            for name, (shape, fan_in) in leaf_shapes(s, "experts").items():
                stack = jnp.zeros((len(moe_layers),) + shape, dtype)
                for i, layer in enumerate(moe_layers):
                    stack = put(stack, draw(name, name, layer, shape, fan_in), i)
                w["experts"][name] = stack
    return w


# Float32 whatever the activations are kept in: the family draws and
# keeps them so.
FLOAT32_LEAVES = ("a_log", "dt_bias", "router_bias")


def layer_weights(config: dict, weights: dict, layer: int, compute=jnp.float32) -> dict:
    """Layer ``layer``'s leaves as ``{"norms", "mixer", "ffn", "experts"}``,
    in ``compute`` (``FLOAT32_LEAVES`` in float32)."""
    s = sizes(config)
    kinds = s["kinds"]
    mixer, ffn = kinds[layer]
    _, start, period, reps, _ = plan(kinds)

    def cast(tree):
        if not isinstance(tree, dict):
            return tree  # None: a layer without experts
        return {k: cast(v) if isinstance(v, dict) or v is None else
                v.astype(jnp.float32 if k in FLOAT32_LEAVES else compute)
                for k, v in tree.items()}

    if not reps or not start <= layer < start + period * reps:
        one = weights["single"][str(layer)]
        return cast({"norms": one["norms"], "mixer": one[mixer], "ffn": one[ffn],
                    "experts": one.get("experts")})
    rep, off = divmod(layer - start, period)
    pattern = kinds[start: start + period]
    nth = lambda kind, col: sum(1 for k in pattern[:off] if k[col] == kind)
    pick = lambda kind, j: jax.tree.map(lambda x: x[rep, j], weights["periods"][kind])
    out = {"norms": pick("norms", off), "mixer": pick(mixer, nth(mixer, 0)),
           "ffn": pick(ffn, nth(ffn, 1)), "experts": None}
    if ffn == "moe":
        per = sum(1 for k in pattern if k[1] == "moe")
        row = rep * per + nth("moe", 1)
        out["experts"] = jax.tree.map(lambda x: x[row], weights["experts"])
    return cast(out)


def to_f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, weight, eps):
    y = to_f32(x)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps) * to_f32(weight)
    return y.astype(x.dtype)


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def short_conv(x, taps):
    """Depthwise causal convolution: ``taps [K, C]``, tap K-1 on the
    current token, zeros before the first."""
    k = taps.shape[0]
    x, taps = to_f32(x), to_f32(taps)
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(padded[j: j + x.shape[0]] * taps[j] for j in range(k))


def kda_mixer(h, lw, s):
    t, H, d = h.shape[0], s["H"], s["d"]
    heads = lambda x: x.reshape(t, H, d)
    q, k, v = (
        heads(jax.nn.silu(short_conv(h @ lw["w" + x], lw["conv_" + x])))
        for x in "qkv"
    )
    q, k = l2norm(q) / math.sqrt(d), l2norm(k)
    a = -jnp.exp(lw["a_log"])[:, None] * heads(
        jax.nn.softplus(to_f32((h @ lw["f_a"]) @ lw["f_b"]) + lw["dt_bias"]))
    beta = jax.nn.sigmoid(to_f32(h @ lw["b_proj"]))  # [T, H]

    def token(S, x):
        q_t, k_t, v_t, a_t, b_t = x
        S = jnp.exp(a_t)[:, :, None] * S  # [H, keys, values]
        mem = jnp.einsum("hk,hkv->hv", k_t, S)
        S = S + k_t[:, :, None] * (b_t[:, None] * (v_t - mem))[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    _, o = jax.lax.scan(token, jnp.zeros((H, d, d), jnp.float32), (q, k, v, a, beta))
    gate = jax.nn.sigmoid(heads(to_f32((h @ lw["g_a"]) @ lw["g_b"]) + to_f32(lw["g_bias"])))
    o = rms_norm(o, lw["o_norm"], s["eps"]) * gate
    return o.reshape(t, H * d).astype(h.dtype) @ lw["wo"]


def latent_mixer(h, lw, s):
    t, A, DC, DN, DR, DV = h.shape[0], s["A"], s["DC"], s["DN"], s["DR"], s["DV"]
    q = (h @ lw["wq"]).reshape(t, A, DN + DR)
    kv_a = h @ lw["wkv_a"]
    c = rms_norm(kv_a[:, :DC], lw["kv_a_norm"], s["eps"])
    r = kv_a[:, DC:]  # not rotated
    kv = (c @ lw["wkv_b"]).reshape(t, A, DN + DV)
    k = jnp.concatenate([kv[..., :DN], jnp.broadcast_to(r[:, None], (t, A, DR))], -1)
    scores = jnp.einsum("iad,jad->aij", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(DN + DR)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    out = jnp.einsum("aij,jav->iav", jax.nn.softmax(scores, axis=-1).astype(h.dtype),
                     kv[..., DN:])
    return out.reshape(t, A * DV) @ lw["wo"]


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def routed(h, lw, experts, s):
    """``(the held experts' part of the routed sum + the shared expert,
    the routing weights [T, E] over the whole router)``."""
    scores = jax.nn.sigmoid(to_f32(h) @ to_f32(lw["router"]))  # [T, E]
    _, ids = jax.lax.top_k(scores + lw["router_bias"], s["top_k"])
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    picked = picked / (picked.sum(-1, keepdims=True) + 1e-20) * s["scaling"]
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


def layer(x, lw, kinds, s):
    mixer, ffn = kinds
    h = rms_norm(x, lw["norms"]["input_norm"], s["eps"])
    x = x + (kda_mixer if mixer == "kda" else latent_mixer)(h, lw["mixer"], s)
    h = rms_norm(x, lw["norms"]["post_norm"], s["eps"])
    if ffn == "dense":
        f = lw["ffn"]
        return x + swiglu(h, f["wgate"], f["wup"], f["wdown"])
    return x + routed(h, lw["ffn"], lw["experts"], s)[0]


def forward(config: dict, weights: dict, tokens, compute=jnp.float32) -> jnp.ndarray:
    """Logits ``[T, V]`` in float32 for one sequence, computed in ``compute``."""
    s = sizes(config)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(tokens, jnp.int32)].astype(compute)
        for i, kinds in enumerate(s["kinds"]):
            x = _layer_jit(x, layer_weights(config, weights, i, compute), kinds, _frozen(s))
        x = rms_norm(x, weights["final_norm"], s["eps"])
        return to_f32(x @ weights["lm_head"].astype(compute))


def forward_many(config: dict, weights: dict, sequences: list, compute=jnp.float32) -> list:
    """``forward`` of each sequence, one at a time. Every sequence is run
    at the length of the longest, filled up with token 0 behind its end
    and cut back: nothing here looks ahead, so the rows are those of the
    sequence alone, and the layers compile for one length, not for each.
    Each result goes to the host as it is made: four answers of a
    thousand tokens are 3.9 GB of float32 logits at this vocabulary, which
    the device does not have beside the weights."""
    import numpy as np

    longest = max(len(seq) for seq in sequences)
    return [np.asarray(forward(config, weights, list(seq) + [0] * (longest - len(seq)),
                               compute)[: len(seq)])
            for seq in sequences]


class _frozen(dict):
    """The sizes as a static argument of the layer's jit."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


@partial(jax.jit, static_argnums=(2, 3))
def _layer_jit(x, lw, kinds, s):
    with jax.default_matmul_precision("highest"):
        return layer(x, lw, kinds, s)
