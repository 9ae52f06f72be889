"""Plain reference of the Falcon-H1 decoder (``model_type`` ``falcon_h1``,
huggingface.co/tiiuae/Falcon-H1-34B-Instruct): the forward pass in float32
``jax.numpy``, one sequence at a time, the state-space rule as the
RECURRENCE A TOKEN (not the chunked form the program uses), full causal
attention with keys and values expanded a head; no cache, no chunking, no
kernel, no batching. Imports nothing from ``vllm_tpu``.

Token states ``x`` [T, D]. ``rms`` has a learned scale, eps ``rms_norm_eps``.
Every layer is alike, ``h = rms_in(x)``::

    x <- x + ssm_out_multiplier * SSM(h)
           + attention_out_multiplier * ATT(attention_in_multiplier * h)
    x <- x + MLP(rms_ff(x))

going in ``x = embed[ids] * embedding_multiplier``, coming out ``logits =
(rms_f(x) W_head) * lm_head_multiplier``.

``ATT(h)``: ``q = h Wq`` as A heads of dh, ``k = (h Wk) * key_multiplier``
and ``v = h Wv`` as KH heads; rope, rotate-half over all dh lanes, theta
``rope_theta``, no scaling; causal softmax of ``q k / sqrt(dh)``; ``Wo``.

``SSM(h)`` (Mamba-2; ``I = mamba_d_ssm`` = Hm heads x P, ``G`` groups, state
size ``N``, ``K`` taps)::

    p = ((ssm_in_multiplier * h) W_in) * mup           [T, 2I + 2GN + Hm]
        mup: ssm_multipliers over the five zones z [I], x [I], B [GN],
        C [GN], dt [Hm], in that order (the order of W_in's columns)
    x, B, C: a depthwise causal convolution of K taps WITH bias over the
        sequence (zeros before the first token), then SiLU
    dt = softplus(dt + dt_bias)  a head, float32, not clipped (the family's
        time-step limit is (0, inf));  a = exp(-exp(A_log) dt)
    H [P, N] a head, float32, zero at the start (a group serves Hm / G heads):
        H_t = a_t H_{t-1} + dt_t x_t B_t^T;   y_t = H_t C_t + D x_t
    y <- y * SiLU(z);  rms over each group's I / G channels, weight [I];  W_out

``MLP(h) = ((h W_up) * SiLU((h W_gate) * mlp_multipliers[0])) W_down *
mlp_multipliers[1]``.

Departures from the published description: none in the arithmetic. The
published code computes the rule in chunks of ``mamba_chunk_size`` where it
has its kernels and as this recurrence where it has not; the two are the
same function. ``mamba_use_mlp``, ``mamba_expand`` (``mamba_d_ssm`` is
given), ``num_logits_to_keep`` and ``attn_layer_indices`` (null) name
nothing further at these values.

Weights. ``seeded_weights`` is the benchmark's own copy of how
``load_format=dummy`` draws this family (``vllm_tpu/models/falcon_h1.py``):
leaf ``name`` of layer ``i`` is ``normal(fold_in(fold_in(key(seed),
id(name)), i)) * w / (m sqrt(fan_in))`` in the served dtype, ``m`` the
product of the config's multipliers that scale the leaf's output
(``DRAW_WIDTHS``), so that what reaches the next operation is ``w`` wide. At
``1 / sqrt(fan_in)`` the published multipliers, which were made for trained
weights, leave the logits 0.008 wide (every logprob -12.47: the comparison
is blind), every softmax flat (the keys at 0.011) and a residual that is the
embedding and nothing else (the branches at 0.0375, 0.088 and 0.011). ``w``
is 1 but for the head (2.5: logits as wide as a trained model's top tokens
stand apart) and the keys (4: a few keys carry a row; at 1 a softmax over a
thousand keys is flat and the attention mixer returns the mean of its
values). ``W_in`` is ONE draw whose columns are scaled zone by zone. Norms
are ones, ``D`` ones, the convolution's bias ``0.1 normal``. ``A_log``,
``dt_bias`` and ``D`` stay float32 and the first two are NOT random:
``exp(A_log)`` runs 1 to 32 over the heads and ``softplus(dt_bias)`` 0.001
to 0.1 beside it, so a head's memory ``1 / (exp(A_log) dt)`` runs from a
thousand tokens (head 0) down to a third of one (the last head), and dt
itself varies with the token about that. No width changes a shape, a count
or a step's time.

On a TPU a float32 matmul runs in lower precision unless told otherwise, so
everything here runs under ``jax.default_matmul_precision("highest")``.

``compute``. ``forward`` and ``forward_many`` take the type the activations
are kept in, float32 unless said: the reference. With ``compute`` the served
type (bfloat16) the SAME formulae run with weights and activations in that
type, every product of matrices rounded to it as it is stored, the logits
too, and in float32 only what the configuration states in float32: the
recurrent state and its step, the convolution's sum, the norms' and the
softmax's arithmetic, the step and the decay. That pass is not the reference
and is compared with nothing served (``harness/checker.py`` reads the served
error as a factor over its distance from the float32 pass). In float32
every cast below is the identity.

Memory. Six layers at the published widths are 10.5 GB of bfloat16 beside
which one layer is cast to float32 at a time (1.7 GB); the head's 5120 x
261,120 is never cast whole: the logits are computed a block of
``HEAD_BLOCK`` vocabulary columns at a time and a sequence's ``[T, V]`` goes
to the host as it is made.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

LEAF_IDS = {
    name: i for i, name in enumerate((
        "embed", "lm_head", "wq", "wk", "wv", "wo", "w_in", "conv_w",
        "conv_b", "w_out", "wgate", "wup", "wdown",
    ))
}

# Leaf -> (w, the config's multipliers that scale the leaf's output): drawn
# at w / (m sqrt(fan_in)) (the docstring says why).
DRAW_WIDTHS = {
    "embed": (1.0, ("embedding_multiplier",)),
    "lm_head": (2.5, ("lm_head_multiplier",)),
    "wq": (1.0, ("attention_in_multiplier",)),
    "wk": (4.0, ("attention_in_multiplier", "key_multiplier")),
    "wv": (1.0, ("attention_in_multiplier",)),
    "wo": (1.0, ("attention_out_multiplier",)),
    "w_in.z": (1.0, ("ssm_in_multiplier", "ssm_multipliers.0")),
    "w_in.x": (1.0, ("ssm_in_multiplier", "ssm_multipliers.1")),
    "w_in.B": (1.0, ("ssm_in_multiplier", "ssm_multipliers.2")),
    "w_in.C": (1.0, ("ssm_in_multiplier", "ssm_multipliers.3")),
    "w_in.dt": (1.0, ("ssm_in_multiplier", "ssm_multipliers.4")),
    "conv_w": (1.0, ()),
    "w_out": (1.0, ("ssm_out_multiplier",)),
    "wgate": (1.0, ("mlp_multipliers.0",)),
    "wup": (1.0, ()),
    "wdown": (1.0, ("mlp_multipliers.1",)),
}
CONV_BIAS_WIDTH = 0.1
A_MAX, DT_MIN, DT_MAX = 32.0, 1e-3, 1e-1
HEAD_BLOCK = 32768  # vocabulary columns of the head a matmul


def multiplier(config: dict, name: str) -> float:
    key, _, index = name.partition(".")
    return float(config[key][int(index)] if index else config[key])


def draw_width(config: dict, leaf: str) -> float:
    w, names = DRAW_WIDTHS[leaf]
    return w / math.prod(multiplier(config, n) for n in names)


def sizes(config: dict) -> dict:
    Hm, Pd = config["mamba_n_heads"], config["mamba_d_head"]
    I = config.get("mamba_d_ssm") or config["mamba_expand"] * config["hidden_size"]
    if I != Hm * Pd:
        raise ValueError(f"mamba_d_ssm {I} is not mamba_n_heads {Hm} x mamba_d_head {Pd}")
    G, N = config["mamba_n_groups"], config["mamba_d_state"]
    A = config["num_attention_heads"]
    return dict(
        n=config["num_hidden_layers"], D=config["hidden_size"], V=config["vocab_size"],
        F=config["intermediate_size"], eps=config["rms_norm_eps"],
        A=A, KH=config["num_key_value_heads"],
        dh=config.get("head_dim") or config["hidden_size"] // A,
        theta=float(config["rope_theta"]),
        Hm=Hm, P=Pd, I=I, G=G, N=N, K=config["mamba_d_conv"], C=I + 2 * G * N,
        conv_bias=bool(config.get("mamba_conv_bias", True)),
        zones=(("z", I), ("x", I), ("B", G * N), ("C", G * N), ("dt", Hm)),
        m_embed=float(config["embedding_multiplier"]),
        m_head=float(config["lm_head_multiplier"]),
        m_attn_in=float(config["attention_in_multiplier"]),
        m_attn_out=float(config["attention_out_multiplier"]),
        m_key=float(config["key_multiplier"]),
        m_ssm_in=float(config["ssm_in_multiplier"]),
        m_ssm_out=float(config["ssm_out_multiplier"]),
        m_gate=float(config["mlp_multipliers"][0]),
        m_down=float(config["mlp_multipliers"][1]),
        m_zones=tuple(float(m) for m in config["ssm_multipliers"]),
    )


def leaf_shapes(s: dict) -> dict:
    """A layer's leaf -> (shape, fan_in; 0 = not drawn from a plain normal)."""
    D, F, A, KH, dh = s["D"], s["F"], s["A"], s["KH"], s["dh"]
    I, C, K, Hm = s["I"], s["C"], s["K"], s["Hm"]
    return {
        "input_norm": ((D,), 0), "post_norm": ((D,), 0),
        "wq": ((D, A * dh), D), "wk": ((D, KH * dh), D),
        "wv": ((D, KH * dh), D), "wo": ((A * dh, D), A * dh),
        "w_in": ((D, I + C + Hm), D),
        "conv_w": ((K, C), K),
        **({"conv_b": ((C,), 0)} if s["conv_bias"] else {}),
        "a_log": ((Hm,), 0), "dt_bias": ((Hm,), 0), "d_skip": ((Hm,), 0),
        "ssm_norm": ((I,), 0), "w_out": ((I, D), I),
        "wgate": ((D, F), D), "wup": ((D, F), D), "wdown": ((F, D), F),
    }


def seeded_weights(config: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    s = sizes(config)
    rng = jax.random.PRNGKey(seed)

    @partial(jax.jit, static_argnums=(1, 3))
    def normal(key, shape, scale, out_dtype):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(out_dtype)

    def key_of(name, layer):
        return jax.random.fold_in(jax.random.fold_in(rng, LEAF_IDS[name]), layer)

    def draw(name, layer, shape, fan_in):
        hm = s["Hm"]
        if name == "a_log":
            return jnp.log(jnp.linspace(1.0, A_MAX, hm, dtype=jnp.float32))
        if name == "dt_bias":
            dt = jnp.exp(jnp.linspace(math.log(DT_MIN), math.log(DT_MAX), hm,
                                      dtype=jnp.float32))
            return jnp.log(jnp.expm1(dt))
        if name == "d_skip":
            return jnp.ones((hm,), jnp.float32)
        if name.endswith("norm"):
            return jnp.ones(shape, dtype)
        if name == "conv_b":
            return normal(key_of(name, layer), shape, jnp.float32(CONV_BIAS_WIDTH), dtype)
        if name == "w_in":
            width = jnp.concatenate([
                jnp.full((n,), draw_width(config, f"w_in.{zone}"), jnp.float32)
                for zone, n in s["zones"]])
            return normal(key_of(name, layer), shape, width / math.sqrt(fan_in), dtype)
        return normal(key_of(name, layer), shape,
                      jnp.float32(draw_width(config, name) / math.sqrt(fan_in)), dtype)

    @partial(jax.jit, donate_argnums=(0,))
    def put(stack, w, i):
        return jax.lax.dynamic_update_index_in_dim(stack, w, i, 0)

    D = s["D"]
    w: dict = {
        "embed": draw("embed", 0, (s["V"], D), D),
        "final_norm": jnp.ones((D,), dtype),
        "lm_head": draw("lm_head", 0, (D, s["V"]), D),
        "layers": {},
    }
    for name, (shape, fan_in) in leaf_shapes(s).items():
        first = draw(name, 0, shape, fan_in)
        stack = jnp.zeros((s["n"],) + shape, first.dtype)
        for layer in range(s["n"]):
            stack = put(stack, first if layer == 0 else draw(name, layer, shape, fan_in),
                        layer)
        w["layers"][name] = stack
    return w


# Float32 whatever the activations are kept in: the family keeps them so.
FLOAT32_LEAVES = ("a_log", "dt_bias", "d_skip")


def layer_weights(weights: dict, layer: int, compute=jnp.float32) -> dict:
    """Layer ``layer``'s leaves in ``compute`` (``FLOAT32_LEAVES`` in float32)."""
    return {k: v[layer].astype(jnp.float32 if k in FLOAT32_LEAVES else compute)
            for k, v in weights["layers"].items()}


def to_f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, weight, eps):
    y = to_f32(x)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps) * to_f32(weight)
    return y.astype(x.dtype)


def scaled(x, m):
    """``x * m`` kept in x's type, the product taken in float32."""
    return (to_f32(x) * m).astype(x.dtype)


def short_conv(x, taps, bias):
    """Depthwise causal convolution: ``taps [K, C]``, tap K-1 on the
    current token, zeros before the first; float32."""
    k = taps.shape[0]
    x, taps = to_f32(x), to_f32(taps)
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    y = sum(padded[j: j + x.shape[0]] * taps[j] for j in range(k))
    return y if bias is None else y + to_f32(bias)


def ssm_mixer(h, lw, s):
    t, Hm, Pd, G, N, I = h.shape[0], s["Hm"], s["P"], s["G"], s["N"], s["I"]
    mup = jnp.concatenate([jnp.full((n,), m, jnp.float32)
                           for (_, n), m in zip(s["zones"], s["m_zones"])])
    p = scaled(scaled(h, s["m_ssm_in"]) @ lw["w_in"], mup)
    z, xbc, dt = p[:, :I], p[:, I: I + s["C"]], p[:, I + s["C"]:]
    y = jax.nn.silu(short_conv(xbc, lw["conv_w"], lw.get("conv_b")))
    x = y[:, :I].reshape(t, Hm, Pd)
    b = jnp.repeat(y[:, I: I + G * N].reshape(t, G, N), Hm // G, axis=1)  # [T, Hm, N]
    c = jnp.repeat(y[:, I + G * N:].reshape(t, G, N), Hm // G, axis=1)
    dt = jax.nn.softplus(to_f32(dt) + lw["dt_bias"])  # [T, Hm]
    a = jnp.exp(-jnp.exp(lw["a_log"]) * dt)

    def token(H, xs):
        x_t, b_t, c_t, dt_t, a_t = xs
        H = a_t[:, None, None] * H + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return H, jnp.einsum("hpn,hn->hp", H, c_t)

    _, o = jax.lax.scan(token, jnp.zeros((Hm, Pd, N), jnp.float32), (x, b, c, dt, a))
    o = o + lw["d_skip"][:, None] * x
    o = o.reshape(t, G, I // G) * jax.nn.silu(to_f32(z)).reshape(t, G, I // G)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + s["eps"])
    o = o.reshape(t, I) * to_f32(lw["ssm_norm"])
    return o.astype(h.dtype) @ lw["w_out"]


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
    h = scaled(h, s["m_attn_in"])
    q = rope((h @ lw["wq"]).reshape(t, A, dh), s["theta"])
    k = rope(scaled(h @ lw["wk"], s["m_key"]).reshape(t, KH, dh), s["theta"])
    v = (h @ lw["wv"]).reshape(t, KH, dh)
    k, v = jnp.repeat(k, A // KH, axis=1), jnp.repeat(v, A // KH, axis=1)
    scores = jnp.einsum("iad,jad->aij", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(dh)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    out = jnp.einsum("aij,jad->iad", jax.nn.softmax(scores, axis=-1).astype(h.dtype), v)
    return out.reshape(t, A * dh) @ lw["wo"]


def mlp(h, lw, s):
    gate = to_f32(h @ lw["wgate"]) * s["m_gate"]
    act = (jax.nn.silu(gate) * to_f32(h @ lw["wup"])).astype(h.dtype)
    return scaled(act @ lw["wdown"], s["m_down"])


def layer(x, lw, s):
    h = rms_norm(x, lw["input_norm"], s["eps"])
    mixed = (to_f32(ssm_mixer(h, lw, s)) * s["m_ssm_out"]
             + to_f32(attention(h, lw, s)) * s["m_attn_out"])
    x = x + mixed.astype(x.dtype)
    return x + mlp(rms_norm(x, lw["post_norm"], s["eps"]), lw, s)


def hidden(config: dict, weights: dict, tokens, compute=jnp.float32):
    """The last norm's output ``[T, D]`` in ``compute``."""
    s = _frozen(sizes(config))
    with jax.default_matmul_precision("highest"):
        x = scaled(weights["embed"][jnp.asarray(tokens, jnp.int32)].astype(compute),
                   s["m_embed"])
        for i in range(s["n"]):
            x = _layer_jit(x, layer_weights(weights, i, compute), s)
        return rms_norm(x, weights["final_norm"], s["eps"])


def logit_blocks(config: dict, weights: dict, x, compute=jnp.float32):
    """The logits of ``x [T, D]`` a block of vocabulary columns at a time,
    float32, each block rounded to ``compute`` first as it would be stored."""
    m = float(config["lm_head_multiplier"])
    head = weights["lm_head"]
    for lo in range(0, head.shape[1], HEAD_BLOCK):
        yield _head_jit(x, head[:, lo: lo + HEAD_BLOCK], m, jnp.dtype(compute))


def forward(config: dict, weights: dict, tokens, compute=jnp.float32) -> jnp.ndarray:
    """Logits ``[T, V]`` in float32 for one sequence, computed in ``compute``."""
    x = hidden(config, weights, tokens, compute)
    return jnp.concatenate(list(logit_blocks(config, weights, x, compute)), axis=-1)


def forward_many(config: dict, weights: dict, sequences: list, compute=jnp.float32) -> list:
    """``forward`` of each sequence, one at a time. Every sequence is run at
    the length of the longest, filled up with token 0 behind its end and cut
    back: nothing here looks ahead, so the rows are those of the sequence
    alone, and the layers compile for one length. Each block of logits goes
    to the host as it is made: a sequence of two thousand tokens is 2.1 GB
    of float32 logits at this vocabulary, beside 10.5 GB of weights."""
    import numpy as np

    longest = max(len(seq) for seq in sequences)
    out = []
    for seq in sequences:
        x = hidden(config, weights, list(seq) + [0] * (longest - len(seq)), compute)
        out.append(np.concatenate(
            [np.asarray(block[: len(seq)])
             for block in logit_blocks(config, weights, x, compute)], axis=-1))
    return out


class _frozen(dict):
    """The sizes as a static argument of the layer's jit."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


@partial(jax.jit, static_argnums=(2,))
def _layer_jit(x, lw, s):
    with jax.default_matmul_precision("highest"):
        return layer(x, lw, s)


@partial(jax.jit, static_argnums=(2, 3))
def _head_jit(x, head, m, compute):
    with jax.default_matmul_precision("highest"):
        return to_f32((x @ head.astype(compute)).astype(compute)) * m
