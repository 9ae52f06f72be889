"""Plain reference of the Llama/Mistral dense decoder: the forward pass in
float32 ``jax.numpy``, one sequence at a time, with no cache, no kernel and
no batching. It follows the published description (the ``transformers``
``MistralModel``): pre-norm RMSNorm, rotary embedding in the rotate-half
layout on the whole head, causal softmax attention with grouped KV heads,
SwiGLU MLP, untied output head.

Departures from the published description, each on purpose:
- Weights are stored ``[in, out]`` (the published checkpoints store
  ``[out, in]``): with weights drawn from a seed the layout is only a name.
- Weights come from ``seeded_weights``, the benchmark's own copy of the
  scheme ``load_format=dummy`` uses (normal(0, 1/sqrt(fan_in)) per stacked
  weight, keys split from the seed in a fixed order), so that the served
  model and the reference hold the same numbers without the reference
  taking anything from the program. They are held in the served dtype and
  cast to float32 one layer at a time: 16 float32 layers do not fit the
  chip beside nothing.
- No sliding window: Mistral-7B-v0.3 publishes ``sliding_window: null``.

On a TPU a float32 matmul runs in lower precision unless told otherwise, so
``forward`` runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def sizes(config: dict) -> dict:
    heads = config["num_attention_heads"]
    return dict(
        L=config["num_hidden_layers"], D=config["hidden_size"], H=heads,
        KH=config.get("num_key_value_heads") or heads,
        Dh=config.get("head_dim") or config["hidden_size"] // heads,
        F=config["intermediate_size"], V=config["vocab_size"],
        eps=config.get("rms_norm_eps", 1e-6), theta=float(config.get("rope_theta", 10000.0)),
    )


def _draw(dtype):
    @partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)

    return draw


def seeded_weights(config: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    s = sizes(config)
    L, D, H, KH, Dh, F, V = (s[k] for k in ("L", "D", "H", "KH", "Dh", "F", "V"))
    keys = jax.random.split(jax.random.PRNGKey(seed), 12)
    draw = _draw(dtype)
    return {
        "embed": draw(keys[7], (V, D), D),
        "lm_head": draw(keys[8], (D, V), D),
        "final_norm": jnp.ones((D,), dtype),
        "layers": {
            "input_norm": jnp.ones((L, D), dtype),
            "post_norm": jnp.ones((L, D), dtype),
            "wq": draw(keys[0], (L, D, H * Dh), D),
            "wk": draw(keys[1], (L, D, KH * Dh), D),
            "wv": draw(keys[2], (L, D, KH * Dh), D),
            "wo": draw(keys[3], (L, H * Dh, D), H * Dh),
            "wgate": draw(keys[4], (L, D, F), D),
            "wup": draw(keys[5], (L, D, F), D),
            "wdown": draw(keys[6], (L, F, D), F),
        },
    }


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, theta):
    """x: [T, heads, Dh], positions 0..T-1, rotate-half layout."""
    t, _, dh = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(q, k, v):
    """Causal softmax attention. q: [T, H, Dh]; k, v: [T, KH, Dh]."""
    t, h, dh = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)


def attention_block(x, lw, s, qk_norm=False):
    t = x.shape[0]
    h = rms_norm(x, lw["input_norm"], s["eps"])
    q, k, v = h @ lw["wq"], h @ lw["wk"], h @ lw["wv"]
    if qk_norm:  # OLMoE: RMSNorm over the whole projected vector
        q = rms_norm(q, lw["q_norm"], s["eps"])
        k = rms_norm(k, lw["k_norm"], s["eps"])
    q = rope(q.reshape(t, s["H"], s["Dh"]), s["theta"])
    k = rope(k.reshape(t, s["KH"], s["Dh"]), s["theta"])
    v = v.reshape(t, s["KH"], s["Dh"])
    return x + attention(q, k, v).reshape(t, s["H"] * s["Dh"]) @ lw["wo"]


def layer(x, lw, s):
    x = attention_block(x, lw, s)
    h = rms_norm(x, lw["post_norm"], s["eps"])
    return x + (jax.nn.silu(h @ lw["wgate"]) * (h @ lw["wup"])) @ lw["wdown"]


def forward_many(config: dict, weights: dict, sequences: list, layer_fn=layer) -> list:
    """Logits ``[T, V]`` in float32 for each sequence of token ids. The
    sequences go through the model one at a time; only the cast of a
    layer's weights to float32 is shared between them."""
    s = sizes(config)

    @jax.jit
    def layer_f32(layers, i):
        return jax.tree_util.tree_map(lambda a: a[i].astype(jnp.float32), layers)

    one_layer = jax.jit(lambda x, lw: layer_fn(x, lw, s))

    @jax.jit
    def head(x, final_norm, lm_head):
        return rms_norm(x, final_norm.astype(jnp.float32), s["eps"]) @ lm_head.astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        xs = [weights["embed"][jnp.asarray(t, jnp.int32)].astype(jnp.float32) for t in sequences]
        for i in range(s["L"]):
            lw = layer_f32(weights["layers"], i)
            xs = [one_layer(x, lw) for x in xs]
        return [head(x, weights["final_norm"], weights["lm_head"]) for x in xs]


def forward(config: dict, weights: dict, tokens, layer_fn=layer):
    """Logits ``[T, V]`` in float32 for one sequence of token ids."""
    return forward_many(config, weights, [tokens], layer_fn)[0]
