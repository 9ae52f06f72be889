"""Plain reference of the SDAR-MoE decoder (``model_type`` ``sdar_moe``,
huggingface.co/JetLM/SDAR-30B-A3B-Chat): the forward pass in float32
``jax.numpy``, one sequence at a time, with no cache, no kernel and no
batching, and generation by diffusion over blocks as the family's public
``generate.py`` does it.

The layer, for token states ``x`` [T, D] at absolute positions ``p``
(D 2048, 32 query and 4 KV heads of 128, 128 experts of width 768, 8 a
token, B = ``block_length`` = 4):

    h = rms(x) * g1
    q = (h Wq) as [T, 32, 128];  k, v = (h Wk), (h Wv) as [T, 4, 128]
    q = rms_128(q) * gq;  k = rms_128(k) * gk     (over the head's 128, eps 1e-6)
    rope, rotate-half, theta 1e6, on the whole head, at p
    a_i = softmax_j(q_i k_j / sqrt(128)) over every j with p_j // B <= p_i // B
          (whole blocks to the left, and the token's own block in both
          directions), 8 query heads to a KV head
    x = x + a Wo
    h2 = rms(x) * g2
    r = softmax(h2 Wr) in float32 over 128; the 8 largest, renormalised to sum 1
    y = sum_e r_e * Wdown_e( silu(Wgate_e h2) * (Wup_e h2) )
    x = x + y

After the layers ``rms(x) * gf``, then the untied head. The logits AT
position ``i`` are for the token AT position ``i`` (no shift): an
uncommitted position is fed the mask token's embedding and its own output
row predicts it.

Generation (``generate``). Blocks lie on absolute positions. The first
``floor(P/B) * B`` prompt tokens are clean from the start. Then, block by
block: the block's positions (for the first block the last ``P mod B``
prompt tokens, committed, then masks) are run against the clean prefix;
every uncommitted position is sampled and its confidence is the
probability of the drawn token; the rule commits ``n = B /
denoising_steps`` of them (never more than are left); committed tokens
never change. When none is left uncommitted the block is clean (the served
path runs it once more to store its K/V; with no cache there is nothing to
store). Rules: ``sequential`` (the n leftmost uncommitted),
``low_confidence_static`` (the n most confident, the leftmost first among
equals), ``low_confidence_dynamic`` (every position whose confidence
passes ``confidence_threshold``, but no fewer than n).

Departures from the published description, each on purpose:
- Weights are stored ``[in, out]`` and come from ``seeded_weights``, the
  benchmark's own copy of how ``load_format=dummy`` draws this family
  (normal(0, 1/sqrt(fan_in)); the attention stacks, the embedding and the
  head from keys split from the seed, the router and the expert stacks
  from keys split from ``fold_in(seed, 1)``, an expert stack drawn a layer
  at a time). They are held in the served dtype and cast to float32 a
  layer at a time: a float32 layer of 128 experts is 2.5 GB.
- The experts are computed densely, one expert at a time over every row,
  with a weight of exactly 0 where the router did not pick the expert:
  the sum is the sparse one, and nothing is gathered or grouped.
- ``generate`` is greedy only (the CPU tests compare tokens); its
  confidence is the plain softmax probability of the argmax. The script
  draws from the truncated distribution and takes that probability.
- The script's static rule takes ``topk(confidence, n)`` with committed
  positions at -inf, so with fewer than n left it would overwrite a
  committed token; here a pass commits ``min(n, left)``.
- ``forward_many`` is NOT the script: it is the function the benchmark's
  checker calls with ``prompt + answer[:-1]`` and reads as "row ``i``
  holds the logits for the token at position ``i + 1``". For this family
  row ``i`` is the pass that COMMITS position ``i + 1`` under
  ``sequential`` with one token a pass: positions ``<= i`` clean,
  positions ``i + 1 ..`` to the end of that block masked, blocks to the
  left clean. That is a function of the tokens alone. It costs
  ``(B + 1) x T`` token-rows a sequence: one clean stream, whose K/V the
  blocks to the right read, and B masked variants (variant ``o`` holds the
  first ``o`` positions of EVERY block clean and the rest masked, and
  gives the rows of the positions at offset ``o``), layer by layer.

On a TPU a float32 matmul runs in lower precision unless told otherwise, so
everything here runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

def sizes(config: dict) -> dict:
    heads = config["num_attention_heads"]
    return dict(
        L=config["num_hidden_layers"], D=config["hidden_size"], H=heads,
        KH=config.get("num_key_value_heads") or heads,
        Dh=config.get("head_dim") or config["hidden_size"] // heads,
        E=config["num_experts"], K=config["num_experts_per_tok"],
        F=config["moe_intermediate_size"], V=config["vocab_size"],
        eps=config.get("rms_norm_eps", 1e-6),
        theta=float(config.get("rope_theta", 10000.0)),
        renorm=bool(config.get("norm_topk_prob", True)),
        B=int(config.get("block_length", 4)), mask=int(config["mask_token_id"]),
    )


def seeded_weights(config: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    s = sizes(config)
    L, D, H, KH, Dh, E, F, V = (s[k] for k in ("L", "D", "H", "KH", "Dh", "E", "F", "V"))
    rng = jax.random.PRNGKey(seed)
    keys = jax.random.split(rng, 12)
    moe = jax.random.split(jax.random.fold_in(rng, 1), 8)

    @partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)

    def experts(key, shape, fan_in):
        return jnp.stack([draw(k, shape[1:], fan_in) for k in jax.random.split(key, shape[0])])

    return {
        "embed": draw(keys[7], (V, D), D),
        "lm_head": draw(keys[8], (D, V), D),
        "final_norm": jnp.ones((D,), dtype),
        "layers": {
            "input_norm": jnp.ones((L, D), dtype),
            "post_norm": jnp.ones((L, D), dtype),
            "q_norm": jnp.ones((L, Dh), dtype),
            "k_norm": jnp.ones((L, Dh), dtype),
            "wq": draw(keys[0], (L, D, H * Dh), D),
            "wk": draw(keys[1], (L, D, KH * Dh), D),
            "wv": draw(keys[2], (L, D, KH * Dh), D),
            "wo": draw(keys[3], (L, H * Dh, D), H * Dh),
            "router": draw(moe[0], (L, D, E), D),
            "we_gate": experts(moe[1], (L, E, D, F), D),
            "we_up": experts(moe[2], (L, E, D, F), D),
            "we_down": experts(moe[3], (L, E, F, D), F),
        },
    }


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, theta):
    """x: [..., T, heads, Dh], positions 0..T-1, rotate-half layout."""
    t, dh = x.shape[-3], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def qkv(x, lw, s):
    """The projections of one stream [T, D], normed per head and rotated."""
    t = x.shape[0]
    h = rms_norm(x, lw["input_norm"], s["eps"])
    q = (h @ lw["wq"]).reshape(t, s["H"], s["Dh"])
    k = (h @ lw["wk"]).reshape(t, s["KH"], s["Dh"])
    v = (h @ lw["wv"]).reshape(t, s["KH"], s["Dh"])
    q = rope(rms_norm(q, lw["q_norm"], s["eps"]), s["theta"])
    k = rope(rms_norm(k, lw["k_norm"], s["eps"]), s["theta"])
    return q, k, v


def attend(q, k, v, allowed):
    """Softmax attention of q [T, H, Dh] over k, v [C, KH, Dh] where
    ``allowed`` [T, C] says which keys a query sees."""
    rep = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where(allowed[None], scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)


def experts(x, lw, s):
    """The mixture of experts over rows x [N, D] (already normed)."""
    r = jax.nn.softmax(x @ lw["router"], axis=-1)  # [N, E] float32
    top, ids = jax.lax.top_k(r, s["K"])
    if s["renorm"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    weight = jnp.zeros_like(r).at[jnp.arange(x.shape[0])[:, None], ids].set(top)

    def one(y, e):
        gate, up, down, w = e
        return y + w[:, None] * ((jax.nn.silu(x @ gate) * (x @ up)) @ down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (lw["we_gate"], lw["we_up"], lw["we_down"], weight.T))
    return y


def layer(x, lw, s):
    """One layer over one stream x [T, D]: attention causal over blocks."""
    t = x.shape[0]
    blk = jnp.arange(t) // s["B"]
    q, k, v = qkv(x, lw, s)
    x = x + attend(q, k, v, blk[None, :] <= blk[:, None]).reshape(t, -1) @ lw["wo"]
    return x + experts(rms_norm(x, lw["post_norm"], s["eps"]), lw, s), (k, v)


def layer_streams(xs, lw, s):
    """One layer over the clean stream xs[0] and the masked variants
    xs[1:], each [T, D]: a stream's queries see the CLEAN keys of the
    blocks to their left and their own stream's keys in their own block."""
    n, t, _ = xs.shape
    blk = jnp.arange(t) // s["B"]
    allowed = jnp.concatenate(
        [blk[None, :] < blk[:, None], blk[None, :] == blk[:, None]], axis=1)
    q0, k0, v0 = qkv(xs[0], lw, s)

    def one(x):
        q, k, v = qkv(x, lw, s)
        a = attend(q, jnp.concatenate([k0, k]), jnp.concatenate([v0, v]), allowed)
        return x + a.reshape(t, -1) @ lw["wo"]

    xs = jax.lax.map(one, xs)
    flat = xs.reshape(n * t, -1)
    return (flat + experts(rms_norm(flat, lw["post_norm"], s["eps"]), lw, s)).reshape(xs.shape)


@jax.jit
def _layer_f32(layers, i):
    return jax.tree_util.tree_map(lambda a: a[i].astype(jnp.float32), layers)


def _head(x, weights, s):
    return rms_norm(x, weights["final_norm"].astype(jnp.float32), s["eps"]) @ weights[
        "lm_head"].astype(jnp.float32)


def block_forward(config: dict, weights: dict, tokens, committed, return_kv: bool = False):
    """Logits ``[T, V]`` in float32 for one sequence at positions 0..T-1:
    a committed position feeds its token, every other the mask token. With
    ``return_kv`` also the K and V each layer computed, ``[L, T, KH, Dh]``."""
    s = sizes(config)
    ids = jnp.where(jnp.asarray(committed, bool), jnp.asarray(tokens, jnp.int32), s["mask"])
    one_layer = jax.jit(lambda x, lw: layer(x, lw, s))
    ks, vs = [], []
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][ids].astype(jnp.float32)
        for i in range(s["L"]):
            x, (k, v) = one_layer(x, _layer_f32(weights["layers"], i))
            ks.append(k)
            vs.append(v)
        logits = jax.jit(lambda x: _head(x, weights, s))(x)
    return (logits, jnp.stack(ks), jnp.stack(vs)) if return_kv else logits


def forward_many(config: dict, weights: dict, sequences: list) -> list:
    """For each sequence of T token ids, ``[T, V]`` float32 logits whose row
    ``i`` is for the token at position ``i + 1``, from the pass that commits
    it under ``sequential`` one token a pass (see the module's docstring)."""
    s = sizes(config)
    b = s["B"]
    streams = jax.jit(lambda xs, lw: layer_streams(xs, lw, s))
    head = jax.jit(lambda x, w: _head(x, w, s))
    # Every sequence is padded with mask tokens to the longest one's
    # blocks, so that one program serves them all (blocks to the right are
    # never seen from the left).
    t_pad = -(-(max(len(seq) for seq in sequences) + 1) // b) * b
    with jax.default_matmul_precision("highest"):
        xs_all = []
        for seq in sequences:
            t = len(seq)
            toks = np.full(t_pad, s["mask"], np.int64)
            toks[:t] = seq
            offset = np.arange(t_pad) % b
            # Stream 0 clean; stream 1 + o has the first o positions of
            # every block clean and the rest masked.
            ids = np.stack([toks] + [np.where(offset < o, toks, s["mask"]) for o in range(b)])
            xs_all.append(weights["embed"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32))
        for i in range(s["L"]):
            lw = _layer_f32(weights["layers"], i)
            xs_all = [streams(xs, lw) for xs in xs_all]
        out = []
        for seq, xs in zip(sequences, xs_all):
            pos = jnp.arange(1, len(seq) + 1)
            out.append(head(xs[1 + pos % b, pos], weights))
    return out


def commit(rule: str, n: int, threshold: float, committed, confidence):
    """The positions a pass commits: a bool array over the block."""
    committed = np.asarray(committed, bool)
    left = np.flatnonzero(~committed)
    n = min(n, len(left))
    conf = np.where(committed, -np.inf, np.asarray(confidence, np.float64))
    surest = np.argsort(-conf, kind="stable")[:n]
    pick = np.zeros(len(committed), bool)
    if rule == "sequential":
        pick[left[:n]] = True
    elif rule == "low_confidence_static":
        pick[surest] = True
    elif rule == "low_confidence_dynamic":
        high = conf > threshold
        if high.sum() >= n:
            pick = high
        else:
            pick[surest] = True
    else:
        raise ValueError(f"unknown rule {rule!r}")
    return pick


def generate(config: dict, weights: dict, prompt: list, max_tokens: int,
             rule: str = "sequential", denoising_steps: int | None = None,
             confidence_threshold: float = 0.9) -> dict:
    """Greedy generation by diffusion over blocks, with no cache: every pass
    runs the whole sequence so far. Returns the ``max_tokens`` tokens after
    the prompt and, for each, the number of the pass that committed it
    (passes counted from 0 over the request; a block that is complete
    counts one more, the served path's store pass) and the logits row of
    that pass at its position."""
    s = sizes(config)
    b = s["B"]
    n = b // (denoising_steps or b)
    start = len(prompt) // b * b
    seq = list(prompt[:start])  # clean; the open block follows
    block = np.asarray(list(prompt[start:]) + [0] * (b - len(prompt) + start))
    committed = np.arange(b) < len(prompt) - start
    pass_of, logits_of, n_pass = {}, {}, 0
    need = len(prompt) + max_tokens
    while start < need:
        logits = np.asarray(block_forward(
            config, weights, seq + block.tolist(),
            [True] * start + committed.tolist()))[start:]
        drawn = logits.argmax(-1)
        prob = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
        pick = commit(rule, n, confidence_threshold, committed,
                      prob[np.arange(b), drawn])
        block = np.where(pick, drawn, block)
        committed = committed | pick
        for j in np.flatnonzero(pick):
            pass_of[start + int(j)], logits_of[start + int(j)] = n_pass, logits[j]
        n_pass += 1
        if committed.all():
            n_pass += 1  # the store pass
            seq += [int(t) for t in block]
            start += b
            block, committed = np.zeros(b, np.int64), np.zeros(b, bool)
    span = range(len(prompt), need)
    return {"tokens": seq[len(prompt):need],
            "commit_passes": [pass_of[p] for p in span],
            "logits": [logits_of[p] for p in span]}
