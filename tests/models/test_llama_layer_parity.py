"""One decoder layer of ``models/llama.py`` against the same arithmetic
written out plainly, for each variant of the Q/K/V projections and the rope
that the class flags select.

``_make_layer_fn`` is ONE body for every family registered through
``models/llama.py``; what changes how the projections are formulated
(PR 27: the head split kept out of the three dots) must leave every variant
with the output it had. The HF-parity tests of ``test_model_zoo.py`` hold
whole models to transformers; this holds the layer alone, so a variant's
fault shows as that variant's and not as "logits differ".

The reference is float32 numpy from the layer's own weights; the layer runs
in float32 (tight) and in bfloat16 (bf16 tolerance). One prefill request,
so attention is plain causal softmax with grouped KV heads.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.models.utils import build_prefill_metadata

T, D, H, KH, DH, F, SLOTS, RANK = 12, 64, 4, 2, 16, 96, 3, 4
EPS = 1e-6

# name -> (config keys, class flags)
VARIANTS = {
    "plain": ({}, {}),
    "attention_bias": ({"attention_bias": True}, {}),
    "clip_qkv": ({}, {"clip_qkv": 0.3}),
    "qk_norm": ({}, {"qk_norm": True}),
    "qk_norm_full": ({}, {"qk_norm_full": True}),
    "rope_interleaved": ({}, {"rope_interleaved": True}),
    "partial_rotary": ({"partial_rotary_factor": 0.5}, {}),
    "partial_rotary_interleaved_bias": (
        {"partial_rotary_factor": 0.5, "attention_bias": True},
        {"rope_interleaved": True},
    ),
    "int8": ({}, {}),
    "lora": ({}, {"enable_lora": True}),
}


def _model(name, dtype):
    from vllm_tpu.models.llama import LlamaForCausalLM

    cfg_keys, flags = VARIANTS[name]
    cfg = types.SimpleNamespace(
        num_hidden_layers=1, hidden_size=D, num_attention_heads=H,
        num_key_value_heads=KH, head_dim=DH, intermediate_size=F,
        vocab_size=32, rms_norm_eps=EPS, tie_word_embeddings=False,
        max_position_embeddings=64, rope_theta=10000.0, rope_scaling=None,
        **cfg_keys,
    )
    model = LlamaForCausalLM(
        cfg, dtype=dtype, quantization="int8" if name == "int8" else None
    )
    for k, v in flags.items():
        setattr(model, k, v)
    return model


def _layer_params(model, name, dtype, rng):
    """Layer 0 of the dummy tree, with the leaves the dummy scheme leaves at
    one or zero (norms, biases) drawn too, so that each of them matters."""
    lp = jax.tree.map(
        lambda a: a[0], model.init_dummy_params(jax.random.PRNGKey(7))["layers"]
    )

    def draw(shape, scale, mean=0.0):
        return jnp.asarray(mean + scale * rng.standard_normal(shape), dtype)

    for key in ("input_norm", "post_norm", "q_norm", "k_norm"):
        if key in lp:
            lp[key] = draw(lp[key].shape, 0.2, 1.0)
    for key in ("bq", "bk", "bv"):
        if key in lp:
            lp[key] = draw(lp[key].shape, 0.3)
    lora = None
    if name == "lora":
        for key in ("wq", "wk", "wv", "wo", "wgate", "wup", "wdown"):
            d_in, d_out = lp[key].shape
            lp[f"lora_a_{key}"] = draw((SLOTS, d_in, RANK), d_in**-0.5)
            b = draw((SLOTS, RANK, d_out), 0.3)
            lp[f"lora_b_{key}"] = b.at[0].set(0)  # slot 0 = no adapter
        lora = (
            jnp.asarray(rng.integers(0, SLOTS, T), jnp.int32),
            jnp.asarray([0.0, 2.0, 0.5], jnp.float32),
        )
    return lp, lora


def _f32(a):
    return np.asarray(a, np.float32)


def _rms(x, w):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + EPS) * _f32(w)


def _reference_layer(model, lp, x, lora):
    """The layer as ``_make_layer_fn`` had it before PR 27, in float32:
    norm, three separate projections on [T, heads * head_dim], bias, clip,
    full-vector norm, head split, per-head norm, rope, attention, output
    projection, gated MLP."""
    from vllm_tpu.layers.quant import QuantizedLinear

    def weight(key):
        w = lp[key]
        if isinstance(w, QuantizedLinear):
            return _f32(w.q) * _f32(w.scale)
        return _f32(w)

    def proj(h, key):
        out = h @ weight(key)
        if lora is not None:
            slot, scaling = np.asarray(lora[0]), _f32(lora[1])
            a, b = _f32(lp[f"lora_a_{key}"]), _f32(lp[f"lora_b_{key}"])
            for i in range(h.shape[0]):
                out[i] += (h[i] @ a[slot[i]]) @ b[slot[i]] * scaling[slot[i]]
        return out

    def rope(v):  # [T, heads, DH]
        rd = model.rope.rotary_dim
        cos = model.rope._cos_np[:T, None, :].astype(np.float32)
        sin = model.rope._sin_np[:T, None, :].astype(np.float32)
        out = v.copy()
        if model.rope_interleaved:
            x1, x2 = v[..., 0:rd:2], v[..., 1:rd:2]
            out[..., 0:rd:2] = x1 * cos - x2 * sin
            out[..., 1:rd:2] = x2 * cos + x1 * sin
        else:
            x1, x2 = v[..., : rd // 2], v[..., rd // 2 : rd]
            out[..., : rd // 2] = x1 * cos - x2 * sin
            out[..., rd // 2 : rd] = x2 * cos + x1 * sin
        return out

    h = _rms(x, lp["input_norm"])
    q, k, v = proj(h, "wq"), proj(h, "wk"), proj(h, "wv")
    if model.attention_bias:
        q, k, v = q + _f32(lp["bq"]), k + _f32(lp["bk"]), v + _f32(lp["bv"])
    if model.clip_qkv is not None:
        q, k, v = (np.clip(a, -model.clip_qkv, model.clip_qkv) for a in (q, k, v))
    if model.qk_norm_full:
        q, k = _rms(q, lp["q_norm"]), _rms(k, lp["k_norm"])
    q, k, v = q.reshape(T, H, DH), k.reshape(T, KH, DH), v.reshape(T, KH, DH)
    if model.qk_norm:
        q, k = _rms(q, lp["q_norm"]), _rms(k, lp["k_norm"])
    q, k = rope(q), rope(k)

    kq, vq = np.repeat(k, H // KH, axis=1), np.repeat(v, H // KH, axis=1)
    scores = np.einsum("thd,shd->hts", q, kq) * model.scale
    scores = np.where(np.tril(np.ones((T, T), bool)), scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    attn = np.einsum("hts,shd->thd", p, vq).reshape(T, H * DH)

    x = x + proj(attn, "wo")
    h2 = _rms(x, lp["post_norm"])
    gate, up = proj(h2, "wgate"), proj(h2, "wup")
    x = x + proj(gate / (1.0 + np.exp(-gate)) * up, "wdown")
    return x, k, v


def _written_kv(kv, md):
    """This step's K and V rows as ``write_kv`` laid them into layer 0."""
    from vllm_tpu.ops.attention import packed_kv_layout

    _, nb, bs, rows, lanes = kv.shape
    got = _f32(kv[0].reshape(nb * bs, rows, lanes)[np.asarray(md.slot_mapping)])
    if packed_kv_layout(DH):  # [T, KH, 2 DH]: k || v on the lane axis
        return got[..., :DH], got[..., DH:]
    return got[:, 0::2], got[:, 1::2]  # [T, 2 KH, DH]: k0, v0, k1, v1, ...


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("name", list(VARIANTS))
def test_layer_matches_plain_arithmetic(name, dtype):
    rng = np.random.default_rng(27)
    model = _model(name, dtype)
    lp, lora = _layer_params(model, name, dtype, rng)
    md, kv = build_prefill_metadata(model, T)
    kv = kv.astype(dtype)
    x = jnp.asarray(rng.standard_normal((T, D)), dtype)

    layer_fn = model._make_layer_fn(
        md, T,
        token_lora_slot=None if lora is None else lora[0],
        lora_scale=None if lora is None else lora[1],
    )
    (out, kv_out), _ = jax.jit(layer_fn)((x, kv), (lp, jnp.int32(0)))

    ref_out, ref_k, ref_v = _reference_layer(model, lp, _f32(x), lora)
    got_k, got_v = _written_kv(kv_out, md)
    # Read at 5e-7 and at 0.016 at most over the variants; a variant left
    # out or applied in the wrong place moves its output by tenths.
    tol = 5e-6 if dtype == jnp.float32 else 3e-2
    for got, ref, what in (
        (_f32(out), ref_out, "layer output"),
        (got_k, ref_k, "K written"),
        (got_v, ref_v, "V written"),
    ):
        err = np.abs(got - ref).max() / np.abs(ref).max()
        assert err < tol, f"{name}: {what} off by {err:.3g} of its range"
