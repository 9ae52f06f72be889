"""Falcon-H1: a Mamba-2 state-space mixer and a rotary GQA attention mixer
side by side in EVERY layer, a dense SwiGLU after them, and a scalar (for
the state-space projection a vector) multiplier on every branch.

Reference analog: ``transformers/models/falcon_h1/modeling_falcon_h1.py``
(``model_type`` ``falcon_h1``). With ``h = rms(x)``::

    x <- x + ssm_out_multiplier * SSM(h)
           + attention_out_multiplier * ATT(attention_in_multiplier * h)
    x <- x + MLP(rms(x))

Both mixers read the same ``h`` and are summed into one residual add.
``ATT``: ``q = h Wq``, ``k = (h Wk) * key_multiplier``, ``v = h Wv``, rope
(rotate-half over the whole head), causal softmax of ``q k / sqrt(head)``,
``Wo``. ``SSM`` (``I = mamba_d_ssm`` = heads x head size, ``G`` groups of
state size ``N``): ``p = ((ssm_in_multiplier * h) W_in) * mup`` where
``mup`` holds ``ssm_multipliers`` over the five zones of ``W_in``'s columns
(gate z ``[I]``, then the convolution's channels x ``[I]``, B ``[G N]``, C
``[G N]``, then dt ``[H]``); x, B, C pass a depthwise causal convolution
with a bias, then SiLU; ``dt = softplus(dt + dt_bias)``, ``a = exp(-exp(
A_log) dt)``; a head's float32 state ``H [P, N]``, zero at the request's
start: ``H_t = a_t H_{t-1} + dt_t x_t B_t^T``, ``y_t = H_t C_t + D x_t``;
``y <- y * SiLU(z)``, an RMSNorm over each group's channels, ``W_out``.
``MLP(h) = ((h W_up) * SiLU((h W_gate) * mlp_multipliers[0])) W_down *
mlp_multipliers[1]``. ``x = embed[ids] * embedding_multiplier`` going in,
``logits = (rms(x) W_head) * lm_head_multiplier`` coming out. Every
multiplier is read from the config and applied where the published code
applies it: none is folded into a weight.

Cache contract: the hybrid one (``models/kimi_linear.py``), with BOTH kinds
of state under every layer. Pages of K/V for the attention mixer (one KV
group, ``FullAttentionSpec``), and per-request slots (``md.state_slots``)
for the state-space mixer: ``ssm [L, S + 1, H, P, N]`` float32 and ``conv
[L, S + 1, K - 1, C / lanes, lanes]`` in the served dtype (a slot's K - 1
last inputs of the convolution's channels as whole lane tiles, which
``ops/kda.py::kda_short_conv`` reads and writes in place by one DMA a live
row), the last slot the padding rows' scratch. ``ops/mamba.py::
ssd_attention`` updates the slots in place.

The layers run as ONE ``lax.scan`` over the stacked leaves ``[L, ...]``, a
layer an iteration, the layer's index selecting its pages and its slots;
each leaf has one consumer in the body. Param tree::

    embed [V, D], final_norm [D], lm_head [D, V]
    layers/  every leaf [L, ...]
      input_norm post_norm [D]
      wq [D, A*dh]  wk wv [D, KH*dh]  wo [A*dh, D]
      w_in [D, 2I + 2GN + H]  conv_w [K, I + 2GN]  conv_b [I + 2GN]
      a_log dt_bias d_skip [H] f32  ssm_norm [I]  w_out [I, D]
      wgate wup [D, F]  wdown [F, D]
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from vllm_tpu.core.kv_cache_utils import FullAttentionSpec, KVCacheSpec
from vllm_tpu.layers.layernorm import rms_norm
from vllm_tpu.layers.rotary import RotaryEmbedding, _apply_rotate_half
from vllm_tpu.logger import init_logger
from vllm_tpu.ops.attention import (
    AttentionMetadata,
    kv_cache_shape,
    kv_dequant_scale,
    paged_attention,
    write_kv,
)
from vllm_tpu.ops.kda import kda_short_conv
from vllm_tpu.ops.mamba import ssd_attention

logger = init_logger(__name__)

# A leaf's place in the dummy draw's key: fold_in(fold_in(rng, id), layer).
LEAF_IDS = {
    name: i for i, name in enumerate((
        "embed", "lm_head", "wq", "wk", "wv", "wo", "w_in", "conv_w",
        "conv_b", "w_out", "wgate", "wup", "wdown",
    ))
}

# The dummy draw: leaf -> (w, the multipliers of the config that scale the
# leaf's output). The leaf is drawn at ``w / (m sqrt(fan_in))``, ``m`` the
# product of those multipliers, so that what reaches the next operation is
# ``w`` wide whatever the multipliers are. At ``1 / sqrt(fan_in)`` the
# published multipliers (made for trained weights) leave logits 0.008 wide
# (every logprob -12.47), a flat softmax in every head (the keys at 0.011)
# and a residual that is the embedding and nothing else (the branches at
# 0.0375, 0.088 and 0.011): a comparison of logprobs would read none of
# the mixers. ``w`` is 1 but for the head (logits 2.5 wide) and the keys
# (scores 4 wide: a few keys carry a row, as in a trained model; at 1 a
# softmax over a thousand keys is flat). ``w_in``'s five zones are listed
# apart: ``ssm_multipliers`` scales each. No width changes a shape, a
# count or a step's time.
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
CONV_BIAS_WIDTH = 0.1  # of a unit normal: small and not zero
# exp(A_log) from 1 to A_MAX over the heads, and softplus(dt_bias) from
# DT_MIN to DT_MAX beside it: a head's memory runs from 1 / (1 x 0.001), a
# thousand tokens, down to 1 / (32 x 0.1), a third of one.
A_MAX, DT_MIN, DT_MAX = 32.0, 1e-3, 1e-1


def multiplier(config: Any, name: str) -> float:
    """``name`` or ``name.i`` (an entry of a list) of the config."""
    key, _, index = name.partition(".")
    value = getattr(config, key)
    return float(value[int(index)] if index else value)


def draw_width(config: Any, leaf: str) -> float:
    """``w / m`` of ``DRAW_WIDTHS`` for this config (times 1 / sqrt(fan_in))."""
    w, names = DRAW_WIDTHS[leaf]
    return w / math.prod(multiplier(config, n) for n in names)


class FalconH1ForCausalLM:
    supports_lora = False
    enable_lora = False
    is_hybrid_ssm = True  # per-request state slots (SSM matrix + conv tail)
    max_state_slots = 256  # set by the worker
    # ``cache["paged"]`` is plain K/V pages under ``paged_attention``: the
    # runner counts the attention kernels' work as it does a dense model's.
    plain_kv_pages = True

    # Decay parameters, the step's bias and the skip stay f32 at load.
    KEEP_F32_SUFFIXES = ("a_log", "dt_bias", "d_skip")

    def __init__(self, hf_config: Any, dtype=jnp.bfloat16,
                 quantization: str | None = None) -> None:
        if quantization:
            logger.warning(
                "weight quantization is not yet supported for hybrid "
                "models; running %s unquantized", type(self).__name__,
            )
        c = hf_config
        self.hf_config = c
        self.dtype = dtype
        self.quantization = None
        self.num_layers = c.num_hidden_layers
        self.hidden_size = c.hidden_size
        self.vocab_size = c.vocab_size
        self.intermediate_size = c.intermediate_size
        self.rms_eps = getattr(c, "rms_norm_eps", 1e-5)
        self.tie_embeddings = getattr(c, "tie_word_embeddings", False)
        for flag in ("attention_bias", "mamba_proj_bias", "mlp_bias",
                     "projectors_bias"):
            if getattr(c, flag, False):
                raise NotImplementedError(f"{flag} with this family")
        if not getattr(c, "mamba_rms_norm", True) or getattr(
                c, "mamba_norm_before_gate", False):
            raise NotImplementedError(
                "this family's gated norm is served as published: "
                "mamba_rms_norm true, mamba_norm_before_gate false")

        # Attention geometry.
        self.num_heads = c.num_attention_heads
        self.num_kv_heads = getattr(c, "num_key_value_heads", self.num_heads)
        self.head_dim = getattr(c, "head_dim", None) or (
            self.hidden_size // self.num_heads)
        self.scale = self.head_dim ** -0.5
        self.rope = RotaryEmbedding(
            head_dim=self.head_dim,
            max_position=getattr(c, "max_position_embeddings", 8192),
            theta=getattr(c, "rope_theta", 10000.0),
            rope_scaling=getattr(c, "rope_scaling", None),
        )

        # State-space geometry. ``mamba_d_ssm`` is the mixer's width where
        # it is given (here NOT ``mamba_expand * hidden_size``).
        self.m_heads = c.mamba_n_heads
        self.m_head_dim = c.mamba_d_head
        self.d_ssm = getattr(c, "mamba_d_ssm", None) or (
            c.mamba_expand * self.hidden_size)
        if self.d_ssm != self.m_heads * self.m_head_dim:
            raise ValueError(
                f"mamba_d_ssm {self.d_ssm} is not mamba_n_heads "
                f"{self.m_heads} x mamba_d_head {self.m_head_dim}")
        self.n_groups = c.mamba_n_groups
        self.state_size = c.mamba_d_state
        self.conv_kernel = c.mamba_d_conv
        self.conv_bias = bool(getattr(c, "mamba_conv_bias", True))
        self.bc_dim = self.n_groups * self.state_size
        self.conv_dim = self.d_ssm + 2 * self.bc_dim
        self.in_dim = self.d_ssm + self.conv_dim + self.m_heads
        # The tail's lanes: whole tiles of 128 where the channels divide.
        self.conv_lanes = 128 if self.conv_dim % 128 == 0 else self.conv_dim
        self.num_linear_attn_layers = self.num_layers

        # The multipliers, each where the published code applies it.
        self.embedding_multiplier = float(c.embedding_multiplier)
        self.lm_head_multiplier = float(c.lm_head_multiplier)
        self.attention_in_multiplier = float(c.attention_in_multiplier)
        self.attention_out_multiplier = float(c.attention_out_multiplier)
        self.key_multiplier = float(c.key_multiplier)
        self.ssm_in_multiplier = float(c.ssm_in_multiplier)
        self.ssm_out_multiplier = float(c.ssm_out_multiplier)
        self.gate_multiplier, self.down_multiplier = (
            float(m) for m in c.mlp_multipliers)
        self.ssm_multipliers = tuple(float(m) for m in c.ssm_multipliers)

    def zones(self) -> tuple[tuple[str, int], ...]:
        """The five zones of ``w_in``'s columns, in order, and their widths."""
        return (("z", self.d_ssm), ("x", self.d_ssm), ("B", self.bc_dim),
                ("C", self.bc_dim), ("dt", self.m_heads))

    def mup_vector(self) -> jnp.ndarray:
        """``ssm_multipliers`` laid over ``w_in``'s columns, ``[in_dim]``."""
        return jnp.concatenate([
            jnp.full((width,), m, jnp.float32)
            for (_, width), m in zip(self.zones(), self.ssm_multipliers)])

    # ------------------------------------------------------------------
    # Params
    # ------------------------------------------------------------------

    def _leaf_shapes(self) -> dict[str, tuple[tuple, int]]:
        """A layer's leaf -> (shape, fan_in; 0 = not drawn from a normal)."""
        D, F = self.hidden_size, self.intermediate_size
        A, KH, dh = self.num_heads, self.num_kv_heads, self.head_dim
        I, C, K = self.d_ssm, self.conv_dim, self.conv_kernel
        return {
            "input_norm": ((D,), 0), "post_norm": ((D,), 0),
            "wq": ((D, A * dh), D), "wk": ((D, KH * dh), D),
            "wv": ((D, KH * dh), D), "wo": ((A * dh, D), A * dh),
            "w_in": ((D, self.in_dim), D),
            "conv_w": ((K, C), K),
            **({"conv_b": ((C,), 0)} if self.conv_bias else {}),
            "a_log": ((self.m_heads,), 0), "dt_bias": ((self.m_heads,), 0),
            "d_skip": ((self.m_heads,), 0), "ssm_norm": ((I,), 0),
            "w_out": ((I, D), I),
            "wgate": ((D, F), D), "wup": ((D, F), D), "wdown": ((F, D), F),
        }

    def init_dummy_params(self, rng: jax.Array, dtype=None) -> dict:
        dtype = dtype or self.dtype
        c = self.hf_config

        @partial(jax.jit, static_argnums=(1, 3))
        def normal(key, shape, scale, out_dtype):
            return (
                jax.random.normal(key, shape, jnp.float32) * scale
            ).astype(out_dtype)

        def key_of(name: str, layer: int):
            return jax.random.fold_in(
                jax.random.fold_in(rng, LEAF_IDS[name]), layer)

        def draw(name: str, layer: int, shape, fan_in):
            hm = self.m_heads
            if name == "a_log":
                return jnp.log(jnp.linspace(1.0, A_MAX, hm, dtype=jnp.float32))
            if name == "dt_bias":
                dt = jnp.exp(jnp.linspace(
                    math.log(DT_MIN), math.log(DT_MAX), hm, dtype=jnp.float32))
                return jnp.log(jnp.expm1(dt))
            if name == "d_skip":
                return jnp.ones((hm,), jnp.float32)
            if name.endswith("norm"):
                return jnp.ones(shape, dtype)
            if name == "conv_b":
                return normal(key_of(name, layer), shape,
                              jnp.float32(CONV_BIAS_WIDTH), dtype)
            if name == "w_in":
                # One draw, each zone's columns at the zone's width.
                width = jnp.concatenate([
                    jnp.full((n,), draw_width(c, f"w_in.{zone}"), jnp.float32)
                    for zone, n in self.zones()])
                return normal(key_of(name, layer), shape,
                              width / math.sqrt(fan_in), dtype)
            return normal(key_of(name, layer), shape,
                          jnp.float32(draw_width(c, name) / math.sqrt(fan_in)),
                          dtype)

        D = self.hidden_size
        params: dict = {
            "embed": draw("embed", 0, (self.vocab_size, D), D),
            "final_norm": jnp.ones((D,), dtype),
        }
        if not self.tie_embeddings:
            params["lm_head"] = draw("lm_head", 0, (D, self.vocab_size), D)
        # A stack is drawn a layer at a time into its place: the float32
        # draw of one layer is live beside the stacks, not a second stack.
        @partial(jax.jit, donate_argnums=(0,))
        def put(stack, w, i):
            return jax.lax.dynamic_update_index_in_dim(stack, w, i, 0)

        params["layers"] = {}
        for name, (shape, fan_in) in self._leaf_shapes().items():
            first = draw(name, 0, shape, fan_in)
            stack = jnp.zeros((self.num_layers,) + shape, first.dtype)
            for layer in range(self.num_layers):
                stack = put(stack, first if layer == 0 else
                            draw(name, layer, shape, fan_in), layer)
            params["layers"][name] = stack
        return params

    def hf_weight_map(self) -> dict:
        m = {
            "model.embed_tokens.weight": ("embed", False),
            "model.final_layernorm.weight": ("final_norm", False),
        }
        if not self.tie_embeddings:
            m["lm_head.weight"] = ("lm_head", True)
        per_layer = {
            "input_layernorm.weight": ("input_norm", False),
            "pre_ff_layernorm.weight": ("post_norm", False),
            "self_attn.q_proj.weight": ("wq", True),
            "self_attn.k_proj.weight": ("wk", True),
            "self_attn.v_proj.weight": ("wv", True),
            "self_attn.o_proj.weight": ("wo", True),
            "mamba.in_proj.weight": ("w_in", True),
            "mamba.conv1d.weight": ("conv_w", False),
            "mamba.conv1d.bias": ("conv_b", False),
            "mamba.A_log": ("a_log", False),
            "mamba.dt_bias": ("dt_bias", False),
            "mamba.D": ("d_skip", False),
            "mamba.norm.weight": ("ssm_norm", False),
            "mamba.out_proj.weight": ("w_out", True),
            "feed_forward.gate_proj.weight": ("wgate", True),
            "feed_forward.up_proj.weight": ("wup", True),
            "feed_forward.down_proj.weight": ("wdown", True),
        }
        if not self.conv_bias:
            del per_layer["mamba.conv1d.bias"]
        for i in range(self.num_layers):
            for name, (ours, tr) in per_layer.items():
                m[f"model.layers.{i}.{name}"] = (f"layers.{ours}.{i}", tr)
        return m

    def postprocess_weight(self, leaf_path: str, arr):
        import numpy as np

        if leaf_path.endswith(tuple("." + s for s in self.KEEP_F32_SUFFIXES)):
            return np.asarray(arr, np.float32)
        if leaf_path.endswith(".conv_w"):
            return np.swapaxes(arr.squeeze(-2), -1, -2)  # [L, C, 1, K] -> [L, K, C]
        return arr

    def load_params(self, path: str, dtype=None, shardings=None) -> dict:
        from vllm_tpu.models.loader import load_params_from

        return load_params_from(self, path, dtype or self.dtype, shardings)

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------

    def _ssm_mixer(self, h, lp, cache, li, md, tok_req, qsl, fresh, mup):
        """``(out [T, D], cache)`` of one layer's state-space mixer."""
        t = h.shape[0]
        I, bc = self.d_ssm, self.bc_dim
        Hm, Pd, G, N = self.m_heads, self.m_head_dim, self.n_groups, self.state_size
        slots = md.state_slots
        f32 = jnp.float32
        with jax.named_scope("ssm_in_proj"):
            p = (h * self.ssm_in_multiplier).astype(h.dtype) @ lp["w_in"]
            p = (p.astype(f32) * mup).astype(h.dtype)  # [T, in_dim]
            z, xbc, dt_raw = (
                p[:, :I], p[:, I: I + self.conv_dim], p[:, I + self.conv_dim:])
        with jax.named_scope("ssm_conv"):
            y, conv = kda_short_conv(
                (xbc,), cache["conv"], (lp["conv_w"],), li, slots, fresh,
                tok_req, qsl, md.num_seqs[0], decode_only=md.decode_only,
                bias=lp["conv_b"] if self.conv_bias else None)
            cache = {**cache, "conv": conv}
            y = jax.nn.silu(y).reshape(t, self.conv_dim)  # f32
            x = y[:, :I].reshape(t, Hm, Pd)
            b = y[:, I: I + bc].reshape(t, G, N)
            c = y[:, I + bc:].reshape(t, G, N)
        with jax.named_scope("ssm_rule"):
            dt = jax.nn.softplus(dt_raw.astype(f32) + lp["dt_bias"].astype(f32))
            ln_a = -jnp.exp(lp["a_log"].astype(f32)) * dt  # [T, Hm] <= 0
            o, ssm = ssd_attention(
                x, dt, ln_a, b, c, cache["ssm"], li, slots, fresh, qsl,
                md.num_seqs[0], decode_only=md.decode_only)
            cache = {**cache, "ssm": ssm}
            o = o + lp["d_skip"].astype(f32)[:, None] * x
        with jax.named_scope("ssm_out_proj"):
            # The gate, then an RMSNorm over each group's channels.
            o = o.reshape(t, G, I // G) * jax.nn.silu(
                z.astype(f32)).reshape(t, G, I // G)
            o = o * jax.lax.rsqrt(
                jnp.mean(o * o, axis=-1, keepdims=True) + self.rms_eps)
            o = o.reshape(t, I) * lp["ssm_norm"].astype(f32)
            out = o.astype(self.dtype) @ lp["w_out"]
        return out, cache

    def _attention(self, h, lp, paged, li, md, cos, sin):
        t = h.shape[0]
        A, KH, dh = self.num_heads, self.num_kv_heads, self.head_dim
        with jax.named_scope("qkv_proj"):
            if self.attention_in_multiplier != 1.0:
                h = (h * self.attention_in_multiplier).astype(h.dtype)
            q = h @ lp["wq"]
            k = ((h @ lp["wk"]) * self.key_multiplier).astype(h.dtype)
            v = h @ lp["wv"]
        # As models/llama.py: the projections end here, so that each reads
        # its stack in place.
        q, k, v = jax.lax.optimization_barrier((q, k, v))
        q = _apply_rotate_half(q.reshape(t, A, dh), cos, sin, dh)
        k = _apply_rotate_half(k.reshape(t, KH, dh), cos, sin, dh)
        v = v.reshape(t, KH, dh)
        kv_scale = kv_dequant_scale(paged)
        with jax.named_scope("attn"):
            paged = write_kv(paged, li, k, v, md.slot_mapping)
            attn = paged_attention(
                q, paged, li, md, self.scale, sliding_window=None,
                k_scale=kv_scale, v_scale=kv_scale)
        with jax.named_scope("o_proj"):
            return attn.reshape(t, A * dh) @ lp["wo"], paged

    def apply(
        self,
        params: dict,
        kv_cache: dict,  # {"paged", "conv", "ssm"}
        input_ids: jnp.ndarray,  # [T]
        md: AttentionMetadata,
        token_lora_slot: jnp.ndarray | None = None,  # unused
    ) -> tuple[jnp.ndarray, dict]:
        x = (params["embed"][input_ids].astype(jnp.float32)
             * self.embedding_multiplier).astype(self.dtype)
        t = x.shape[0]
        assert md.state_slots is not None, "hybrid model needs state slots"
        r = md.state_slots.shape[0]
        if md.decode_only:
            tok_req = jnp.arange(t, dtype=jnp.int32)
            qsl = jnp.arange(r + 1, dtype=jnp.int32)
        else:
            tok_req, qsl = md.token_req_idx, md.query_start_loc
        first_pos = md.positions[jnp.clip(qsl[:-1], 0, t - 1)]
        fresh = first_pos == 0
        cos = self.rope.cos[md.positions][:, None, :]
        sin = self.rope.sin[md.positions][:, None, :]
        mup = self.mup_vector()

        def layer_fn(carry, inputs):
            x, cache = carry
            lp, li = inputs
            h = rms_norm(x, lp["input_norm"], self.rms_eps)
            ssm_out, cache = self._ssm_mixer(
                h, lp, cache, li, md, tok_req, qsl, fresh, mup)
            attn_out, paged = self._attention(
                h, lp, cache["paged"], li, md, cos, sin)
            cache = {**cache, "paged": paged}
            x = x + (
                ssm_out.astype(jnp.float32) * self.ssm_out_multiplier
                + attn_out.astype(jnp.float32) * self.attention_out_multiplier
            ).astype(x.dtype)
            h2 = rms_norm(x, lp["post_norm"], self.rms_eps)
            with jax.named_scope("mlp"):
                gate = (h2 @ lp["wgate"]).astype(jnp.float32) * self.gate_multiplier
                up = h2 @ lp["wup"]
                act = (jax.nn.silu(gate) * up.astype(jnp.float32)).astype(x.dtype)
                out = (act @ lp["wdown"]).astype(jnp.float32) * self.down_multiplier
            return (x + out.astype(x.dtype), cache), None

        (x, cache), _ = jax.lax.scan(
            layer_fn, (x, kv_cache),
            (params["layers"], jnp.arange(self.num_layers, dtype=jnp.int32)),
        )
        return rms_norm(x, params["final_norm"], self.rms_eps), cache

    def compute_logits(self, params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
        head = params["embed"].T if self.tie_embeddings else params["lm_head"]
        with jax.named_scope("lm_head"):
            return (hidden @ head.astype(hidden.dtype)).astype(
                jnp.float32) * self.lm_head_multiplier

    # ------------------------------------------------------------------
    # Runner contracts
    # ------------------------------------------------------------------

    def get_kv_cache_spec(self, block_size: int, dtype_bytes: int) -> dict[str, KVCacheSpec]:
        """Pages for the attention mixer of EVERY layer (one KV group); the
        state-space mixers' slots are budgeted by ``fixed_state_bytes``."""
        spec = FullAttentionSpec(
            block_size=block_size, num_kv_heads=self.num_kv_heads,
            head_size=self.head_dim, dtype_bytes=dtype_bytes,
        )
        return {f"layers.{i}": spec for i in range(self.num_layers)}

    def state_bytes_per_slot(self) -> int:
        """One request's state over the layers: the float32 matrices and
        the convolution's tail in the served dtype."""
        tail = (self.conv_kernel - 1) * self.conv_dim * jnp.dtype(self.dtype).itemsize
        matrix = self.m_heads * self.m_head_dim * self.state_size * 4
        return self.num_layers * (matrix + tail)

    def fixed_state_bytes(self, max_slots: int) -> int:
        return (max_slots + 1) * self.state_bytes_per_slot()

    def alloc_kv_cache(self, num_blocks: int, block_size: int, dtype) -> dict:
        s = self.max_state_slots + 1  # last slot = padding scratch
        return {
            "paged": jnp.zeros(
                kv_cache_shape(self.num_layers, num_blocks, block_size,
                               self.num_kv_heads, self.head_dim), dtype),
            # A slot is K-1 tokens of the convolution's channels as whole
            # lane tiles, so one DMA moves it (ops/kda.py).
            "conv": jnp.zeros(
                (self.num_layers, s, self.conv_kernel - 1,
                 self.conv_dim // self.conv_lanes, self.conv_lanes), self.dtype),
            "ssm": jnp.zeros(
                (self.num_layers, s, self.m_heads, self.m_head_dim,
                 self.state_size), jnp.float32),
        }

    def param_shardings(self, data_axis: str | None = None,
                        model_axis: str = "tp") -> dict:
        """Replicated: this family is served a chip a replica; no
        tensor-parallel plan yet (2 groups and 4 KV heads)."""
        shapes = jax.eval_shape(
            lambda: self.init_dummy_params(jax.random.PRNGKey(0))
        )
        return jax.tree.map(lambda leaf: P(*([None] * leaf.ndim)), shapes)

    def kv_cache_sharding(self, model_axis: str = "tp") -> dict:
        return {
            "paged": P(None, None, None, None, None),
            "conv": P(None, None, None, None, None),
            "ssm": P(None, None, None, None, None),
        }
