"""Llama-family decoder (Llama 2/3, Mistral, Qwen2 via flags).

Reference analog: ``vllm/model_executor/models/llama.py:81-598``. The design
departs from the reference deliberately (SURVEY.md §7): no parallel-linear
wrapper classes — weights carry GSPMD PartitionSpecs and XLA inserts the
TP collectives; layers are stacked on a leading axis and iterated with
``lax.scan`` so compile time is O(1) in depth and pipeline stages can later
slice the stack.

Param tree::

    embed            [V, D]
    layers/          every leaf stacked [L, ...]
      input_norm     [L, D]
      wq [L, D, H*Dh]  wk/wv [L, D, KH*Dh]  wo [L, H*Dh, D]
      (bq/bk/bv      [L, *]   when attention_bias — Qwen2)
      post_norm      [L, D]
      wgate/wup      [L, D, F]   wdown [L, F, D]
    final_norm       [D]
    lm_head          [D, V]   (absent when tie_word_embeddings)
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from vllm_tpu.core.kv_cache_utils import FullAttentionSpec, KVCacheSpec
from vllm_tpu.layers.activation import silu_and_mul
from vllm_tpu.layers.layernorm import rms_norm
from vllm_tpu.layers.quant import (
    QuantizedEmbedding,
    QuantizedLinear,
    qmm,
    quantize_jnp,
)
from vllm_tpu.lora.layers import lora_delta
from vllm_tpu.layers.rotary import (
    RotaryEmbedding,
    _apply_interleaved,
    _apply_rotate_half,
)
from vllm_tpu.logger import init_logger
from vllm_tpu.ops.attention import (
    AttentionMetadata,
    kv_dequant_scale,
    paged_attention,
    write_kv,
)

logger = init_logger(__name__)


class LlamaForCausalLM:
    # Subclass hooks (Qwen2/Qwen3 etc.)
    attention_bias = False
    # Per-head RMSNorm on q/k after projection (Qwen3, Gemma-3).
    qk_norm = False
    # Set by the worker when LoRA serving is enabled; the runner then adds
    # stacked adapter leaves to the param tree and ships per-token slots.
    enable_lora = False
    supports_lora = True
    # Weight-only quantized matmuls (per-output-channel int8/fp8); norms
    # stay in the model dtype.
    QUANT_KEYS = ("wq", "wk", "wv", "wo", "wgate", "wup", "wdown")
    # Quantize the embedding table (per-row int8, dequant on gather) and
    # lm_head (per-out-channel int8) too. Off by default — projections
    # dominate the FLOPs, so the quality-sensitive table/head stay full
    # precision unless memory demands otherwise (on a 16 GiB chip an 8B
    # model's bf16 embed+head cost 2.1 GiB). Set by the worker from
    # ModelConfig.quantize_embedding_layers; the capability flag below
    # marks model classes whose forward/logits paths handle
    # QuantizedEmbedding (the worker rejects the option elsewhere).
    quantize_embedding_layers = False
    supports_quantized_embedding = True
    # Pipeline parallelism (set by the worker): stage count over the 'pp'
    # mesh axis, microbatch count, and the mesh for shard_map. The layer
    # stack's leading axis is sharded over 'pp'; a collective-permute
    # microbatch pipeline runs inside one jit (``_apply_pp``).
    pp_size = 1
    pp_microbatches = 0  # 0 -> pp_size
    pp_mesh = None
    # Context parallelism (set by the worker): the cache's block dim is
    # sharded over the 'cp' mesh axis; attention runs striped + LSE-merged
    # (``ops/cp_attention.cp_write_and_attend``).
    cp_size = 1
    cp_mesh = None
    # Norm flavor: "rms" (Llama) or "layer" (StableLM-class: classic
    # LayerNorm with bias leaves input_norm_b/post_norm_b/final_norm_b).
    norm_type = "rms"
    # MLP flavor: "gated_silu" (Llama wgate/wup/wdown) or "plain"
    # (GPT-class fc1/fc2 on the wup/wdown leaves, activation mlp_act).
    mlp_type = "gated_silu"
    # False where every layer's MLP is a mixture of experts with leaves of
    # its own (models/mixtral.py): the dummy load then draws no dense
    # wgate/wup/wdown stacks.
    dense_mlp = True
    mlp_act = "silu"  # "gelu" | "gelu_new" | "relu" for plain MLPs
    mlp_bias = False  # b_up/b_down leaves (GPT-class)
    attention_out_bias = False  # bo leaf on the output projection
    # "rope" or "learned" (GPT-2/OPT-class absolute position table on
    # the pos_embed leaf, looked up at positions + learned_pos_offset).
    position_embedding = "rope"
    learned_pos_offset = 0
    # GPT-NeoX/Falcon parallel residual: x + attn(ln1(x)) + mlp(ln2(x))
    # — the MLP reads a norm of the BLOCK INPUT, not of x + attn.
    parallel_residual = False
    # Norm placement: True = pre-norm (Llama); False = post-sublayer
    # norms on the same weight leaves (OLMo-2).
    pre_norm = True
    # qk-norm over the full projected vector, pre-head-split (OLMo-2),
    # vs the per-head qk_norm flag (Qwen3).
    qk_norm_full = False
    # Phi-class biased lm_head (lm_head_b leaf).
    lm_head_bias = False
    # Rope pair layout: False = rotate_half (Llama/NeoX halves), True =
    # interleaved adjacent lanes (GPT-J / GLM / Cohere).
    rope_interleaved = False
    # QKV clipping (OLMo-1 clip_qkv): clamp projections to +-value.
    clip_qkv = None
    # Granite-style scalar modulation hooks (all 1.0 = plain Llama).
    embedding_multiplier = 1.0
    residual_multiplier = 1.0
    logits_scaling = 1.0
    # EAGLE-3: layer indices whose OUTPUT hidden states feed the draft
    # (set by the worker; apply() then returns (hidden, kv, aux_concat)).
    aux_hidden_layers = None
    # lax.scan over the stacked layer weights vs an unrolled Python loop
    # (VLLM_TPU_UNROLL_LAYERS). Scan compiles fast and is the default. What
    # scan was measured to cost (v5e, Mistral-7B widths, PR 27): the bf16
    # program sliced AND transposed the three attention projections out of
    # their stacks a layer, never the whole stack; the head-split barrier
    # in _make_layer_fn ended that, and an int8 tree never did it.
    scan_layers = True

    def __init__(self, hf_config: Any, dtype=jnp.bfloat16,
                 quantization: str | None = None) -> None:
        c = hf_config
        self.hf_config = c
        self.dtype = dtype
        self.quantization = quantization
        self.num_layers = c.num_hidden_layers
        self.hidden_size = c.hidden_size
        self.num_heads = c.num_attention_heads
        self.num_kv_heads = getattr(c, "num_key_value_heads", c.num_attention_heads)
        self.head_dim = getattr(c, "head_dim", None) or c.hidden_size // c.num_attention_heads
        self.intermediate_size = c.intermediate_size
        self.vocab_size = c.vocab_size
        self.rms_eps = getattr(c, "rms_norm_eps", 1e-6)
        self.tie_embeddings = getattr(c, "tie_word_embeddings", False)
        self.attention_bias = getattr(c, "attention_bias", self.attention_bias)
        self.scale = 1.0 / math.sqrt(self.head_dim)
        self.max_position = getattr(c, "max_position_embeddings", 8192)
        self.sliding_window = None  # full attention

        prf = getattr(c, "partial_rotary_factor", 1.0) or 1.0
        self.rope = RotaryEmbedding(
            head_dim=self.head_dim,
            max_position=self.max_position,
            theta=getattr(c, "rope_theta", 10000.0),
            rope_scaling=getattr(c, "rope_scaling", None),
            # StableLM-class partial rotary: only the leading slice of
            # each head rotates.
            rotary_dim=int(self.head_dim * prf) if prf < 1.0 else None,
            # Phi-3-style longrope keeps its pivot at config level.
            original_max_position=getattr(
                c, "original_max_position_embeddings", None
            ),
        )

    # ------------------------------------------------------------------
    # Params
    # ------------------------------------------------------------------

    def init_dummy_params(self, rng: jax.Array, dtype=None) -> dict:
        dtype = dtype or self.dtype
        L, D, H, KH, Dh, F, V = (
            self.num_layers,
            self.hidden_size,
            self.num_heads,
            self.num_kv_heads,
            self.head_dim,
            self.intermediate_size,
            self.vocab_size,
        )
        keys = jax.random.split(rng, 12)

        # One fused program per weight: eagerly, the f32 draw and its
        # scaled copy are both live (7 GiB for a 16-layer 8B-width wup)
        # next to the weights already placed on a 16 GiB chip.
        @partial(jax.jit, static_argnums=(1, 2))
        def init(key, shape, fan_in):
            return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)

        def init_w(key, shape, fan_in, name):
            # Quantize each stacked weight AS IT IS CREATED, generating in
            # bf16: holding the whole fp tree (or f32 temporaries) before
            # quantizing would peak at full-precision model size — an 8B
            # int8 dummy on a 16 GiB chip would OOM.
            if self.quantization and name in self.QUANT_KEYS:
                if shape[0] >= 8 and math.prod(shape) >= 2**28:
                    # Big stacks quantize LAYER-BY-LAYER: the bf16
                    # transient shrinks from the full [L, ...] stack
                    # (3.5 GiB for an 8B wup) to one layer — on a
                    # 16 GiB chip that headroom decides whether the
                    # quantized 8B fits at all.
                    subkeys = jax.random.split(key, shape[0])
                    per = []
                    for i in range(shape[0]):
                        w = (
                            jax.random.normal(
                                subkeys[i], shape[1:], jnp.bfloat16
                            ) / math.sqrt(fan_in)
                        ).astype(jnp.bfloat16)
                        q = quantize_jnp(w, self.quantization)
                        w.delete()
                        per.append(q)
                    stacked = jax.tree_util.tree_map(
                        lambda *xs: jnp.stack(xs), *per
                    )
                    for p in per:
                        for leaf in jax.tree_util.tree_leaves(p):
                            leaf.delete()
                    return stacked
                w = (
                    jax.random.normal(key, shape, jnp.bfloat16)
                    / math.sqrt(fan_in)
                ).astype(jnp.bfloat16)
                q = quantize_jnp(w, self.quantization)
                w.delete()
                return q
            return init(key, shape, fan_in)

        layers = {
            "input_norm": jnp.ones((L, D), dtype),
            "wq": init_w(keys[0], (L, D, H * Dh), D, "wq"),
            "wk": init_w(keys[1], (L, D, KH * Dh), D, "wk"),
            "wv": init_w(keys[2], (L, D, KH * Dh), D, "wv"),
            "wo": init_w(keys[3], (L, H * Dh, D), H * Dh, "wo"),
            "post_norm": jnp.ones((L, D), dtype),
        }
        if self.dense_mlp:
            layers["wup"] = init_w(keys[5], (L, D, F), D, "wup")
            layers["wdown"] = init_w(keys[6], (L, F, D), F, "wdown")
        if self.norm_type == "nonparam_layer":
            del layers["input_norm"], layers["post_norm"]
        if self.dense_mlp and self.mlp_type == "gated_silu":
            layers["wgate"] = init_w(keys[4], (L, D, F), D, "wgate")
        if self.mlp_bias:
            layers["b_up"] = jnp.zeros((L, F), dtype)
            layers["b_down"] = jnp.zeros((L, D), dtype)
        if self.attention_out_bias:
            layers["bo"] = jnp.zeros((L, D), dtype)
        if self.attention_bias:
            layers["bq"] = jnp.zeros((L, H * Dh), dtype)
            layers["bk"] = jnp.zeros((L, KH * Dh), dtype)
            layers["bv"] = jnp.zeros((L, KH * Dh), dtype)
        if self.qk_norm:
            layers["q_norm"] = jnp.ones((L, Dh), dtype)
            layers["k_norm"] = jnp.ones((L, Dh), dtype)
        if self.qk_norm_full:
            layers["q_norm"] = jnp.ones((L, H * Dh), dtype)
            layers["k_norm"] = jnp.ones((L, KH * Dh), dtype)
        if self.norm_type == "layer":
            layers["input_norm_b"] = jnp.zeros((L, D), dtype)
            layers["post_norm_b"] = jnp.zeros((L, D), dtype)
        q_extra = self.quantization and self.quantize_embedding_layers
        if q_extra:
            from vllm_tpu.layers.quant import quantize_embedding_jnp

            w = init(keys[7], (V, D), D)
            embed = quantize_embedding_jnp(w)
            w.delete()
        else:
            embed = init(keys[7], (V, D), D)
        params = {
            "embed": embed,
            "layers": layers,
            "final_norm": jnp.ones((D,), dtype),
        }
        if self.position_embedding == "learned":
            params["pos_embed"] = init(
                jax.random.fold_in(rng, 99),
                (self.max_position + self.learned_pos_offset, D), D,
            )
        if self.norm_type == "layer":
            params["final_norm_b"] = jnp.zeros((D,), dtype)
        if self.norm_type == "nonparam_layer":
            del params["final_norm"]
        if not self.tie_embeddings:
            if q_extra:
                # Per-out-channel int8 regardless of the projection
                # scheme: the head is one [D, V] GEMM per step.
                w = init(keys[8], (D, V), D)
                params["lm_head"] = quantize_jnp(w, "int8")
                w.delete()
            else:
                params["lm_head"] = init(keys[8], (D, V), D)
            if self.lm_head_bias:
                params["lm_head_b"] = jnp.zeros((V,), dtype)
        return params

    # HF checkpoint name -> (our path, transpose, stack-axis layer index fn)
    def hf_weight_map(self) -> dict:
        m = {
            "model.embed_tokens.weight": ("embed", False),
            "model.norm.weight": ("final_norm", False),
        }
        if not self.tie_embeddings:
            m["lm_head.weight"] = ("lm_head", True)
        per_layer = {
            "input_layernorm.weight": ("input_norm", False),
            "self_attn.q_proj.weight": ("wq", True),
            "self_attn.k_proj.weight": ("wk", True),
            "self_attn.v_proj.weight": ("wv", True),
            "self_attn.o_proj.weight": ("wo", True),
            "post_attention_layernorm.weight": ("post_norm", False),
            "mlp.up_proj.weight": ("wup", True),
            "mlp.down_proj.weight": ("wdown", True),
        }
        if self.mlp_type == "gated_silu":
            per_layer["mlp.gate_proj.weight"] = ("wgate", True)
        if self.attention_bias:
            per_layer |= {
                "self_attn.q_proj.bias": ("bq", False),
                "self_attn.k_proj.bias": ("bk", False),
                "self_attn.v_proj.bias": ("bv", False),
            }
        if self.norm_type == "layer":
            m["model.norm.bias"] = ("final_norm_b", False)
            per_layer |= {
                "input_layernorm.bias": ("input_norm_b", False),
                "post_attention_layernorm.bias": ("post_norm_b", False),
            }
        if self.norm_type == "nonparam_layer":
            # OLMo-1: the checkpoint has NO norm weights at all.
            del m["model.norm.weight"]
            del per_layer["input_layernorm.weight"]
            del per_layer["post_attention_layernorm.weight"]
        if self.qk_norm or self.qk_norm_full:
            per_layer |= {
                "self_attn.q_norm.weight": ("q_norm", False),
                "self_attn.k_norm.weight": ("k_norm", False),
            }
        for i in range(self.num_layers):
            for hf_name, (ours, transpose) in per_layer.items():
                m[f"model.layers.{i}.{hf_name}"] = (f"layers.{ours}.{i}", transpose)
        return m

    def load_params(self, path: str, dtype=None, shardings: Any | None = None) -> dict:
        from vllm_tpu.models.loader import load_params_from

        return load_params_from(self, path, dtype or self.dtype, shardings)

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------

    def apply(
        self,
        params: dict,
        kv_cache: jnp.ndarray,  # [L, NB, BS, 2*KH, Dh]
        input_ids: jnp.ndarray,  # [T]
        md: AttentionMetadata,
        token_lora_slot: jnp.ndarray | None = None,  # [T] i32 (LoRA)
        inputs_embeds: jnp.ndarray | None = None,  # [T, D] (multimodal merge)
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        from vllm_tpu.layers.quant import embedding_lookup

        x = (
            inputs_embeds.astype(self.dtype)
            if inputs_embeds is not None
            else embedding_lookup(params["embed"], input_ids, self.dtype)
        )  # [T, D]
        if self.embedding_multiplier != 1.0:
            x = x * self.embedding_multiplier
        if self.position_embedding == "learned":
            x = x + params["pos_embed"][
                jnp.clip(
                    md.positions + self.learned_pos_offset,
                    0, params["pos_embed"].shape[0] - 1,
                )
            ].astype(self.dtype)
        if self.pp_size > 1:
            return self._apply_pp(params, kv_cache, x, md)
        x = self._cp_token_shard(x)
        layer_fn = self._make_layer_fn(
            md, x.shape[0],
            token_lora_slot=token_lora_slot,
            lora_scale=params.get("lora_scaling"),
        )
        # EAGLE-3 aux capture: collect the OUTPUT hidden of three layer
        # indices for the draft's fused conditioning (reference:
        # aux_hidden_state_layers in vllm's llama.py).
        aux_idx = getattr(self, "aux_hidden_layers", None)
        if aux_idx is not None:
            idxs = jnp.asarray(aux_idx, jnp.int32)
            aux0 = jnp.zeros((len(aux_idx),) + x.shape, x.dtype)
            inner_fn = layer_fn

            def layer_fn(carry, inputs):  # noqa: F811 - deliberate wrap
                xc, kv, aux = carry
                (xc, kv), _ = inner_fn((xc, kv), inputs)
                match = (idxs == inputs[1])[:, None, None]
                aux = jnp.where(match, xc[None].astype(aux.dtype), aux)
                return (xc, kv, aux), None

        if self.scan_layers:
            # Scan over the layer stack with the WHOLE cache in the carry:
            # the per-layer scatter + page gathers touch only live slots,
            # and the donated buffer is updated in place (per-layer xs/ys
            # would double-buffer the cache and copy a full layer per
            # iteration).
            carry0 = (
                (x, kv_cache) if aux_idx is None else (x, kv_cache, aux0)
            )
            carry, _ = jax.lax.scan(
                layer_fn,
                carry0,
                (params["layers"],
                 jnp.arange(self.num_layers, dtype=jnp.int32)),
            )
        else:
            # Unrolled: one layer sliced at a time in a program L times
            # the size (slower first use; the persistent cache amortizes
            # the compile only). Under scan no weight-sized temporary is
            # left to avoid (tests/test_tpu_aot_compile.py holds that for
            # the bf16 and the int8 tree).
            carry = (x, kv_cache) if aux_idx is None else (x, kv_cache, aux0)
            for i in range(self.num_layers):
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                carry, _ = layer_fn(carry, (lp, jnp.int32(i)))
        if aux_idx is not None:
            x, new_kv, aux = carry
            t = x.shape[0]
            aux_cat = aux.transpose(1, 0, 2).reshape(t, -1)  # [T, 3D]
            x = self._norm(x, params, "final_norm")
            return x, new_kv, aux_cat
        x, new_kv = carry
        x = self._norm(x, params, "final_norm")
        return x, new_kv

    def _norm(self, x, p, name: str):
        if self.norm_type == "layer":
            from vllm_tpu.layers.layernorm import layer_norm

            return layer_norm(x, p[name], p[name + "_b"], self.rms_eps)
        if self.norm_type == "nonparam_layer":
            # OLMo-1: LayerNorm without learnable parameters.
            import jax.numpy as _jnp

            xf = x.astype(_jnp.float32)
            mu = xf.mean(-1, keepdims=True)
            var = ((xf - mu) ** 2).mean(-1, keepdims=True)
            import jax as _jax

            return ((xf - mu) * _jax.lax.rsqrt(var + self.rms_eps)).astype(
                x.dtype
            )
        return rms_norm(x, p[name], self.rms_eps)

    def _cp_token_shard(self, x: jnp.ndarray) -> jnp.ndarray:
        """Prefill sequence parallelism over the cp axis (VERDICT r4
        missing #6; reference analog: PCP, ``parallel_state.py:1631``).

        Token-dim sharding constraint on the residual stream: GSPMD then
        partitions every norm / projection / MLP matmul over cp (1/cp of
        the prefill FLOPs per rank) and inserts the all-gather exactly at
        the attention shard_map boundary (whose in_specs are replicated —
        the striped-KV partial-attention design is unchanged). The
        TPU-native 'annotate shardings, let XLA place collectives' recipe
        instead of a hand-written ring; the ring schedule is what XLA's
        collective pipelining lowers the gather to on ICI."""
        if self.cp_size <= 1 or self.cp_mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.cp_mesh, P("cp"))
        )

    def _make_layer_fn(self, md: AttentionMetadata, t: int, *,
                       token_lora_slot=None, lora_scale=None,
                       attn_fn=paged_attention, rope_cos_sin=None):
        """One decoder layer as a ``lax.scan`` body over (lp, layer_idx)
        with carry (hidden, kv_cache); shared by the plain and pipelined
        forward paths."""
        H, KH, Dh = self.num_heads, self.num_kv_heads, self.head_dim
        rope_cos, rope_sin = self.rope.cos, self.rope.sin
        bias = self.attention_bias
        use_lora = self.enable_lora and token_lora_slot is not None

        def proj(h, lp, key):
            out = qmm(h, lp[key])
            if use_lora:
                out = out + lora_delta(
                    h, lp[f"lora_a_{key}"], lp[f"lora_b_{key}"],
                    token_lora_slot, lora_scale,
                )
            return out

        def layer_fn(carry, inputs):
            x, kv = carry
            lp, li = inputs
            # pre_norm (Llama): norm the sublayer INPUT; post-norm archs
            # (OLMo-2) norm the sublayer OUTPUT before the residual add,
            # reusing the same weight leaves.
            h = self._norm(x, lp, "input_norm") if self.pre_norm else x

            # The named scopes reach the profiler's device events (the
            # op_name of every operation below them), so that a trace can
            # tell the layer's parts apart where XLA prints only "fusion".
            with jax.named_scope("qkv_proj"):
                q = proj(h, lp, "wq")
                k = proj(h, lp, "wk")
                v = proj(h, lp, "wv")
            if bias:
                q = q + lp["bq"]
                k = k + lp["bk"]
                v = v + lp["bv"]
            if self.clip_qkv is not None:
                q = jnp.clip(q, -self.clip_qkv, self.clip_qkv)
                k = jnp.clip(k, -self.clip_qkv, self.clip_qkv)
                v = jnp.clip(v, -self.clip_qkv, self.clip_qkv)
            if self.qk_norm_full:
                # OLMo-2: RMSNorm over the FULL projected vector,
                # pre-head-split (vs Qwen3's per-head norm below).
                q = rms_norm(q, lp["q_norm"], self.rms_eps)
                k = rms_norm(k, lp["k_norm"], self.rms_eps)
            # The projections end here, as [T, heads * head_dim] rows. Left
            # free, XLA folds the head split below into the three dots, lays
            # their outputs out token-minor, and then slices AND transposes
            # wq/wk/wv out of the [L, ...] stacks into temporaries a layer
            # (9.9 % of the v5e's step at Mistral-7B widths, PERF.md PR 27);
            # behind the barrier each dot reads its stack in place, as
            # wo and the MLP weights are read.
            q, k, v = jax.lax.optimization_barrier((q, k, v))
            q = q.reshape(t, H, Dh)
            k = k.reshape(t, KH, Dh)
            v = v.reshape(t, KH, Dh)
            if self.qk_norm:
                q = rms_norm(q, lp["q_norm"], self.rms_eps)
                k = rms_norm(k, lp["k_norm"], self.rms_eps)

            rope_apply = (
                _apply_interleaved if self.rope_interleaved
                else _apply_rotate_half
            )
            if rope_cos_sin is not None:
                # Precomputed per-token tables (Qwen2-VL m-rope).
                cos = rope_cos_sin[0][:, None, :]
                sin = rope_cos_sin[1][:, None, :]
                q = rope_apply(q, cos, sin, self.rope.rotary_dim)
                k = rope_apply(k, cos, sin, self.rope.rotary_dim)
            elif self.position_embedding == "rope":
                cos = rope_cos[md.positions][:, None, :]
                sin = rope_sin[md.positions][:, None, :]
                q = rope_apply(q, cos, sin, self.rope.rotary_dim)
                k = rope_apply(k, cos, sin, self.rope.rotary_dim)

            kv_scale = kv_dequant_scale(kv)
            if self.cp_size > 1:
                from vllm_tpu.ops.cp_attention import cp_write_and_attend

                kv, attn = cp_write_and_attend(
                    kv, li, k, v, q, md, self.scale,
                    mesh=self.cp_mesh,
                    sliding_window=self.sliding_window,
                    k_scale=kv_scale, v_scale=kv_scale,
                )
            else:
                with jax.named_scope("attn"):
                    kv = write_kv(kv, li, k, v, md.slot_mapping)
                    attn = attn_fn(
                        q, kv, li, md, self.scale,
                        sliding_window=self.sliding_window,
                        k_scale=kv_scale, v_scale=kv_scale,
                    )
            with jax.named_scope("o_proj"):
                attn_out = proj(attn.reshape(t, H * Dh), lp, "wo")
            if self.attention_out_bias:
                attn_out = attn_out + lp["bo"]
            if not self.pre_norm:
                attn_out = self._norm(attn_out, lp, "input_norm")
            if self.parallel_residual:
                # NeoX/Falcon: the MLP reads a norm of the BLOCK input.
                h2 = self._norm(x, lp, "post_norm")
                x = x + self.residual_multiplier * attn_out
            else:
                x = x + self.residual_multiplier * attn_out
                h2 = self._norm(x, lp, "post_norm") if self.pre_norm else x

            with jax.named_scope("mlp"):
                if self.mlp_type == "gated_silu":
                    gate = proj(h2, lp, "wgate")
                    up = proj(h2, lp, "wup")
                    ffn_out = proj(
                        silu_and_mul(jnp.concatenate([gate, up], axis=-1)),
                        lp, "wdown",
                    )
                else:
                    up = proj(h2, lp, "wup")
                    if self.mlp_bias:
                        up = up + lp["b_up"]
                    act = {
                        "gelu": lambda v: jax.nn.gelu(
                            v.astype(jnp.float32), approximate=False
                        ).astype(v.dtype),
                        "gelu_new": lambda v: jax.nn.gelu(
                            v.astype(jnp.float32), approximate=True
                        ).astype(v.dtype),
                        "relu": lambda v: jax.nn.relu(v),
                        # Nemotron/Persimmon squared ReLU.
                        "relu2": lambda v: jnp.square(jax.nn.relu(v)),
                    }[self.mlp_act]
                    ffn_out = proj(act(up), lp, "wdown")
                    if self.mlp_bias:
                        ffn_out = ffn_out + lp["b_down"]
            if not self.pre_norm:
                ffn_out = self._norm(ffn_out, lp, "post_norm")
            x = x + self.residual_multiplier * ffn_out
            # Pin the carry's token sharding each iteration (attention's
            # replicated output would otherwise let propagation drift the
            # residual stream back to replicated).
            x = self._cp_token_shard(x)
            return (x, kv), None

        return layer_fn

    def _apply_pp(
        self,
        params: dict,
        kv_cache: jnp.ndarray,  # [L, ...] sharded P('pp', ...) on axis 0
        x: jnp.ndarray,  # [T, D] embedded inputs (replicated)
        md: AttentionMetadata,
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Collective-permute microbatch pipeline over the 'pp' mesh axis.

        Reference analog: PP layer-range partitioning + send/recv of
        IntermediateTensors (``parallel_state.py:821,916``) and the
        batch-queue bubble fill (``core.py:443``). The TPU formulation is
        the classic GSPMD pipeline: each stage holds L/S layers (leading
        stack axis sharded over 'pp'), M microbatches flow through
        M+S-1 ticks inside ONE jitted program, activations hop stages via
        ``lax.ppermute`` over ICI. Bubbles across steps are additionally
        filled by the engine's in-flight step queue (async_pipeline_depth),
        which plays the role of the reference's batch queue.

        KV correctness across microbatches: microbatch m reaches stage s at
        tick s+m, strictly after m-1's KV for that stage's layers was
        written at tick s+m-1, so causal attention over the step's own
        tokens sees exactly the prefix KV it would in the unpipelined scan.
        The attention inside the pipeline takes the XLA reference path (the
        Pallas kernel's per-request descriptors assume the full [T] batch;
        a microbatch-aware kernel is the optimization seam).
        """
        from functools import partial as _partial

        from jax.sharding import PartitionSpec as P

        from vllm_tpu.ops.attention import ref_ragged_paged_attention
        from vllm_tpu.parallel.mesh import shard_map

        S = self.pp_size
        mesh = self.pp_mesh
        assert mesh is not None, "pp_mesh must be set for pipeline parallel"
        assert self.num_layers % S == 0, (
            f"num_layers {self.num_layers} not divisible by pp={S}"
        )
        t, d = x.shape
        m = self.pp_microbatches or S
        while t % m:
            m //= 2  # token buckets are powers of two
        m = max(m, 1)
        tm = t // m
        ls = self.num_layers // S

        chunks = x.reshape(m, tm, d)
        pos_m = md.positions.reshape(m, tm)
        slot_m = md.slot_mapping.reshape(m, tm)
        tri_m = md.token_req_idx.reshape(m, tm)

        def attn_ref(q, kv, li, md_m, scale, **kw):
            return ref_ragged_paged_attention(q, kv, li, md_m, scale, **kw)

        @_partial(
            shard_map,
            mesh=mesh,
            in_specs=(P("pp"), P("pp"), P(), P(), P(), P(), P(), P(), P(),
                      P(), P()),
            out_specs=(P(), P("pp")),
            axis_names={"pp"},
        )
        def run(layers_local, kv_local, chunks, pos_m, slot_m, tri_m,
                block_tables, seq_lens, qsl, logits_idx, num_seqs):
            stage = jax.lax.axis_index("pp")
            varying = _partial(jax.lax.pcast, axis_name=("pp",), to="varying")
            buf = varying(jnp.zeros((tm, d), x.dtype))
            outs = varying(jnp.zeros((m, tm, d), x.dtype))
            li_local = jnp.arange(ls, dtype=jnp.int32)

            def tick(carry, tk):
                buf, outs, kv_l = carry
                mb = jnp.clip(tk - stage, 0, m - 1)
                valid = (tk - stage >= 0) & (tk - stage < m)
                cur = jnp.where(stage == 0, chunks[jnp.clip(tk, 0, m - 1)], buf)
                md_m = AttentionMetadata(
                    positions=pos_m[mb],
                    # Invalid (bubble) ticks scatter into the write-only
                    # null slot 0 instead of corrupting live pages.
                    slot_mapping=jnp.where(valid, slot_m[mb], 0),
                    block_tables=block_tables,
                    seq_lens=seq_lens,
                    query_start_loc=qsl,
                    token_req_idx=tri_m[mb],
                    logits_indices=logits_idx,
                    num_seqs=num_seqs,
                )
                layer_fn = self._make_layer_fn(md_m, tm, attn_fn=attn_ref)
                (cur, kv_l), _ = jax.lax.scan(
                    layer_fn, (cur, kv_l), (layers_local, li_local)
                )
                out_idx = tk - (S - 1)
                do = (stage == S - 1) & (out_idx >= 0) & (out_idx < m)
                upd = jax.lax.dynamic_update_index_in_dim(
                    outs, cur, jnp.clip(out_idx, 0, m - 1), 0
                )
                outs = jnp.where(do, upd, outs)
                nxt = jax.lax.ppermute(
                    cur, "pp", [(i, (i + 1) % S) for i in range(S)]
                )
                return (nxt, outs, kv_l), None

            (buf, outs, kv_local), _ = jax.lax.scan(
                tick, (buf, outs, kv_local),
                jnp.arange(m + S - 1, dtype=jnp.int32),
            )
            # Only the last stage holds real outputs; broadcast them.
            outs = jnp.where(stage == S - 1, outs, jnp.zeros_like(outs))
            outs = jax.lax.psum(outs, "pp")
            return outs.reshape(t, d), kv_local

        hidden, new_kv = run(
            params["layers"], kv_cache, chunks, pos_m, slot_m, tri_m,
            md.block_tables, md.seq_lens, md.query_start_loc,
            md.logits_indices, md.num_seqs,
        )
        hidden = self._norm(hidden, params, "final_norm")
        return hidden, new_kv

    @jax.named_scope("lm_head")
    def compute_logits(self, params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
        from vllm_tpu.layers.quant import embedding_logits

        if self.tie_embeddings:
            logits = embedding_logits(hidden, params["embed"])
        else:
            logits = qmm(hidden, params["lm_head"])
        logits = logits.astype(jnp.float32)
        if "lm_head_b" in params:  # Phi-class biased head
            logits = logits + params["lm_head_b"].astype(jnp.float32)
        if self.logits_scaling != 1.0:
            logits = logits / self.logits_scaling  # Granite semantics
        return logits

    # ------------------------------------------------------------------
    # Runner contracts
    # ------------------------------------------------------------------

    def get_kv_cache_spec(self, block_size: int, dtype_bytes: int) -> dict[str, KVCacheSpec]:
        if self.sliding_window is not None:
            from vllm_tpu.core.kv_cache_utils import SlidingWindowSpec

            spec: KVCacheSpec = SlidingWindowSpec(
                block_size=block_size,
                num_kv_heads=self.num_kv_heads,
                head_size=self.head_dim,
                dtype_bytes=dtype_bytes,
                sliding_window=self.sliding_window,
            )
        else:
            spec = FullAttentionSpec(
                block_size=block_size,
                num_kv_heads=self.num_kv_heads,
                head_size=self.head_dim,
                dtype_bytes=dtype_bytes,
            )
        return {f"layers.{i}": spec for i in range(self.num_layers)}

    def param_shardings(self, data_axis: str | None = None, model_axis: str = "tp") -> dict:
        """GSPMD TP plan (Megatron layout): attention/MLP sharded on the
        head/ffn axis, row-parallel outputs on the input axis, vocab sharded
        embedding + head. XLA inserts the psums the reference performs
        manually in RowParallelLinear (``parallel_state.py:502``)."""
        tp = model_axis
        layers = {
            "input_norm": P(None, None),
            "wq": P(None, None, tp),
            "wk": P(None, None, tp),
            "wv": P(None, None, tp),
            "wo": P(None, tp, None),
            "post_norm": P(None, None),
            "wup": P(None, None, tp),
            "wdown": P(None, tp, None),
        }
        if self.mlp_type == "gated_silu":
            layers["wgate"] = P(None, None, tp)
        if self.mlp_bias:
            layers |= {"b_up": P(None, tp), "b_down": P(None, None)}
        if self.attention_out_bias:
            layers["bo"] = P(None, None)
        if self.attention_bias:
            layers |= {"bq": P(None, tp), "bk": P(None, tp), "bv": P(None, tp)}
        if self.qk_norm:
            layers |= {"q_norm": P(None, None), "k_norm": P(None, None)}
        if self.qk_norm_full:
            # Full-width norm weights shard like the projection output.
            layers |= {"q_norm": P(None, tp), "k_norm": P(None, tp)}
        if self.norm_type == "layer":
            layers |= {
                "input_norm_b": P(None, None),
                "post_norm_b": P(None, None),
            }
        if self.norm_type == "nonparam_layer":
            del layers["input_norm"], layers["post_norm"]
        from vllm_tpu.layers.quant import Int4Linear

        if self.quantization in ("int4", "gptq", "awq"):
            # Packed nibbles shard like the weight; group scale/zero
            # shard like (group axis replicated, output axis as weight).
            for k in self.QUANT_KEYS:
                if k not in layers:
                    continue
                w = layers[k]
                gs = P(w[0], None, w[-1])
                layers[k] = Int4Linear(q=w, scale=gs, zero=gs)
        elif self.quantization:
            # Scale vectors shard like the weight's output axis.
            for k in self.QUANT_KEYS:
                if k not in layers:
                    continue
                w = layers[k]
                layers[k] = QuantizedLinear(q=w, scale=P(w[0], w[-1]))
        if self.pp_size > 1:
            # Layer stacks: leading L axis over the 'pp' stage axis.
            def stage(spec):
                if isinstance(spec, QuantizedLinear):
                    return QuantizedLinear(
                        q=stage(spec.q), scale=stage(spec.scale)
                    )
                if isinstance(spec, Int4Linear):
                    return Int4Linear(
                        q=stage(spec.q), scale=stage(spec.scale),
                        zero=stage(spec.zero),
                    )
                return P("pp", *spec[1:])

            layers = {k: stage(v) for k, v in layers.items()}
        q_extra = self.quantization and self.quantize_embedding_layers
        out = {
            "embed": (
                QuantizedEmbedding(q=P(tp, None), scale=P(tp))
                if q_extra
                else P(tp, None)
            ),
            "layers": layers,
            "final_norm": P(None),
        }
        if self.norm_type == "layer":
            out["final_norm_b"] = P(None)
        if self.norm_type == "nonparam_layer":
            del out["final_norm"]
        if self.position_embedding == "learned":
            out["pos_embed"] = P(None, None)
        if not self.tie_embeddings:
            out["lm_head"] = (
                QuantizedLinear(q=P(None, tp), scale=P(tp))
                if q_extra
                else P(None, tp)
            )
            if self.lm_head_bias:
                out["lm_head_b"] = P(tp)
        return out

    def kv_cache_sharding(self, model_axis: str = "tp") -> P:
        """KV heads sharded over TP: [L, NB, BS, 2*KH(tp), Dh]; the layer
        axis over 'pp' stages when pipelined; the block axis over 'cp'
        under context parallelism (striped pool colors = cp ranks)."""
        lead = "pp" if self.pp_size > 1 else None
        blocks = "cp" if self.cp_size > 1 else None
        return P(lead, blocks, None, model_axis, None)


class MistralForCausalLM(LlamaForCausalLM):
    """Same graph; sliding window when configured."""

    def __init__(self, hf_config: Any, dtype=jnp.bfloat16,
                 quantization: str | None = None) -> None:
        super().__init__(hf_config, dtype, quantization)
        self.sliding_window = getattr(hf_config, "sliding_window", None)


class Qwen2ForCausalLM(LlamaForCausalLM):
    attention_bias = True


class Qwen3ForCausalLM(LlamaForCausalLM):
    """Llama graph + per-head q/k RMSNorm, decoupled head_dim.

    Reference analog: ``vllm/model_executor/models/qwen3.py``.
    """

    qk_norm = True
