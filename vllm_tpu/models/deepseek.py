"""DeepSeek-V2/V3 family: MLA attention + grouped-routing MoE.

Reference analog: ``vllm/model_executor/models/deepseek_v2.py`` (V2+V3 in
one file there too) and the MLA stack (``mla_attention.py:318``, decode
kernels ``csrc/attention/mla/``). TPU-first departures:

- MLA runs fully ABSORBED for prefill and decode over a paged latent
  cache (``ops/mla_attention.py``): no per-head K/V materialization, no
  separate prefill/decode kernels.
- Layers live in TWO homogeneous scan stacks — the dense prefix
  (``first_k_dense_replace`` layers) and the MoE rest — so ``lax.scan``
  keeps compile time flat despite the heterogeneous architecture.
- Expert compute reuses the shared fused-MoE paths (megablox grouped GEMM
  single-chip, dense one-hot GSPMD formulation for EP); only the routing
  differs (softmax group-limited for V2, sigmoid+bias ``noaux_tc`` for
  V3 — matching the HF gate semantics exactly).

Param tree::

    embed              [V, D]
    dense_layers/      every leaf stacked [K, ...]   (K = first dense)
      input_norm, <attn leaves>, post_norm, wgate/wup/wdown
    moe_layers/        every leaf stacked [M, ...]   (M = L - K)
      input_norm, <attn leaves>, post_norm,
      router [M, D, E]  (router_bias [M, E] on V3)
      we_gate/we_up/we_down  [M, E, D, Fm]
      ws_gate/ws_up/ws_down  [M, D, Fs]   (shared experts, Fs = Fm * n_sh)
    final_norm, lm_head

    <attn leaves>: wq [D, H*QK] (lite) | wq_a/q_a_norm/wq_b (q-LoRA),
      wkv_a [D, DC+DR], kv_a_norm [DC], wkv_b [DC, H*(DN+DV)], wo [H*DV, D]
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from vllm_tpu.core.kv_cache_utils import KVCacheSpec, MLAAttentionSpec
from vllm_tpu.layers.activation import silu_and_mul
from vllm_tpu.layers.layernorm import rms_norm
from vllm_tpu.layers.moe import (  # noqa: F401 (the routing's old home)
    fused_experts,
    select_experts_grouped,
)
from vllm_tpu.layers.rotary import RotaryEmbedding
from vllm_tpu.logger import init_logger
from vllm_tpu.ops.attention import AttentionMetadata
from vllm_tpu.ops.mla_attention import (
    mla_kv_cache_shape,
    mla_paged_attention,
    write_latent,
)

logger = init_logger(__name__)


def _rope_interleaved(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Complex/interleaved rope (HF deepseek ``apply_rotary_emb``): pairs
    (x[2i], x[2i+1]) rotated by angle i — NOT the rotate_half layout."""
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)


def latent_attention(
    h: jnp.ndarray,  # [T, D] normed layer input
    lp: dict,  # wq | wq_a/q_a_norm/wq_b, wkv_a, kv_a_norm, wkv_b, wo
    kv: jnp.ndarray,  # the paged latent cache
    li: jnp.ndarray,  # this layer's index in it
    md: AttentionMetadata,
    *,
    num_heads: int,
    nope_dim: int,
    rope_dim: int,
    v_dim: int,
    latent_rank: int,
    rms_eps: float,
    scale: float,
    cos_sin: tuple[jnp.ndarray, jnp.ndarray] | None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Absorbed latent attention over the paged latent cache: ``(out [T,
    D], kv)``. ``cos_sin`` None carries the rope lanes of the query and
    of the latent row WITHOUT rotation (``mla_use_nope``: position comes
    from elsewhere in the model)."""
    t = h.shape[0]
    H, DN, DR, DV, DC = num_heads, nope_dim, rope_dim, v_dim, latent_rank
    if "wq" in lp:
        q = h @ lp["wq"]
    else:
        q = rms_norm(h @ lp["wq_a"], lp["q_a_norm"], rms_eps) @ lp["wq_b"]
    # The projection ends here, as [T, heads * head_dim] rows. Left free,
    # XLA folds the head split below into the dot, lays its output out
    # token-minor, and then slices AND transposes the weight out of its
    # stack into temporaries a layer (models/llama.py has the same before
    # its head split, PERF.md PR 27; here 28 MB twice a latent layer of
    # Kimi-Linear, PR 40); behind the barrier the dot reads the stack in
    # place.
    q = jax.lax.optimization_barrier(q)
    q = q.reshape(t, H, DN + DR)
    q_nope, q_pe = q[..., :DN], q[..., DN:]
    kv_a = h @ lp["wkv_a"]  # [T, DC+DR]
    c_kv = rms_norm(kv_a[:, :DC], lp["kv_a_norm"], rms_eps)
    k_pe = kv_a[:, DC:]
    if cos_sin is not None:
        q_pe = _rope_interleaved(q_pe, *cos_sin)
        k_pe = _rope_interleaved(k_pe[:, None], *cos_sin)[:, 0]

    # Absorb W_uk: queries into latent space.
    w_uk = lp["wkv_b"].reshape(DC, H, DN + DV)[..., :DN]
    w_uv = lp["wkv_b"].reshape(DC, H, DN + DV)[..., DN:]
    q_lat = jnp.einsum("thn,chn->thc", q_nope, w_uk)
    q_abs = jnp.concatenate(
        [q_lat, q_pe.astype(q_lat.dtype)], axis=-1
    )  # [T, H, DC+DR]
    latent = jnp.concatenate(
        [c_kv, k_pe.astype(c_kv.dtype)], axis=-1
    )  # [T, DC+DR]
    kv = write_latent(kv, li, latent, md.slot_mapping)
    with jax.named_scope("mla_attn"):
        ctx = mla_paged_attention(
            q_abs, kv, li, md, scale, value_dim=DC
        )  # [T, H, DC]
    out = jnp.einsum("thc,chv->thv", ctx, w_uv)  # absorbed W_uv
    return out.reshape(t, H * DV) @ lp["wo"], kv


class DeepseekV2ForCausalLM:
    """DeepSeek-V2 / V2-Lite (softmax routing); V3 subclasses the gate."""

    supports_lora = False
    enable_lora = False
    sigmoid_routing = False  # V3: sigmoid scores + e_score_correction_bias

    def __init__(self, hf_config: Any, dtype=jnp.bfloat16,
                 quantization: str | None = None) -> None:
        if quantization:
            logger.warning(
                "weight quantization is not yet supported for MLA models; "
                "running %s unquantized", type(self).__name__,
            )
        c = hf_config
        self.hf_config = c
        self.dtype = dtype
        self.quantization = None
        self.num_layers = c.num_hidden_layers
        self.hidden_size = c.hidden_size
        self.num_heads = c.num_attention_heads
        self.vocab_size = c.vocab_size
        self.rms_eps = getattr(c, "rms_norm_eps", 1e-6)
        self.tie_embeddings = getattr(c, "tie_word_embeddings", False)
        self.max_position = getattr(c, "max_position_embeddings", 8192)

        # MLA geometry.
        self.q_lora_rank = getattr(c, "q_lora_rank", None)
        self.kv_lora_rank = c.kv_lora_rank
        self.qk_nope_head_dim = c.qk_nope_head_dim
        self.qk_rope_head_dim = c.qk_rope_head_dim
        self.v_head_dim = c.v_head_dim
        self.qk_head_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
        self.latent_dim = self.kv_lora_rank + self.qk_rope_head_dim
        # Runner cache contract: one shared latent "head".
        self.num_kv_heads = 1
        self.head_dim = self.latent_dim
        self.scale = self.qk_head_dim ** -0.5
        # DeepSeek yarn applies the mscale_all_dim correction SQUARED to
        # the softmax scale (original checkpoint semantics; vLLM
        # deepseek_v2.py does the same). With mscale == mscale_all_dim the
        # cos/sin mscale ratio is 1, so this is the only correction.
        rs = getattr(c, "rope_scaling", None)
        if rs and rs.get("rope_type", rs.get("type")) == "yarn":
            factor = rs.get("factor", 1.0)
            mad = rs.get("mscale_all_dim", 0.0)
            if factor > 1 and mad:
                m = 0.1 * mad * math.log(factor) + 1.0
                self.scale *= m * m

        # MoE geometry.
        self.num_experts = getattr(c, "n_routed_experts", None)
        self.top_k = getattr(c, "num_experts_per_tok", 0)
        self.moe_intermediate = getattr(c, "moe_intermediate_size", 0)
        self.n_shared = getattr(c, "n_shared_experts", 0) or 0
        self.n_group = getattr(c, "n_group", 1) or 1
        self.topk_group = getattr(c, "topk_group", 1) or 1
        self.topk_method = getattr(c, "topk_method", "greedy")
        self.norm_topk_prob = getattr(c, "norm_topk_prob", False)
        self.routed_scaling = getattr(c, "routed_scaling_factor", 1.0)
        self.intermediate_size = c.intermediate_size
        self.first_dense = (
            getattr(c, "first_k_dense_replace", 0)
            if self.num_experts
            else self.num_layers
        )
        self.num_moe_layers = self.num_layers - self.first_dense
        self.expert_parallel = False
        self.ep_mesh = None

        # Interleaved rope over the decoupled rope dims; yarn mscale (the
        # DeepSeek long-context recipe) is baked into the cos/sin tables
        # exactly as HF bakes attention_scaling into freqs_cis.
        self.rope = RotaryEmbedding(
            head_dim=self.qk_rope_head_dim,
            max_position=self.max_position,
            theta=getattr(c, "rope_theta", 10000.0),
            rope_scaling=getattr(c, "rope_scaling", None),
        )

    # ------------------------------------------------------------------
    # Params
    # ------------------------------------------------------------------

    def _attn_leaf_shapes(self) -> dict[str, tuple]:
        D, H = self.hidden_size, self.num_heads
        QK, DN, DV = self.qk_head_dim, self.qk_nope_head_dim, self.v_head_dim
        DC, DR = self.kv_lora_rank, self.qk_rope_head_dim
        leaves: dict[str, tuple] = {}
        if self.q_lora_rank is None:
            leaves["wq"] = (D, H * QK)
        else:
            leaves["wq_a"] = (D, self.q_lora_rank)
            leaves["q_a_norm"] = (self.q_lora_rank,)
            leaves["wq_b"] = (self.q_lora_rank, H * QK)
        leaves["wkv_a"] = (D, DC + DR)
        leaves["kv_a_norm"] = (DC,)
        leaves["wkv_b"] = (DC, H * (DN + DV))
        leaves["wo"] = (H * DV, D)
        return leaves

    def init_dummy_params(self, rng: jax.Array, dtype=None) -> dict:
        dtype = dtype or self.dtype
        D, E = self.hidden_size, self.num_experts or 0
        key = iter(jax.random.split(rng, 64))

        def init(shape, fan_in):
            return (
                jax.random.normal(next(key), shape, jnp.float32)
                / math.sqrt(fan_in)
            ).astype(dtype)

        def stack(n, shape, fan_in):
            return init((n,) + shape, fan_in)

        def attn_group(n):
            return {
                name: (
                    jnp.ones((n,) + shape, dtype)
                    if name.endswith("norm")
                    else stack(n, shape, shape[0])
                )
                for name, shape in self._attn_leaf_shapes().items()
            }

        params: dict = {
            "embed": init((self.vocab_size, D), D),
            "final_norm": jnp.ones((D,), dtype),
        }
        if not self.tie_embeddings:
            params["lm_head"] = init((D, self.vocab_size), D)
        K, M = self.first_dense, self.num_moe_layers
        if K:
            F = self.intermediate_size
            params["dense_layers"] = {
                "input_norm": jnp.ones((K, D), dtype),
                "post_norm": jnp.ones((K, D), dtype),
                **attn_group(K),
                "wgate": stack(K, (D, F), D),
                "wup": stack(K, (D, F), D),
                "wdown": stack(K, (F, D), F),
            }
        if M:
            Fm = self.moe_intermediate
            Fs = Fm * self.n_shared
            moe = {
                "input_norm": jnp.ones((M, D), dtype),
                "post_norm": jnp.ones((M, D), dtype),
                **attn_group(M),
                "router": stack(M, (D, E), D),
                "we_gate": stack(M, (E, D, Fm), D),
                "we_up": stack(M, (E, D, Fm), D),
                "we_down": stack(M, (E, Fm, D), Fm),
            }
            if self.sigmoid_routing:
                moe["router_bias"] = jnp.zeros((M, E), jnp.float32)
            if self.n_shared:
                moe["ws_gate"] = stack(M, (D, Fs), D)
                moe["ws_up"] = stack(M, (D, Fs), D)
                moe["ws_down"] = stack(M, (Fs, D), Fs)
            params["moe_layers"] = moe
        return params

    def hf_weight_map(self) -> dict:
        m = {
            "model.embed_tokens.weight": ("embed", False),
            "model.norm.weight": ("final_norm", False),
        }
        if not self.tie_embeddings:
            m["lm_head.weight"] = ("lm_head", True)
        attn = {
            "self_attn.kv_a_proj_with_mqa.weight": ("wkv_a", True),
            "self_attn.kv_a_layernorm.weight": ("kv_a_norm", False),
            "self_attn.kv_b_proj.weight": ("wkv_b", True),
            "self_attn.o_proj.weight": ("wo", True),
            "input_layernorm.weight": ("input_norm", False),
            "post_attention_layernorm.weight": ("post_norm", False),
        }
        if self.q_lora_rank is None:
            attn["self_attn.q_proj.weight"] = ("wq", True)
        else:
            attn["self_attn.q_a_proj.weight"] = ("wq_a", True)
            attn["self_attn.q_a_layernorm.weight"] = ("q_a_norm", False)
            attn["self_attn.q_b_proj.weight"] = ("wq_b", True)
        for i in range(self.num_layers):
            hf = f"model.layers.{i}"
            if i < self.first_dense:
                group, gi = "dense_layers", i
                for name, (ours, tr) in attn.items():
                    m[f"{hf}.{name}"] = (f"{group}.{ours}.{gi}", tr)
                m[f"{hf}.mlp.gate_proj.weight"] = (f"{group}.wgate.{gi}", True)
                m[f"{hf}.mlp.up_proj.weight"] = (f"{group}.wup.{gi}", True)
                m[f"{hf}.mlp.down_proj.weight"] = (f"{group}.wdown.{gi}", True)
            else:
                group, gi = "moe_layers", i - self.first_dense
                for name, (ours, tr) in attn.items():
                    m[f"{hf}.{name}"] = (f"{group}.{ours}.{gi}", tr)
                m[f"{hf}.mlp.gate.weight"] = (f"{group}.router.{gi}", True)
                if self.sigmoid_routing:
                    m[f"{hf}.mlp.gate.e_score_correction_bias"] = (
                        f"{group}.router_bias.{gi}", False)
                for j in range(self.num_experts):
                    base = f"{hf}.mlp.experts.{j}"
                    m[f"{base}.gate_proj.weight"] = (
                        f"{group}.we_gate.{gi}.{j}", True)
                    m[f"{base}.up_proj.weight"] = (
                        f"{group}.we_up.{gi}.{j}", True)
                    m[f"{base}.down_proj.weight"] = (
                        f"{group}.we_down.{gi}.{j}", True)
                if self.n_shared:
                    sh = f"{hf}.mlp.shared_experts"
                    m[f"{sh}.gate_proj.weight"] = (f"{group}.ws_gate.{gi}", True)
                    m[f"{sh}.up_proj.weight"] = (f"{group}.ws_up.{gi}", True)
                    m[f"{sh}.down_proj.weight"] = (f"{group}.ws_down.{gi}", True)
        return m

    def load_params(self, path: str, dtype=None, shardings: Any | None = None) -> dict:
        from vllm_tpu.models.loader import load_params_from

        return load_params_from(self, path, dtype or self.dtype, shardings)

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------

    def _select_experts(
        self, logits: jnp.ndarray, bias: jnp.ndarray | None
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        return select_experts_grouped(
            logits, bias, top_k=self.top_k, n_group=self.n_group,
            topk_group=self.topk_group, sigmoid=self.sigmoid_routing,
            method=self.topk_method, renormalize=self.norm_topk_prob,
            scaling=self.routed_scaling,
        )

    def apply(
        self,
        params: dict,
        kv_cache: jnp.ndarray,  # [L, NB, BS, DC+DR]
        input_ids: jnp.ndarray,  # [T]
        md: AttentionMetadata,
        token_lora_slot: jnp.ndarray | None = None,  # unused
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        x = params["embed"][input_ids].astype(self.dtype)
        H = self.num_heads
        DN, DR, DV = self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim
        DC = self.kv_lora_rank

        cos = self.rope.cos[md.positions][:, None, :]  # [T, 1, DR/2]
        sin = self.rope.sin[md.positions][:, None, :]
        live = md.live_tokens()

        def attention(lp, x, kv, li):
            h = rms_norm(x, lp["input_norm"], self.rms_eps)
            out, kv = latent_attention(
                h, lp, kv, li, md, num_heads=H, nope_dim=DN, rope_dim=DR,
                v_dim=DV, latent_rank=DC, rms_eps=self.rms_eps,
                scale=self.scale, cos_sin=(cos, sin),
            )
            return x + out, kv

        def dense_layer(carry, inputs):
            x, kv = carry
            lp, li = inputs
            x, kv = attention(lp, x, kv, li)
            h2 = rms_norm(x, lp["post_norm"], self.rms_eps)
            gate_up = jnp.concatenate([h2 @ lp["wgate"], h2 @ lp["wup"]], -1)
            x = x + silu_and_mul(gate_up) @ lp["wdown"]
            return (x, kv), None

        def moe_layer(carry, inputs):
            x, kv = carry
            lp, li = inputs
            x, kv = attention(lp, x, kv, li)
            h2 = rms_norm(x, lp["post_norm"], self.rms_eps)
            logits = h2.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
            weights, ids = self._select_experts(logits, lp.get("router_bias"))
            routed = fused_experts(
                h2, lp["we_gate"], lp["we_up"], lp["we_down"], weights, ids,
                use_grouped=None if not self.expert_parallel else False,
                ep_mesh=self.ep_mesh if self.expert_parallel else None,
                ep_axis="tp", live=live,
            )
            out = routed
            if self.n_shared:
                gate_up = jnp.concatenate(
                    [h2 @ lp["ws_gate"], h2 @ lp["ws_up"]], -1
                )
                out = out + silu_and_mul(gate_up) @ lp["ws_down"]
            return (x + out, kv), None

        carry = (x, kv_cache)
        K = self.first_dense
        if K:
            carry, _ = jax.lax.scan(
                dense_layer, carry,
                (params["dense_layers"], jnp.arange(K, dtype=jnp.int32)),
            )
        if self.num_moe_layers:
            carry, _ = jax.lax.scan(
                moe_layer, carry,
                (params["moe_layers"],
                 jnp.arange(K, self.num_layers, dtype=jnp.int32)),
            )
        x, new_kv = carry
        x = rms_norm(x, params["final_norm"], self.rms_eps)
        return x, new_kv

    def compute_logits(self, params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
        head = params["embed"].T if self.tie_embeddings else params["lm_head"]
        return (hidden @ head.astype(hidden.dtype)).astype(jnp.float32)

    # ------------------------------------------------------------------
    # Runner contracts
    # ------------------------------------------------------------------

    def kv_cache_shape(
        self, num_blocks: int, block_size: int
    ) -> tuple[int, int, int, int]:
        return mla_kv_cache_shape(
            self.num_layers, num_blocks, block_size, self.latent_dim
        )

    def get_kv_cache_spec(self, block_size: int, dtype_bytes: int) -> dict[str, KVCacheSpec]:
        spec = MLAAttentionSpec(
            block_size=block_size,
            num_kv_heads=1,
            head_size=self.latent_dim,
            dtype_bytes=dtype_bytes,
        )
        return {f"layers.{i}": spec for i in range(self.num_layers)}

    def param_shardings(self, data_axis: str | None = None, model_axis: str = "tp") -> dict:
        """TP plan: q/kv up-projections and output sharded on the head
        axis; the tiny down-projections (wq_a/wkv_a) and the shared latent
        cache replicated (MQA-style — every head reads the same latent)."""
        tp = model_axis

        def attn_group():
            g = {
                "wkv_a": P(None, None, None),
                "kv_a_norm": P(None, None),
                "wkv_b": P(None, None, tp),
                "wo": P(None, tp, None),
                "input_norm": P(None, None),
                "post_norm": P(None, None),
            }
            if self.q_lora_rank is None:
                g["wq"] = P(None, None, tp)
            else:
                g["wq_a"] = P(None, None, None)
                g["q_a_norm"] = P(None, None)
                g["wq_b"] = P(None, None, tp)
            return g

        out: dict = {
            "embed": P(tp, None),
            "final_norm": P(None),
        }
        if not self.tie_embeddings:
            out["lm_head"] = P(None, tp)
        if self.first_dense:
            out["dense_layers"] = {
                **attn_group(),
                "wgate": P(None, None, tp),
                "wup": P(None, None, tp),
                "wdown": P(None, tp, None),
            }
        if self.num_moe_layers:
            moe = {
                **attn_group(),
                "router": P(None, None, None),
            }
            if self.sigmoid_routing:
                moe["router_bias"] = P(None, None)
            if self.expert_parallel:
                moe |= {
                    "we_gate": P(None, tp, None, None),
                    "we_up": P(None, tp, None, None),
                    "we_down": P(None, tp, None, None),
                }
            else:
                moe |= {
                    "we_gate": P(None, None, None, tp),
                    "we_up": P(None, None, None, tp),
                    "we_down": P(None, None, tp, None),
                }
            if self.n_shared:
                moe |= {
                    "ws_gate": P(None, None, tp),
                    "ws_up": P(None, None, tp),
                    "ws_down": P(None, tp, None),
                }
            out["moe_layers"] = moe
        return out

    def kv_cache_sharding(self, model_axis: str = "tp") -> P:
        """Latent rows are shared by every head: replicate over TP."""
        return P(None, None, None, None)


class DeepseekV3ForCausalLM(DeepseekV2ForCausalLM):
    """V3/R1: sigmoid routing with aux-loss-free bias (``noaux_tc``).
    Reference analog: HF DeepseekV3TopkRouter semantics."""

    sigmoid_routing = True

    def __init__(self, hf_config: Any, dtype=jnp.bfloat16,
                 quantization: str | None = None) -> None:
        super().__init__(hf_config, dtype, quantization)
        self.topk_method = "noaux_tc"
