"""Qwen3-Next: hybrid gated-delta-net (GDN) + gated attention + MoE.

Reference analog: ``vllm/model_executor/models/qwen3_next.py`` +
``vllm/v1/attention/backends/gdn_attn.py``. The third hybrid family,
adding the linear-attention state class the VERDICT named: most layers
are GDN mixers (matrix-valued per-request state updated by a gated
delta rule, ``ops/gdn.py``), every fourth layer is full attention with
an output GATE (o_proj(attn * sigmoid(gate))), per-head q/k RMSNorm and
partial rotary; the FFN is MoE everywhere with a sigmoid-gated shared
expert (Qwen2-MoE style).

Cache contract is the hybrid one (Bamba/Jamba): paged KV for attention
layers + per-request constant-size slots (``md.state_slots``) holding
the GDN conv tails and recurrent matrices.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from vllm_tpu.core.kv_cache_utils import FullAttentionSpec, KVCacheSpec
from vllm_tpu.layers.activation import silu_and_mul
from vllm_tpu.layers.layernorm import rms_norm
from vllm_tpu.layers.moe import fused_experts, select_experts
from vllm_tpu.layers.rotary import RotaryEmbedding, _apply_rotate_half
from vllm_tpu.logger import init_logger
from vllm_tpu.ops.attention import (
    AttentionMetadata,
    kv_cache_shape,
    kv_dequant_scale,
    paged_attention,
    write_kv,
)
from vllm_tpu.ops.gdn import ragged_gated_delta_rule
from vllm_tpu.ops.mamba import ragged_causal_conv

logger = init_logger(__name__)


class Qwen3NextForCausalLM:
    supports_lora = False
    enable_lora = False
    is_hybrid_ssm = True  # per-request state slots (GDN conv + matrix)
    max_state_slots = 256  # set by the worker

    # Decay parameters stay f32 at load (bf16 rounding of the
    # recurrence decays compounds over long sequences).
    KEEP_F32_SUFFIXES = ("a_log", "dt_bias")

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
        self.rms_eps = getattr(c, "rms_norm_eps", 1e-6)
        self.tie_embeddings = getattr(c, "tie_word_embeddings", False)

        # Full-attention geometry.
        self.num_heads = c.num_attention_heads
        self.num_kv_heads = c.num_key_value_heads
        self.head_dim = getattr(c, "head_dim", None) or (
            c.hidden_size // c.num_attention_heads
        )
        self.scale = self.head_dim ** -0.5
        self.sliding_window = None
        prf = getattr(c, "partial_rotary_factor", 0.25) or 1.0
        self.rope = RotaryEmbedding(
            head_dim=self.head_dim,
            max_position=getattr(c, "max_position_embeddings", 8192),
            theta=getattr(c, "rope_theta", 10000.0),
            rotary_dim=(
                int(self.head_dim * prf) if prf < 1.0 else None
            ),
        )

        # Layer schedule.
        lt = list(getattr(c, "layer_types"))
        self.attn_layer_indices = [
            i for i, k in enumerate(lt) if k == "full_attention"
        ]
        self.gdn_layer_indices = [
            i for i, k in enumerate(lt) if k == "linear_attention"
        ]
        self.num_attn_layers = len(self.attn_layer_indices)
        if not self.attn_layer_indices:
            raise ValueError("Qwen3-Next config with no attention layers")

        # GDN geometry.
        self.nv = c.linear_num_value_heads
        self.nk = c.linear_num_key_heads
        self.dk = c.linear_key_head_dim
        self.dv = c.linear_value_head_dim
        self.key_dim = self.nk * self.dk
        self.value_dim = self.nv * self.dv
        self.conv_dim = 2 * self.key_dim + self.value_dim
        self.conv_kernel = c.linear_conv_kernel_dim
        self.vr = self.nv // self.nk  # v-heads per k-head

        # MoE.
        self.num_experts = c.num_experts
        self.top_k = c.num_experts_per_tok
        self.norm_topk = getattr(c, "norm_topk_prob", True)
        self.moe_intermediate = c.moe_intermediate_size
        self.shared_intermediate = c.shared_expert_intermediate_size

    # ------------------------------------------------------------------
    # Params
    # ------------------------------------------------------------------

    def _attn_dummy(self, rng, dtype) -> dict:
        D, H, KH, Dh = (
            self.hidden_size, self.num_heads, self.num_kv_heads,
            self.head_dim,
        )
        ks = jax.random.split(rng, 4)

        def init(k, shape, fan_in):
            return (
                jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)
            ).astype(dtype)

        return {
            # Fused query+gate, like the checkpoint layout.
            "wq": init(ks[0], (D, 2 * H * Dh), D),
            "wk": init(ks[1], (D, KH * Dh), D),
            "wv": init(ks[2], (D, KH * Dh), D),
            "wo": init(ks[3], (H * Dh, D), H * Dh),
            "q_norm": jnp.ones((Dh,), dtype),
            "k_norm": jnp.ones((Dh,), dtype),
        }

    def _gdn_dummy(self, rng, dtype) -> dict:
        D = self.hidden_size
        qkvz = 2 * self.key_dim + 2 * self.value_dim
        ks = jax.random.split(rng, 4)

        def init(k, shape, fan_in):
            return (
                jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)
            ).astype(dtype)

        return {
            "in_qkvz": init(ks[0], (D, qkvz), D),
            "in_ba": init(ks[1], (D, 2 * self.nv), D),
            "conv_w": init(
                ks[2], (self.conv_dim, self.conv_kernel), self.conv_kernel
            ),
            "a_log": jnp.log(
                jnp.arange(1, self.nv + 1, dtype=jnp.float32)
            ),
            "dt_bias": jnp.ones((self.nv,), jnp.float32),
            "gated_norm": jnp.ones((self.dv,), dtype),
            "out_proj": init(ks[3], (self.value_dim, D), self.value_dim),
        }

    def init_dummy_params(self, rng: jax.Array, dtype=None) -> dict:
        dtype = dtype or self.dtype
        D, E, F = self.hidden_size, self.num_experts, self.moe_intermediate
        Fs = self.shared_intermediate
        keys = jax.random.split(rng, self.num_layers + 2)

        def init(k, shape, fan_in):
            return (
                jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)
            ).astype(dtype)

        attn_set = set(self.attn_layer_indices)
        layers: dict[str, dict] = {}
        for i in range(self.num_layers):
            mixer = (
                self._attn_dummy(keys[i], dtype)
                if i in attn_set
                else self._gdn_dummy(keys[i], dtype)
            )
            ks = jax.random.split(jax.random.fold_in(keys[i], 7), 8)
            layers[str(i)] = {
                **mixer,
                "input_norm": jnp.ones((D,), dtype),
                "post_norm": jnp.ones((D,), dtype),
                "router": init(ks[0], (D, E), D),
                "we_gate": init(ks[1], (E, D, F), D),
                "we_up": init(ks[2], (E, D, F), D),
                "we_down": init(ks[3], (E, F, D), F),
                "ws_gate": init(ks[4], (D, Fs), D),
                "ws_up": init(ks[5], (D, Fs), D),
                "ws_down": init(ks[6], (Fs, D), Fs),
                "wsg": init(ks[7], (D, 1), D),
            }
        params = {
            "embed": init(keys[-1], (self.vocab_size, D), D),
            "layers": layers,
            "final_norm": jnp.ones((D,), dtype),
        }
        if not self.tie_embeddings:
            params["lm_head"] = init(keys[-2], (D, self.vocab_size), D)
        return params

    def hf_weight_map(self) -> dict:
        m = {
            "model.embed_tokens.weight": ("embed", False),
            "model.norm.weight": ("final_norm", False),
        }
        if not self.tie_embeddings:
            m["lm_head.weight"] = ("lm_head", True)
        attn_set = set(self.attn_layer_indices)
        for i in range(self.num_layers):
            hf = f"model.layers.{i}"
            base = f"layers.{i}"
            m[f"{hf}.input_layernorm.weight"] = (f"{base}.input_norm", False)
            m[f"{hf}.post_attention_layernorm.weight"] = (
                f"{base}.post_norm", False)
            if i in attn_set:
                m[f"{hf}.self_attn.q_proj.weight"] = (f"{base}.wq", True)
                m[f"{hf}.self_attn.k_proj.weight"] = (f"{base}.wk", True)
                m[f"{hf}.self_attn.v_proj.weight"] = (f"{base}.wv", True)
                m[f"{hf}.self_attn.o_proj.weight"] = (f"{base}.wo", True)
                m[f"{hf}.self_attn.q_norm.weight"] = (f"{base}.q_norm", False)
                m[f"{hf}.self_attn.k_norm.weight"] = (f"{base}.k_norm", False)
            else:
                la = f"{hf}.linear_attn"
                m[f"{la}.in_proj_qkvz.weight"] = (f"{base}.in_qkvz", True)
                m[f"{la}.in_proj_ba.weight"] = (f"{base}.in_ba", True)
                m[f"{la}.conv1d.weight"] = (f"{base}.conv_w", False)
                m[f"{la}.A_log"] = (f"{base}.a_log", False)
                m[f"{la}.dt_bias"] = (f"{base}.dt_bias", False)
                m[f"{la}.norm.weight"] = (f"{base}.gated_norm", False)
                m[f"{la}.out_proj.weight"] = (f"{base}.out_proj", True)
            m[f"{hf}.mlp.gate.weight"] = (f"{base}.router", True)
            for j in range(self.num_experts):
                e = f"{hf}.mlp.experts.{j}"
                m[f"{e}.gate_proj.weight"] = (f"{base}.we_gate.{j}", True)
                m[f"{e}.up_proj.weight"] = (f"{base}.we_up.{j}", True)
                m[f"{e}.down_proj.weight"] = (f"{base}.we_down.{j}", True)
            se = f"{hf}.mlp.shared_expert"
            m[f"{se}.gate_proj.weight"] = (f"{base}.ws_gate", True)
            m[f"{se}.up_proj.weight"] = (f"{base}.ws_up", True)
            m[f"{se}.down_proj.weight"] = (f"{base}.ws_down", True)
            m[f"{hf}.mlp.shared_expert_gate.weight"] = (f"{base}.wsg", True)
        return m

    def postprocess_weight(self, leaf_path: str, arr):
        import numpy as np

        if leaf_path.endswith(".conv_w"):
            return arr.squeeze(1)  # [C, 1, K] -> [C, K]
        if leaf_path.endswith((".a_log", ".dt_bias")):
            return arr.astype(np.float32)
        if leaf_path == "final_norm" or leaf_path.endswith(
            (".input_norm", ".post_norm", ".q_norm", ".k_norm")
        ):
            # Qwen3NextRMSNorm is ZERO-CENTERED: checkpoints store w with
            # the output computed as norm(x) * (1 + w). The gated norm
            # (gated_norm) is the standard w * norm(x) — no offset.
            return arr + 1.0
        return arr

    def load_params(self, path: str, dtype=None, shardings=None) -> dict:
        from vllm_tpu.models.loader import load_params_from

        return load_params_from(
            self, path, dtype or self.dtype, shardings
        )

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------

    def _split_qkvz(self, qkvz: jnp.ndarray, t: int):
        """HF fix_query_key_value_ordering: per-K-HEAD interleaved
        [q(dk) | k(dk) | v(r*dv) | z(r*dv)] blocks."""
        nk, dk, dv, r = self.nk, self.dk, self.dv, self.vr
        grp = qkvz.reshape(t, nk, 2 * dk + 2 * r * dv)
        q = grp[:, :, :dk]
        k = grp[:, :, dk : 2 * dk]
        v = grp[:, :, 2 * dk : 2 * dk + r * dv].reshape(t, self.nv, dv)
        z = grp[:, :, 2 * dk + r * dv :].reshape(t, self.nv, dv)
        return q, k, v, z

    def apply(
        self,
        params: dict,
        kv_cache: dict,  # {"paged", "conv", "gdn"}
        input_ids: jnp.ndarray,  # [T]
        md: AttentionMetadata,
        token_lora_slot: jnp.ndarray | None = None,  # unused
    ) -> tuple[jnp.ndarray, dict]:
        x = params["embed"][input_ids].astype(self.dtype)
        t = x.shape[0]
        H, KH, Dh = self.num_heads, self.num_kv_heads, self.head_dim
        paged, conv_c, gdn_c = (
            kv_cache["paged"], kv_cache["conv"], kv_cache["gdn"]
        )
        assert md.state_slots is not None, "hybrid model needs state slots"
        slots = md.state_slots
        first_pos = md.positions[jnp.clip(md.query_start_loc[:-1], 0, t - 1)]
        fresh = first_pos == 0
        live = md.live_tokens()
        kv_scale = kv_dequant_scale(paged)
        rope_cos, rope_sin = self.rope.cos, self.rope.sin

        def attn_layer(x, lp, attn_li):
            nonlocal paged
            h = rms_norm(x, lp["input_norm"], self.rms_eps)
            qg = (h @ lp["wq"]).reshape(t, H, 2 * Dh)
            q, gate = qg[..., :Dh], qg[..., Dh:]
            k = (h @ lp["wk"]).reshape(t, KH, Dh)
            v = (h @ lp["wv"]).reshape(t, KH, Dh)
            q = rms_norm(q, lp["q_norm"], self.rms_eps)
            k = rms_norm(k, lp["k_norm"], self.rms_eps)
            cos = rope_cos[md.positions][:, None, :]
            sin = rope_sin[md.positions][:, None, :]
            q = _apply_rotate_half(q, cos, sin, self.rope.rotary_dim)
            k = _apply_rotate_half(k, cos, sin, self.rope.rotary_dim)
            li = jnp.int32(attn_li)
            paged = write_kv(paged, li, k, v, md.slot_mapping)
            attn = paged_attention(
                q, paged, li, md, self.scale,
                k_scale=kv_scale, v_scale=kv_scale,
            ).reshape(t, H * Dh)
            attn = attn * jax.nn.sigmoid(
                gate.reshape(t, H * Dh).astype(jnp.float32)
            ).astype(self.dtype)
            return x + attn @ lp["wo"]

        def gdn_layer(x, lp, g_li):
            nonlocal conv_c, gdn_c
            h = rms_norm(x, lp["input_norm"], self.rms_eps)
            q, k, v, z = self._split_qkvz(h @ lp["in_qkvz"], t)
            ba = (h @ lp["in_ba"]).reshape(t, self.nk, 2 * self.vr)
            b = ba[:, :, : self.vr].reshape(t, self.nv)
            a = ba[:, :, self.vr :].reshape(t, self.nv)

            qkv_flat = jnp.concatenate(
                [q.reshape(t, -1), k.reshape(t, -1), v.reshape(t, -1)],
                axis=-1,
            )  # [T, conv_dim]
            conv_seed = jnp.where(
                fresh[:, None, None], 0.0, conv_c[g_li, slots]
            )
            qkv_conv, new_conv = ragged_causal_conv(
                qkv_flat, conv_seed, lp["conv_w"], None,
                md.token_req_idx, md.query_start_loc,
            )
            qkv_conv = jax.nn.silu(qkv_conv.astype(jnp.float32))
            kd = self.key_dim
            qc = qkv_conv[:, :kd].reshape(t, self.nk, self.dk)
            kc = qkv_conv[:, kd : 2 * kd].reshape(t, self.nk, self.dk)
            vc = qkv_conv[:, 2 * kd :].reshape(t, self.nv, self.dv)

            beta = jax.nn.sigmoid(b.astype(jnp.float32))
            g = -jnp.exp(lp["a_log"].astype(jnp.float32)) * jax.nn.softplus(
                a.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32)
            )  # [T, nv] log-decay
            if self.vr > 1:
                qc = jnp.repeat(qc, self.vr, axis=1)
                kc = jnp.repeat(kc, self.vr, axis=1)

            gdn_seed = jnp.where(
                fresh[:, None, None, None], 0.0, gdn_c[g_li, slots]
            )
            y, new_state = ragged_gated_delta_rule(
                qc, kc, vc, g, beta, gdn_seed,
                md.token_req_idx, md.query_start_loc,
            )
            # Gated RMSNorm per v-head (norm before gate), then flatten.
            yf = y.astype(jnp.float32)
            yf = rms_norm(yf, lp["gated_norm"], self.rms_eps)
            yf = yf * jax.nn.silu(z.astype(jnp.float32))
            out = yf.reshape(t, self.value_dim).astype(self.dtype)
            conv_c = conv_c.at[g_li, slots].set(new_conv)
            gdn_c = gdn_c.at[g_li, slots].set(new_state)
            return x + out @ lp["out_proj"]

        attn_set = set(self.attn_layer_indices)
        attn_li = g_li = 0
        for i in range(self.num_layers):
            lp = params["layers"][str(i)]
            if i in attn_set:
                x = attn_layer(x, lp, attn_li)
                attn_li += 1
            else:
                x = gdn_layer(x, lp, g_li)
                g_li += 1
            h2 = rms_norm(x, lp["post_norm"], self.rms_eps)
            logits = (
                h2.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
            )
            weights, ids = select_experts(logits, self.top_k, self.norm_topk)
            moe = fused_experts(
                h2, lp["we_gate"], lp["we_up"], lp["we_down"], weights, ids,
                live=live,
            )
            shared = silu_and_mul(jnp.concatenate(
                [h2 @ lp["ws_gate"], h2 @ lp["ws_up"]], -1
            )) @ lp["ws_down"]
            sg = jax.nn.sigmoid((h2 @ lp["wsg"]).astype(jnp.float32))
            x = x + moe + shared * sg.astype(self.dtype)
        x = rms_norm(x, params["final_norm"], self.rms_eps)
        return x, {"paged": paged, "conv": conv_c, "gdn": gdn_c}

    def compute_logits(self, params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
        head = params["embed"].T if self.tie_embeddings else params["lm_head"]
        return (hidden @ head.astype(hidden.dtype)).astype(jnp.float32)

    # ------------------------------------------------------------------
    # Runner contracts
    # ------------------------------------------------------------------

    def get_kv_cache_spec(self, block_size: int, dtype_bytes: int) -> dict[str, KVCacheSpec]:
        spec = FullAttentionSpec(
            block_size=block_size,
            num_kv_heads=self.num_kv_heads,
            head_size=self.head_dim,
            dtype_bytes=dtype_bytes,
        )
        return {f"layers.{i}": spec for i in self.attn_layer_indices}

    def fixed_state_bytes(self, max_slots: int) -> int:
        per_slot = 4 * (
            self.conv_dim * (self.conv_kernel - 1)
            + self.nv * self.dk * self.dv
        )
        return len(self.gdn_layer_indices) * (max_slots + 1) * per_slot

    def alloc_kv_cache(self, num_blocks: int, block_size: int, dtype) -> dict:
        lg = len(self.gdn_layer_indices)
        s = self.max_state_slots + 1  # last slot = padding scratch
        return {
            "paged": jnp.zeros(
                kv_cache_shape(
                    self.num_attn_layers, num_blocks, block_size,
                    self.num_kv_heads, self.head_dim,
                ),
                dtype,
            ),
            "conv": jnp.zeros(
                (lg, s, self.conv_dim, self.conv_kernel - 1), jnp.float32
            ),
            "gdn": jnp.zeros(
                (lg, s, self.nv, self.dk, self.dv), jnp.float32
            ),
        }

    def param_shardings(self, data_axis: str | None = None,
                        model_axis: str = "tp") -> dict:
        tp = model_axis
        attn_set = set(self.attn_layer_indices)
        layers: dict[str, dict] = {}
        for i in range(self.num_layers):
            lp: dict[str, Any] = {
                "input_norm": P(None),
                "post_norm": P(None),
                "router": P(None, None),
                "we_gate": P(None, None, tp),
                "we_up": P(None, None, tp),
                "we_down": P(None, tp, None),
                "ws_gate": P(None, tp),
                "ws_up": P(None, tp),
                "ws_down": P(tp, None),
                "wsg": P(None, None),
            }
            if i in attn_set:
                lp |= {
                    "wq": P(None, tp), "wk": P(None, tp),
                    "wv": P(None, tp), "wo": P(tp, None),
                    "q_norm": P(None), "k_norm": P(None),
                }
            else:
                lp |= {
                    k: P(*([None] * nd)) for k, nd in (
                        ("in_qkvz", 2), ("in_ba", 2), ("conv_w", 2),
                        ("out_proj", 2), ("a_log", 1), ("dt_bias", 1),
                        ("gated_norm", 1),
                    )
                }
            layers[str(i)] = lp
        out = {
            "embed": P(None, None),
            "layers": layers,
            "final_norm": P(None),
        }
        if not self.tie_embeddings:
            out["lm_head"] = P(None, tp)
        return out

    def kv_cache_sharding(self, model_axis: str = "tp") -> dict:
        return {
            "paged": P(None, None, None, model_axis, None),
            "conv": P(None, None, None, None),
            "gdn": P(None, None, None, None, None),
        }
