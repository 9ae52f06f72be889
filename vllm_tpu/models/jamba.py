"""Jamba (hybrid Mamba1 + attention + MoE, AI21 Jamba-class).

Reference analog: ``vllm/model_executor/models/jamba.py``. The second
hybrid family next to Bamba, stressing the hybrid path on two new axes:
the SSM mixer is MAMBA1 (per-channel selective scan with dt/B/C
RMSNorms) and the FFN alternates dense MLPs with sparse MoE blocks on a
period/offset schedule. Attention layers use NO positional encoding
(Jamba is NoPE — the SSM layers carry position).

Cache contract is Bamba's: paged KV for the attention layers + per-
request constant-size Mamba slots (``md.state_slots``), prefix caching
off.

Param tree: per-layer dicts (heterogeneous mixers/FFNs)::

    layers/{i}/
      input_norm, post_norm                       [D]
      attention: wq/wk/wv/wo
      mamba: in_proj, conv_w(+conv_b), x_proj, dt_w/dt_b, a_log, d_skip,
             out_proj, dt_norm, b_norm, c_norm
      dense FFN: wgate/wup/wdown
      MoE FFN:   router, we_gate/we_up/we_down    [E, ...]
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
from vllm_tpu.logger import init_logger
from vllm_tpu.ops.attention import (
    AttentionMetadata,
    kv_cache_shape,
    kv_dequant_scale,
    paged_attention,
    write_kv,
)
from vllm_tpu.ops.mamba import ragged_causal_conv, ragged_mamba1_scan

logger = init_logger(__name__)


class JambaForCausalLM:
    supports_lora = False
    enable_lora = False
    is_hybrid_ssm = True
    max_state_slots = 256  # set by the worker

    # Decay parameters stay f32 at load (bf16 rounding of the
    # recurrence decays compounds over long sequences).
    KEEP_F32_SUFFIXES = ("a_log", "dt_b")

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
        self.rms_eps = getattr(c, "rms_norm_eps", 1e-6)
        self.tie_embeddings = getattr(c, "tie_word_embeddings", False)

        self.num_heads = c.num_attention_heads
        self.num_kv_heads = getattr(c, "num_key_value_heads", c.num_attention_heads)
        self.head_dim = c.hidden_size // c.num_attention_heads
        self.scale = self.head_dim ** -0.5
        self.sliding_window = None

        self.attn_layer_indices = [
            i for i in range(self.num_layers)
            if i % c.attn_layer_period == c.attn_layer_offset
        ]
        self.mamba_layer_indices = [
            i for i in range(self.num_layers)
            if i not in set(self.attn_layer_indices)
        ]
        self.num_attn_layers = len(self.attn_layer_indices)
        if not self.attn_layer_indices:
            raise ValueError("Jamba config with no attention layers")
        self.expert_layer_indices = [
            i for i in range(self.num_layers)
            if c.num_experts > 1
            and i % c.expert_layer_period == c.expert_layer_offset
        ]
        self.num_experts = c.num_experts
        self.top_k = c.num_experts_per_tok

        self.state_size = c.mamba_d_state  # N
        self.conv_kernel = c.mamba_d_conv  # K
        self.m_intermediate = int(c.mamba_expand * c.hidden_size)  # I
        tr = getattr(c, "mamba_dt_rank", "auto")
        self.dt_rank = (
            math.ceil(c.hidden_size / 16) if tr == "auto" else int(tr)
        )
        self.use_conv_bias = getattr(c, "mamba_conv_bias", True)
        if getattr(c, "mamba_proj_bias", False):
            raise ValueError("Jamba with mamba_proj_bias=True is not wired")

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
            "wq": init(ks[0], (D, H * Dh), D),
            "wk": init(ks[1], (D, KH * Dh), D),
            "wv": init(ks[2], (D, KH * Dh), D),
            "wo": init(ks[3], (H * Dh, D), H * Dh),
        }

    def _mamba_dummy(self, rng, dtype) -> dict:
        D, I, N, R = (
            self.hidden_size, self.m_intermediate, self.state_size,
            self.dt_rank,
        )
        ks = jax.random.split(rng, 5)

        def init(k, shape, fan_in):
            return (
                jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)
            ).astype(dtype)

        out = {
            "in_proj": init(ks[0], (D, 2 * I), D),
            "conv_w": init(ks[1], (I, self.conv_kernel), self.conv_kernel),
            "x_proj": init(ks[2], (I, R + 2 * N), I),
            "dt_w": init(ks[3], (R, I), R),
            "dt_b": jnp.ones((I,), dtype),
            "a_log": jnp.log(
                jnp.broadcast_to(
                    jnp.arange(1, N + 1, dtype=jnp.float32), (I, N)
                )
            ).astype(jnp.float32),
            "d_skip": jnp.ones((I,), dtype),
            "dt_norm": jnp.ones((R,), dtype),
            "b_norm": jnp.ones((N,), dtype),
            "c_norm": jnp.ones((N,), dtype),
            "out_proj": init(ks[4], (I, D), I),
        }
        if self.use_conv_bias:
            out["conv_b"] = jnp.zeros((I,), dtype)
        return out

    def init_dummy_params(self, rng: jax.Array, dtype=None) -> dict:
        dtype = dtype or self.dtype
        D, F, E = self.hidden_size, self.intermediate_size, self.num_experts
        keys = jax.random.split(rng, self.num_layers + 2)

        def init(k, shape, fan_in):
            return (
                jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)
            ).astype(dtype)

        attn_set = set(self.attn_layer_indices)
        moe_set = set(self.expert_layer_indices)
        layers: dict[str, dict] = {}
        for i in range(self.num_layers):
            mixer = (
                self._attn_dummy(keys[i], dtype)
                if i in attn_set
                else self._mamba_dummy(keys[i], dtype)
            )
            ks = jax.random.split(jax.random.fold_in(keys[i], 7), 4)
            lp = {
                **mixer,
                "input_norm": jnp.ones((D,), dtype),
                "post_norm": jnp.ones((D,), dtype),
            }
            if i in moe_set:
                lp["router"] = init(ks[3], (D, E), D)
                lp["we_gate"] = init(ks[0], (E, D, F), D)
                lp["we_up"] = init(ks[1], (E, D, F), D)
                lp["we_down"] = init(ks[2], (E, F, D), F)
            else:
                lp["wgate"] = init(ks[0], (D, F), D)
                lp["wup"] = init(ks[1], (D, F), D)
                lp["wdown"] = init(ks[2], (F, D), F)
            layers[str(i)] = lp
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
            "model.final_layernorm.weight": ("final_norm", False),
        }
        if not self.tie_embeddings:
            m["lm_head.weight"] = ("lm_head", True)
        attn_set = set(self.attn_layer_indices)
        moe_set = set(self.expert_layer_indices)
        for i in range(self.num_layers):
            hf = f"model.layers.{i}"
            base = f"layers.{i}"
            m[f"{hf}.input_layernorm.weight"] = (f"{base}.input_norm", False)
            m[f"{hf}.pre_ff_layernorm.weight"] = (f"{base}.post_norm", False)
            if i in attn_set:
                for hf_n, ours in (("q_proj", "wq"), ("k_proj", "wk"),
                                   ("v_proj", "wv"), ("o_proj", "wo")):
                    m[f"{hf}.self_attn.{hf_n}.weight"] = (f"{base}.{ours}", True)
            else:
                mm = f"{hf}.mamba"
                m[f"{mm}.in_proj.weight"] = (f"{base}.in_proj", True)
                m[f"{mm}.conv1d.weight"] = (f"{base}.conv_w", False)
                m[f"{mm}.x_proj.weight"] = (f"{base}.x_proj", True)
                m[f"{mm}.dt_proj.weight"] = (f"{base}.dt_w", True)
                m[f"{mm}.dt_proj.bias"] = (f"{base}.dt_b", False)
                m[f"{mm}.A_log"] = (f"{base}.a_log", False)
                m[f"{mm}.D"] = (f"{base}.d_skip", False)
                m[f"{mm}.dt_layernorm.weight"] = (f"{base}.dt_norm", False)
                m[f"{mm}.b_layernorm.weight"] = (f"{base}.b_norm", False)
                m[f"{mm}.c_layernorm.weight"] = (f"{base}.c_norm", False)
                m[f"{mm}.out_proj.weight"] = (f"{base}.out_proj", True)
                if self.use_conv_bias:
                    m[f"{mm}.conv1d.bias"] = (f"{base}.conv_b", False)
            if i in moe_set:
                m[f"{hf}.feed_forward.router.weight"] = (f"{base}.router", True)
                for j in range(self.num_experts):
                    e = f"{hf}.feed_forward.experts.{j}"
                    m[f"{e}.gate_proj.weight"] = (f"{base}.we_gate.{j}", True)
                    m[f"{e}.up_proj.weight"] = (f"{base}.we_up.{j}", True)
                    m[f"{e}.down_proj.weight"] = (f"{base}.we_down.{j}", True)
            else:
                m[f"{hf}.feed_forward.gate_proj.weight"] = (f"{base}.wgate", True)
                m[f"{hf}.feed_forward.up_proj.weight"] = (f"{base}.wup", True)
                m[f"{hf}.feed_forward.down_proj.weight"] = (f"{base}.wdown", True)
        return m

    def postprocess_weight(self, leaf_path: str, arr):
        import numpy as np

        if leaf_path.endswith(".conv_w"):
            return arr.squeeze(1)  # [I, 1, K] -> [I, K]
        if leaf_path.endswith(".a_log"):
            return arr.astype(np.float32)
        return arr

    def load_params(self, path: str, dtype=None, shardings=None) -> dict:
        from vllm_tpu.models.loader import load_params_from

        return load_params_from(
            self, path, dtype or self.dtype, shardings
        )

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------

    def apply(
        self,
        params: dict,
        kv_cache: dict,  # {"paged", "conv", "ssm"}
        input_ids: jnp.ndarray,  # [T]
        md: AttentionMetadata,
        token_lora_slot: jnp.ndarray | None = None,  # unused
    ) -> tuple[jnp.ndarray, dict]:
        x = params["embed"][input_ids].astype(self.dtype)
        t = x.shape[0]
        H, KH, Dh = self.num_heads, self.num_kv_heads, self.head_dim
        I, N, R = self.m_intermediate, self.state_size, self.dt_rank
        paged, conv_c, ssm_c = (
            kv_cache["paged"], kv_cache["conv"], kv_cache["ssm"]
        )
        assert md.state_slots is not None, "hybrid model needs state slots"
        slots = md.state_slots  # [R]
        first_pos = md.positions[jnp.clip(md.query_start_loc[:-1], 0, t - 1)]
        fresh = first_pos == 0
        live = md.live_tokens()
        kv_scale = kv_dequant_scale(paged)

        def attn_layer(x, lp, attn_li):
            nonlocal paged
            h = rms_norm(x, lp["input_norm"], self.rms_eps)
            # NoPE: no rotary/learned positions on attention layers.
            q = (h @ lp["wq"]).reshape(t, H, Dh)
            k = (h @ lp["wk"]).reshape(t, KH, Dh)
            v = (h @ lp["wv"]).reshape(t, KH, Dh)
            li = jnp.int32(attn_li)
            paged = write_kv(paged, li, k, v, md.slot_mapping)
            attn = paged_attention(
                q, paged, li, md, self.scale,
                k_scale=kv_scale, v_scale=kv_scale,
            )
            return x + attn.reshape(t, H * Dh) @ lp["wo"]

        def mamba_layer(x, lp, m_li):
            nonlocal conv_c, ssm_c
            h = rms_norm(x, lp["input_norm"], self.rms_eps)
            proj = h @ lp["in_proj"]
            xs = proj[:, :I]
            gate = proj[:, I:]

            conv_seed = jnp.where(
                fresh[:, None, None], 0.0, conv_c[m_li, slots]
            )
            x_conv, new_conv = ragged_causal_conv(
                xs, conv_seed, lp["conv_w"], lp.get("conv_b"),
                md.token_req_idx, md.query_start_loc,
            )
            x_conv = jax.nn.silu(x_conv.astype(jnp.float32))

            ssm_in = x_conv.astype(self.dtype) @ lp["x_proj"]
            dt_low = rms_norm(ssm_in[:, :R], lp["dt_norm"], self.rms_eps)
            b = rms_norm(
                ssm_in[:, R : R + N], lp["b_norm"], self.rms_eps
            ).astype(jnp.float32)
            c = rms_norm(
                ssm_in[:, R + N :], lp["c_norm"], self.rms_eps
            ).astype(jnp.float32)
            dt = jax.nn.softplus(
                (dt_low @ lp["dt_w"]).astype(jnp.float32)
                + lp["dt_b"].astype(jnp.float32)
            )

            ssm_seed = jnp.where(
                fresh[:, None, None], 0.0, ssm_c[m_li, slots]
            )
            y, new_ssm = ragged_mamba1_scan(
                x_conv, dt, lp["a_log"], b, c, ssm_seed,
                md.token_req_idx, md.query_start_loc,
            )
            y = y + lp["d_skip"].astype(jnp.float32)[None, :] * x_conv
            y = y * jax.nn.silu(gate.astype(jnp.float32))
            conv_c = conv_c.at[m_li, slots].set(new_conv)
            ssm_c = ssm_c.at[m_li, slots].set(new_ssm)
            return x + y.astype(self.dtype) @ lp["out_proj"]

        attn_set = set(self.attn_layer_indices)
        moe_set = set(self.expert_layer_indices)
        attn_li = m_li = 0
        for i in range(self.num_layers):
            lp = params["layers"][str(i)]
            if i in attn_set:
                x = attn_layer(x, lp, attn_li)
                attn_li += 1
            else:
                x = mamba_layer(x, lp, m_li)
                m_li += 1
            h2 = rms_norm(x, lp["post_norm"], self.rms_eps)
            if i in moe_set:
                logits = (
                    h2.astype(jnp.float32)
                    @ lp["router"].astype(jnp.float32)
                )
                # HF Jamba uses the softmax weights directly (NO top-k
                # renormalization, unlike Mixtral).
                weights, ids = select_experts(logits, self.top_k, False)
                ffn = fused_experts(
                    h2, lp["we_gate"], lp["we_up"], lp["we_down"],
                    weights, ids, live=live,
                )
            else:
                gate_up = jnp.concatenate(
                    [h2 @ lp["wgate"], h2 @ lp["wup"]], -1
                )
                ffn = silu_and_mul(gate_up) @ lp["wdown"]
            x = x + ffn
        x = rms_norm(x, params["final_norm"], self.rms_eps)
        return x, {"paged": paged, "conv": conv_c, "ssm": ssm_c}

    def compute_logits(self, params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
        head = params["embed"].T if self.tie_embeddings else params["lm_head"]
        return (hidden @ head.astype(hidden.dtype)).astype(jnp.float32)

    # ------------------------------------------------------------------
    # Runner contracts (Bamba's hybrid cache shape with Mamba1 state)
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
            self.m_intermediate * (self.conv_kernel - 1)
            + self.m_intermediate * self.state_size
        )
        return len(self.mamba_layer_indices) * (max_slots + 1) * per_slot

    def alloc_kv_cache(self, num_blocks: int, block_size: int, dtype) -> dict:
        lm = len(self.mamba_layer_indices)
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
                (lm, s, self.m_intermediate, self.conv_kernel - 1),
                jnp.float32,
            ),
            "ssm": jnp.zeros(
                (lm, s, self.m_intermediate, self.state_size), jnp.float32
            ),
        }

    def param_shardings(self, data_axis: str | None = None,
                        model_axis: str = "tp") -> dict:
        tp = model_axis
        attn_set = set(self.attn_layer_indices)
        moe_set = set(self.expert_layer_indices)
        layers: dict[str, dict] = {}
        for i in range(self.num_layers):
            lp: dict[str, Any] = {
                "input_norm": P(None),
                "post_norm": P(None),
            }
            if i in attn_set:
                lp |= {
                    "wq": P(None, tp), "wk": P(None, tp),
                    "wv": P(None, tp), "wo": P(tp, None),
                }
            else:
                # Mamba mixer replicated (segment-interleaved in_proj).
                lp |= {
                    k: P(*([None] * nd)) for k, nd in (
                        ("in_proj", 2), ("conv_w", 2), ("x_proj", 2),
                        ("dt_w", 2), ("a_log", 2), ("out_proj", 2),
                        ("dt_b", 1), ("d_skip", 1), ("dt_norm", 1),
                        ("b_norm", 1), ("c_norm", 1),
                    )
                }
                if self.use_conv_bias:
                    lp["conv_b"] = P(None)
            if i in moe_set:
                lp |= {
                    "router": P(None, None),
                    "we_gate": P(None, None, tp),
                    "we_up": P(None, None, tp),
                    "we_down": P(None, tp, None),
                }
            else:
                lp |= {
                    "wgate": P(None, tp), "wup": P(None, tp),
                    "wdown": P(tp, None),
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
            "ssm": P(None, None, None, None),
        }
