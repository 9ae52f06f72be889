"""Kimi-Linear: delta-rule linear attention with a decay per key channel
(KDA) beside latent attention without rotation, sparse experts with a
shared one.

Reference analog: ``vllm/model_executor/models/kimi_linear.py`` +
``vllm/model_executor/layers/kda.py``. Three of four layers are KDA
mixers (``ops/kda.py``: a float32 matrix a head a request, a short
causal convolution on q, k and v whose tail is per-request state too);
the others are the DeepSeek latent attention (``models/deepseek.py::
latent_attention``) with ``mla_use_nope``: the rope lanes of the latent
row are carried unrotated, position comes from the KDA layers. The first
``first_k_dense_replace`` layers have a dense SwiGLU, the rest routed
experts (sigmoid scores, a selection bias, renormalised, scaled) beside
one shared expert.

Cache contract: the hybrid one of ``models/qwen3_next.py``. Pages of
latent rows for the attention layers, and per-request slots
(``md.state_slots``) for the KDA layers' state: ``kda [Lk, S + 1, H, dk,
dv]`` float32 and ``conv [Lk, S + 1, K - 1, 3 * H, dk]`` in the served
dtype (a slot's K - 1 last inputs, a token the heads of q, k and v: whole
tiles, which ``ops/kda.py::kda_short_conv`` reads and writes in place by
one DMA a live row), the last slot the padding rows' scratch.

Expert share. ``config.json`` may carry ``ep_size`` and ``ep_rank``:
``num_experts`` is then the count HELD here, the router keeps ``ep_size *
num_experts`` outputs, and the layer computes its own experts' part of the
result for the pairs routed to them (``layers/moe.py``, ``first_expert``).

Layers run as scans wherever the kinds repeat: the leading layers one by
one, then ``lax.scan`` over whole periods of the pattern (3 KDA : 1
latent), then what is left. The scan steps over the period's index alone
and each layer slices its own leaves ``leaf[period, place]`` out of the
whole ``[P, n, ...]`` stacks, so that every slice has one consumer and the
matmul that uses a weight reads it where it lies. Handed to the scan as
``xs``, the stacks reached an iteration as ``[n, ...]`` slabs that n layers
indexed, and XLA wrote each slab out of its stack twice an iteration: 4.2
ms of a 23.1 ms step on the v5e, which then took 16.3 (PERF.md, PR 40).
Param tree::

    embed [V, D], final_norm [D], lm_head [D, V]
    single/<layer>/   the layers outside the periods, leaves unstacked
    periods/          every leaf [P, n, ...]: P periods, n of that kind in one
      norms/ input_norm, post_norm   [P, p, D]
      kda/   wq wk wv [D, H*dk]  conv_q conv_k conv_v [K, H*dk]
             f_a [D, dk]  f_b [dk, H*dk]
             dt_bias [H*dk] f32  a_log [H] f32  b_proj [D, H]
             g_a [D, dk]  g_b [dk, H*dv]  g_bias [H*dv]  o_norm [dv]  wo [H*dv, D]
      mla/   wq [D, H*(DN+DR)]  wkv_a [D, DC+DR]  kv_a_norm [DC]
             wkv_b [DC, H*(DN+DV)]  wo [H*DV, D]
      dense/ wgate wup [D, F]  wdown [F, D]
      moe/   router [D, E_all]  router_bias [E_all] f32  ws_gate ws_up [D, Fs]  ws_down
    experts/ we_gate we_up [P*m, E, D, Fm]  we_down [P*m, E, Fm, D]
             (m expert layers a period; kept whole beside the scan)
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from vllm_tpu.core.kv_cache_utils import KVCacheSpec, MLAAttentionSpec
from vllm_tpu.layers.activation import silu_and_mul
from vllm_tpu.layers.layernorm import rms_norm
from vllm_tpu.layers.moe import share_of_sparse_layer
from vllm_tpu.logger import init_logger
from vllm_tpu.models.deepseek import latent_attention
from vllm_tpu.ops.attention import AttentionMetadata
from vllm_tpu.ops.kda import kda_attention, kda_short_conv, l2norm
from vllm_tpu.ops.mla_attention import mla_kv_cache_shape

logger = init_logger(__name__)

# A leaf's place in the dummy draw's key: fold_in(fold_in(rng, id), layer).
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


def layer_plan(kinds: list[tuple[str, str]]) -> tuple[list[int], int, int, list[int]]:
    """Cut the layers' (mixer, ffn) kinds into leading single layers, ``reps``
    periods of ``period`` layers, and trailing single layers, so that the
    periods cover as many layers as a pattern of at most 8 can (the
    earliest start among equals: two leading layers alike do not keep a
    longer pattern behind them out of the scan)."""
    n = len(kinds)
    best = (0, 0, 0, 0)  # covered, start, period, reps
    for start in range(n):
        for period in range(1, 9):
            reps = 1
            while (
                start + (reps + 1) * period <= n
                and kinds[start + reps * period: start + (reps + 1) * period]
                == kinds[start: start + period]
            ):
                reps += 1
            if reps > 1 and reps * period > best[0]:
                best = (reps * period, start, period, reps)
    covered, start, period, reps = best
    if not covered:
        return list(range(n)), 0, 0, []
    return (list(range(start)), period, reps,
            list(range(start + covered, n)))


# Widths of the dummy draw that are not 1 / sqrt(fan_in) (times that).
# With unit-variance scores a softmax over a thousand keys is nearly flat
# and a latent layer adds next to nothing to the residual: the query is
# drawn wider so that a few keys carry a row, as in a trained model. With
# unit-width experts, one expert chosen differently in bf16 (the 8th and
# 9th of 256 scores lie 0.06 deviations apart) moves the residual by a
# tenth and the later layers' choices with it; the routed experts' way
# out is drawn narrower so that a comparison reads more than that cascade.
DRAW_WIDTHS = {"mla_wq": 4.0, "we_down": 0.25}


class KimiLinearForCausalLM:
    supports_lora = False
    enable_lora = False
    is_hybrid_ssm = True  # per-request state slots (KDA matrix + conv tail)
    max_state_slots = 256  # set by the worker
    returns_expert_counts = True  # apply() -> (hidden, cache, counts)

    # Decay parameters and the selection bias stay f32 at load.
    KEEP_F32_SUFFIXES = ("a_log", "dt_bias", "router_bias")

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
        self.rms_eps = getattr(c, "rms_norm_eps", 1e-5)
        self.tie_embeddings = getattr(c, "tie_word_embeddings", False)

        # Which layer is which (the config counts layers from 1).
        la = dict(getattr(c, "linear_attn_config"))
        self.kda_layer_indices = [i - 1 for i in la["kda_layers"]]
        self.attn_layer_indices = [i - 1 for i in la["full_attn_layers"]]
        if sorted(self.kda_layer_indices + self.attn_layer_indices) != list(
            range(self.num_layers)
        ):
            raise ValueError(
                "linear_attn_config must name every layer 1.."
                f"{self.num_layers} once: kda_layers {la['kda_layers']}, "
                f"full_attn_layers {la['full_attn_layers']}"
            )
        if not self.attn_layer_indices:
            raise ValueError("no full-attention layer: nothing to page")
        self.num_attn_layers = len(self.attn_layer_indices)
        self.num_linear_attn_layers = len(self.kda_layer_indices)

        # KDA geometry.
        self.kda_heads = la["num_heads"]
        self.kda_dim = la["head_dim"]
        self.conv_kernel = la["short_conv_kernel_size"]
        self.kda_proj = self.kda_heads * self.kda_dim
        self.conv_dim = 3 * self.kda_proj

        # Latent attention geometry (the runner's cache contract: one
        # shared latent "head").
        self.num_heads = c.num_attention_heads
        self.kv_lora_rank = c.kv_lora_rank
        self.qk_nope_head_dim = c.qk_nope_head_dim
        self.qk_rope_head_dim = c.qk_rope_head_dim
        self.v_head_dim = c.v_head_dim
        self.qk_head_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
        self.latent_dim = self.kv_lora_rank + self.qk_rope_head_dim
        self.num_kv_heads = 1
        self.head_dim = self.latent_dim
        self.scale = self.qk_head_dim ** -0.5
        if getattr(c, "q_lora_rank", None) is not None:
            raise NotImplementedError("q_lora_rank with this family")
        if not getattr(c, "mla_use_nope", False):
            raise NotImplementedError(
                "this family's latent attention is served without rotation "
                "(mla_use_nope); a rotated variant has no table here"
            )

        # Experts. ``num_experts`` is what this process holds.
        self.num_experts = c.num_experts
        self.ep_size = int(getattr(c, "ep_size", 1) or 1)
        self.ep_rank = int(getattr(c, "ep_rank", 0) or 0)
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"ep_rank {self.ep_rank} of ep_size {self.ep_size}")
        self.router_width = self.num_experts * self.ep_size
        self.first_expert = (
            self.ep_rank * self.num_experts if self.ep_size > 1 else None
        )
        self.top_k = c.num_experts_per_token
        self.moe_intermediate = c.moe_intermediate_size
        self.n_shared = getattr(c, "num_shared_experts", 0) or 0
        self.n_group = getattr(c, "num_expert_group", 1) or 1
        self.topk_group = getattr(c, "topk_group", 1) or 1
        self.sigmoid_routing = (
            getattr(c, "moe_router_activation_func", "sigmoid") == "sigmoid"
        )
        self.renormalize = getattr(c, "moe_renormalize", True)
        self.routed_scaling = getattr(c, "routed_scaling_factor", 1.0)
        self.intermediate_size = c.intermediate_size
        self.first_dense = getattr(c, "first_k_dense_replace", 0)
        if getattr(c, "moe_layer_freq", 1) != 1:
            raise NotImplementedError("moe_layer_freq other than 1")
        self.moe_layer_indices = list(range(self.first_dense, self.num_layers))
        self.num_moe_layers = len(self.moe_layer_indices)

        kda = set(self.kda_layer_indices)
        self.kinds = [
            ("kda" if i in kda else "mla",
             "dense" if i < self.first_dense else "moe")
            for i in range(self.num_layers)
        ]
        self.head_layers, self.period, self.reps, self.tail_layers = (
            layer_plan(self.kinds)
        )
        self.period_start = len(self.head_layers)

    # ------------------------------------------------------------------
    # Layout
    # ------------------------------------------------------------------

    def _leaf_shapes(self, kind: str) -> dict[str, tuple[tuple, int]]:
        """Leaf -> (shape, fan_in; 0 = not drawn from a normal)."""
        D, Hk, dk = self.hidden_size, self.kda_heads, self.kda_dim
        pj = self.kda_proj
        if kind == "kda":
            return {
                "wq": ((D, pj), D), "wk": ((D, pj), D), "wv": ((D, pj), D),
                **{f"conv_{x}": ((self.conv_kernel, pj), self.conv_kernel)
                   for x in "qkv"},
                "f_a": ((D, dk), D), "f_b": ((dk, pj), dk),
                "dt_bias": ((pj,), 0), "a_log": ((Hk,), 0),
                "b_proj": ((D, Hk), D),
                "g_a": ((D, dk), D), "g_b": ((dk, pj), dk),
                "g_bias": ((pj,), 0), "o_norm": ((dk,), 0),
                "wo": ((pj, D), pj),
            }
        if kind == "mla":
            H, DC, DR = self.num_heads, self.kv_lora_rank, self.qk_rope_head_dim
            DN, DV = self.qk_nope_head_dim, self.v_head_dim
            return {
                "wq": ((D, H * (DN + DR)), D),
                "wkv_a": ((D, DC + DR), D),
                "kv_a_norm": ((DC,), 0),
                "wkv_b": ((DC, H * (DN + DV)), DC),
                "wo": ((H * DV, D), H * DV),
            }
        if kind == "dense":
            F = self.intermediate_size
            return {"wgate": ((D, F), D), "wup": ((D, F), D),
                    "wdown": ((F, D), F)}
        if kind == "moe":
            E, Fs = self.router_width, self.moe_intermediate * self.n_shared
            out = {"router": ((D, E), D), "router_bias": ((E,), 0)}
            if Fs:
                out |= {"ws_gate": ((D, Fs), D), "ws_up": ((D, Fs), D),
                        "ws_down": ((Fs, D), Fs)}
            return out
        if kind == "norms":
            return {"input_norm": ((D,), 0), "post_norm": ((D,), 0)}
        raise KeyError(kind)

    def _expert_shapes(self) -> dict[str, tuple[tuple, int]]:
        E, D, F = self.num_experts, self.hidden_size, self.moe_intermediate
        return {"we_gate": ((E, D, F), D), "we_up": ((E, D, F), D),
                "we_down": ((E, F, D), F)}

    def _place(self, layer: int, kind: str) -> tuple:
        """Where layer ``layer``'s leaves of ``kind`` live in the tree:
        ``("single", layer)`` or ``("periods", period, index in it)``."""
        s, p = self.period_start, self.period
        if not p or not s <= layer < s + p * self.reps:
            return ("single", layer)
        rep, off = divmod(layer - s, p)
        pattern = self.kinds[s: s + p]
        if kind == "norms":
            return ("periods", rep, off)
        col = 0 if kind in ("kda", "mla") else 1
        return ("periods", rep,
                sum(1 for k in pattern[:off] if k[col] == kind))

    def _expert_layer(self, layer: int) -> tuple[str, int]:
        """The expert stack that holds ``layer`` and its row there."""
        place = self._place(layer, "moe")
        if place[0] == "single":
            return "single", layer
        per = sum(1 for k in self.kinds[self.period_start:][: self.period]
                  if k[1] == "moe")
        return "periods", place[1] * per + place[2]

    # ------------------------------------------------------------------
    # Params
    # ------------------------------------------------------------------

    def _special(self, name: str, shape: tuple, dtype) -> jnp.ndarray | None:
        """Leaves the dummy load does not draw from a normal."""
        if name == "a_log":
            # exp(a_log) from 1 to 16 over the heads.
            return jnp.log(jnp.linspace(1.0, 16.0, shape[0], dtype=jnp.float32))
        if name == "dt_bias":
            # softplus(dt_bias) from 0.001 to 0.1 over a head's channels.
            dt = jnp.exp(jnp.linspace(
                math.log(1e-3), math.log(1e-1), self.kda_dim,
                dtype=jnp.float32,
            ))
            return jnp.tile(jnp.log(jnp.expm1(dt)), self.kda_heads)
        if name.endswith("norm"):
            return jnp.ones(shape, dtype)
        return None

    def init_dummy_params(self, rng: jax.Array, dtype=None) -> dict:
        dtype = dtype or self.dtype

        @partial(jax.jit, static_argnums=(1, 2, 3))
        def normal(key, shape, scale, out_dtype):
            return (
                jax.random.normal(key, shape, jnp.float32) * scale
            ).astype(out_dtype)

        def draw(name: str, key_name: str, layer: int, shape, fan_in):
            fixed = self._special(name, shape, dtype)
            if fixed is not None:
                return fixed
            key = jax.random.fold_in(
                jax.random.fold_in(rng, LEAF_IDS[key_name]), layer
            )
            if name == "router_bias":
                return normal(key, shape, 0.05, jnp.float32)
            if name == "g_bias":
                return normal(key, shape, 0.5, dtype)
            return normal(
                key, shape,
                DRAW_WIDTHS.get(key_name, 1.0) / math.sqrt(fan_in), dtype)

        def key_name(kind: str, name: str) -> str:
            return "mla_" + name if kind == "mla" and name in ("wq", "wo") else name

        def layer_leaves(layer: int, kind: str) -> dict:
            return {
                name: draw(name, key_name(kind, name), layer, shape, fan_in)
                for name, (shape, fan_in) in self._leaf_shapes(kind).items()
            }

        # An expert stack is drawn a layer at a time into its place (as
        # models/mixtral.py): the float32 draw of one layer is live, not
        # the stack's.
        @partial(jax.jit, donate_argnums=(0,))
        def put(stack, w, i):
            return jax.lax.dynamic_update_index_in_dim(stack, w, i, 0)

        D = self.hidden_size
        params: dict = {
            "embed": draw("embed", "embed", 0, (self.vocab_size, D), D),
            "final_norm": jnp.ones((D,), dtype),
            "single": {},
        }
        if not self.tie_embeddings:
            params["lm_head"] = draw(
                "lm_head", "lm_head", 0, (D, self.vocab_size), D)
        for layer in self.head_layers + self.tail_layers:
            mixer, ffn = self.kinds[layer]
            leaves = {
                "norms": layer_leaves(layer, "norms"),
                mixer: layer_leaves(layer, mixer),
                ffn: layer_leaves(layer, ffn),
            }
            if ffn == "moe":
                leaves["experts"] = {
                    name: draw(name, name, layer, shape, fan_in)
                    for name, (shape, fan_in) in self._expert_shapes().items()
                }
            params["single"][str(layer)] = leaves
        if self.reps:
            s, p = self.period_start, self.period
            pattern = self.kinds[s: s + p]
            groups: dict = {}
            for kind in ["norms"] + sorted({k for pair in pattern for k in pair}):
                col = 0 if kind in ("kda", "mla") else 1
                offs = [o for o in range(p)
                        if kind == "norms" or pattern[o][col] == kind]
                per_layer = [
                    [layer_leaves(s + rep * p + o, kind) for o in offs]
                    for rep in range(self.reps)
                ]
                groups[kind] = {
                    name: jnp.stack([
                        jnp.stack([lv[name] for lv in row]) for row in per_layer
                    ])
                    for name in self._leaf_shapes(kind)
                }
            params["periods"] = groups
            moe_layers = [
                s + rep * p + o for rep in range(self.reps)
                for o in range(p) if pattern[o][1] == "moe"
            ]
            if moe_layers:
                experts = {}
                for name, (shape, fan_in) in self._expert_shapes().items():
                    stack = jnp.zeros((len(moe_layers),) + shape, dtype)
                    for i, layer in enumerate(moe_layers):
                        stack = put(
                            stack, draw(name, name, layer, shape, fan_in), i)
                    experts[name] = stack
                params["experts"] = experts
        return params

    def hf_weight_map(self) -> dict:
        m = {
            "model.embed_tokens.weight": ("embed", False),
            "model.norm.weight": ("final_norm", False),
        }
        if not self.tie_embeddings:
            m["lm_head.weight"] = ("lm_head", True)

        def path(layer: int, kind: str, name: str) -> str:
            place = self._place(layer, kind)
            if place[0] == "single":
                return f"single.{layer}.{kind}.{name}"
            return f"periods.{kind}.{name}.{place[1]}.{place[2]}"

        kda = {
            "q_proj.weight": ("wq", True), "k_proj.weight": ("wk", True),
            "v_proj.weight": ("wv", True),
            "q_conv1d.weight": ("conv_q", False),
            "k_conv1d.weight": ("conv_k", False),
            "v_conv1d.weight": ("conv_v", False),
            "f_a_proj.weight": ("f_a", True), "f_b_proj.weight": ("f_b", True),
            "dt_bias": ("dt_bias", False), "A_log": ("a_log", False),
            "b_proj.weight": ("b_proj", True),
            "g_a_proj.weight": ("g_a", True), "g_b_proj.weight": ("g_b", True),
            "g_b_proj.bias": ("g_bias", False),
            "o_norm.weight": ("o_norm", False), "o_proj.weight": ("wo", True),
        }
        mla = {
            "q_proj.weight": ("wq", True),
            "kv_a_proj_with_mqa.weight": ("wkv_a", True),
            "kv_a_layernorm.weight": ("kv_a_norm", False),
            "kv_b_proj.weight": ("wkv_b", True),
            "o_proj.weight": ("wo", True),
        }
        first = self.first_expert or 0
        for i, (mixer, ffn) in enumerate(self.kinds):
            hf = f"model.layers.{i}"
            m[f"{hf}.input_layernorm.weight"] = (
                path(i, "norms", "input_norm"), False)
            m[f"{hf}.post_attention_layernorm.weight"] = (
                path(i, "norms", "post_norm"), False)
            for name, (ours, tr) in (kda if mixer == "kda" else mla).items():
                m[f"{hf}.self_attn.{name}"] = (path(i, mixer, ours), tr)
            if ffn == "dense":
                for name, ours in (("gate_proj", "wgate"), ("up_proj", "wup"),
                                   ("down_proj", "wdown")):
                    m[f"{hf}.mlp.{name}.weight"] = (path(i, "dense", ours), True)
                continue
            moe = f"{hf}.block_sparse_moe"
            m[f"{moe}.gate.weight"] = (path(i, "moe", "router"), True)
            m[f"{moe}.gate.e_score_correction_bias"] = (
                path(i, "moe", "router_bias"), False)
            if self.n_shared:
                for name, ours in (("gate_proj", "ws_gate"), ("up_proj", "ws_up"),
                                   ("down_proj", "ws_down")):
                    m[f"{moe}.shared_experts.{name}.weight"] = (
                        path(i, "moe", ours), True)
            where, row = self._expert_layer(i)
            for j in range(self.num_experts):
                e = f"{moe}.experts.{first + j}"
                for name, ours in (("w1", "we_gate"), ("w3", "we_up"),
                                   ("w2", "we_down")):
                    m[f"{e}.{name}.weight"] = (
                        f"single.{i}.experts.{ours}.{j}" if where == "single"
                        else f"experts.{ours}.{row}.{j}", True)
        return m

    def postprocess_weight(self, leaf_path: str, arr):
        import numpy as np

        if leaf_path.endswith(tuple("." + s for s in self.KEEP_F32_SUFFIXES)):
            return np.asarray(arr, np.float32).reshape(-1)
        if ".conv_" in leaf_path:
            return arr.squeeze(1).T  # [C, 1, K] -> [K, C]
        return arr

    def load_params(self, path: str, dtype=None, shardings=None) -> dict:
        from vllm_tpu.models.loader import load_params_from

        return load_params_from(self, path, dtype or self.dtype, shardings)

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------

    def _kda_mixer(self, h, lp, cache, g_li, md, tok_req, qsl, fresh):
        """``(out [T, D], cache)`` of one KDA layer; ``g_li`` its row in
        the state stacks."""
        t = h.shape[0]
        Hk, dk, pj = self.kda_heads, self.kda_dim, self.kda_proj
        slots = md.state_slots
        with jax.named_scope("kda_proj"):
            qkv = (h @ lp["wq"], h @ lp["wk"], h @ lp["wv"])  # [T, pj] each
            decay_in = (h @ lp["f_a"]) @ lp["f_b"]
            gate_in = (h @ lp["g_a"]) @ lp["g_b"] + lp["g_bias"]
            beta = jax.nn.sigmoid((h @ lp["b_proj"]).astype(jnp.float32))
        with jax.named_scope("kda_conv"):
            y, conv = kda_short_conv(
                qkv, cache["conv"],
                (lp["conv_q"], lp["conv_k"], lp["conv_v"]), g_li, slots,
                fresh, tok_req, qsl, md.num_seqs[0],
                decode_only=md.decode_only)
            cache = {**cache, "conv": conv}
            y = jax.nn.silu(y)  # [T, 3 * Hk, dk]: q's heads, k's, v's
            q = l2norm(y[:, :Hk]) * dk ** -0.5
            k = l2norm(y[:, Hk: 2 * Hk])
            v = y[:, 2 * Hk:]
            a = -jnp.exp(lp["a_log"].astype(jnp.float32))[:, None] * (
                jax.nn.softplus(
                    decay_in.astype(jnp.float32)
                    + lp["dt_bias"].astype(jnp.float32)
                ).reshape(t, Hk, dk)
            )
        with jax.named_scope("kda_state"):
            o, state = kda_attention(
                q, k, v, a, beta, cache["kda"], g_li, slots, fresh, qsl,
                md.num_seqs[0], decode_only=md.decode_only,
            )
            cache = {**cache, "kda": state}
        with jax.named_scope("kda_out"):
            o = rms_norm(o, lp["o_norm"], self.rms_eps) * jax.nn.sigmoid(
                gate_in.astype(jnp.float32).reshape(t, Hk, dk)
            )
            out = o.reshape(t, pj).astype(self.dtype) @ lp["wo"]
        return out, cache

    def _moe(self, h2, lp, experts, row, live):
        """Routed experts held here plus the shared one, and the live
        tokens routed to each held expert ``[E]``."""
        return share_of_sparse_layer(
            h2, lp, experts, row, live,
            select=dict(
                top_k=self.top_k, n_group=self.n_group,
                topk_group=self.topk_group, sigmoid=self.sigmoid_routing,
                method="noaux_tc", renormalize=self.renormalize,
                scaling=self.routed_scaling,
            ),
            num_experts=self.num_experts, first_expert=self.first_expert,
            shared=bool(self.n_shared),
        )

    def apply(
        self,
        params: dict,
        kv_cache: dict,  # {"paged", "conv", "kda"}
        input_ids: jnp.ndarray,  # [T]
        md: AttentionMetadata,
        token_lora_slot: jnp.ndarray | None = None,  # unused
    ) -> tuple[jnp.ndarray, dict, jnp.ndarray]:
        x = params["embed"][input_ids].astype(self.dtype)
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
        live = md.live_tokens()
        mla_kw = dict(
            num_heads=self.num_heads, nope_dim=self.qk_nope_head_dim,
            rope_dim=self.qk_rope_head_dim, v_dim=self.v_head_dim,
            latent_rank=self.kv_lora_rank, rms_eps=self.rms_eps,
            scale=self.scale, cos_sin=None,
        )

        def layer(x, cache, mixer, ffn, norms, mp, fp, experts, g_li, a_li, row):
            h = rms_norm(x, norms["input_norm"], self.rms_eps)
            if mixer == "kda":
                out, cache = self._kda_mixer(
                    h, mp, cache, g_li, md, tok_req, qsl, fresh)
            else:
                out, paged = latent_attention(
                    h, mp, cache["paged"], a_li, md, **mla_kw)
                cache = {**cache, "paged": paged}
            x = x + out
            h2 = rms_norm(x, norms["post_norm"], self.rms_eps)
            if ffn == "dense":
                with jax.named_scope("mlp"):
                    gate_up = jnp.concatenate(
                        [h2 @ fp["wgate"], h2 @ fp["wup"]], -1)
                    return x + silu_and_mul(gate_up) @ fp["wdown"], cache, None
            out, counts = self._moe(h2, fp, experts, row, live)
            return x + out, cache, counts

        counts_of: dict[int, jnp.ndarray] = {}
        kda_before = lambda i: sum(1 for j in self.kda_layer_indices if j < i)
        mla_before = lambda i: sum(1 for j in self.attn_layer_indices if j < i)

        def single(x, cache, i):
            mixer, ffn = self.kinds[i]
            lp = params["single"][str(i)]
            x, cache, counts = layer(
                x, cache, mixer, ffn, lp["norms"], lp[mixer], lp[ffn],
                lp.get("experts"), jnp.int32(kda_before(i)),
                jnp.int32(mla_before(i)), None,
            )
            if counts is not None:
                counts_of[i] = counts
            return x, cache

        cache = kv_cache
        for i in self.head_layers:
            x, cache = single(x, cache, i)
        if self.reps:
            s, p = self.period_start, self.period
            pattern = self.kinds[s: s + p]
            n_of = lambda kind, col: sum(1 for k in pattern if k[col] == kind)
            g0, a0 = kda_before(s), mla_before(s)
            experts = params.get("experts")
            stacks = params["periods"]

            def period_fn(carry, rep):
                x, cache = carry
                # One layer's leaves out of the whole stacks (the module
                # docstring says why not a slab of the period's).
                pick = lambda kind, j: jax.tree.map(
                    lambda leaf: leaf[rep, j], stacks[kind])
                seen = {"kda": 0, "mla": 0, "dense": 0, "moe": 0}
                counts = []
                for off, (mixer, ffn) in enumerate(pattern):
                    x, cache, c = layer(
                        x, cache, mixer, ffn, pick("norms", off),
                        pick(mixer, seen[mixer]), pick(ffn, seen[ffn]),
                        experts,
                        g0 + rep * n_of("kda", 0) + seen["kda"],
                        a0 + rep * n_of("mla", 0) + seen["mla"],
                        rep * n_of("moe", 1) + seen["moe"],
                    )
                    seen[mixer] += 1
                    seen[ffn] += 1
                    if c is not None:
                        counts.append(c)
                return (x, cache), (jnp.stack(counts) if counts else None)

            (x, cache), per_period = jax.lax.scan(
                period_fn, (x, cache), jnp.arange(self.reps, dtype=jnp.int32),
            )
            if per_period is not None:
                moe_offs = [o for o in range(p) if pattern[o][1] == "moe"]
                for rep in range(self.reps):
                    for j, o in enumerate(moe_offs):
                        counts_of[s + rep * p + o] = per_period[rep, j]
        for i in self.tail_layers:
            x, cache = single(x, cache, i)
        x = rms_norm(x, params["final_norm"], self.rms_eps)
        counts = jnp.stack([counts_of[i] for i in sorted(counts_of)])
        return x, cache, counts  # counts [moe layers, E held]

    def compute_logits(self, params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
        head = params["embed"].T if self.tie_embeddings else params["lm_head"]
        with jax.named_scope("lm_head"):
            return (hidden @ head.astype(hidden.dtype)).astype(jnp.float32)

    # ------------------------------------------------------------------
    # Runner contracts
    # ------------------------------------------------------------------

    def get_kv_cache_spec(self, block_size: int, dtype_bytes: int) -> dict[str, KVCacheSpec]:
        spec = MLAAttentionSpec(
            block_size=block_size, num_kv_heads=1,
            head_size=self.latent_dim, dtype_bytes=dtype_bytes,
        )
        return {f"layers.{i}": spec for i in self.attn_layer_indices}

    def state_bytes_per_slot(self) -> int:
        """One request's state over the KDA layers: the float32 matrices
        and the convolution tail in the served dtype."""
        tail = (self.conv_kernel - 1) * self.conv_dim * jnp.dtype(self.dtype).itemsize
        matrix = self.kda_heads * self.kda_dim * self.kda_dim * 4
        return len(self.kda_layer_indices) * (matrix + tail)

    def fixed_state_bytes(self, max_slots: int) -> int:
        return (max_slots + 1) * self.state_bytes_per_slot()

    def alloc_kv_cache(self, num_blocks: int, block_size: int, dtype) -> dict:
        lk = len(self.kda_layer_indices)
        s = self.max_state_slots + 1  # last slot = padding scratch
        return {
            "paged": jnp.zeros(
                mla_kv_cache_shape(
                    self.num_attn_layers, num_blocks, block_size,
                    self.latent_dim,
                ),
                dtype,
            ),
            # A slot is K-1 tokens of q's, k's and v's heads, [3 * H, dk]
            # each: whole tiles, so one DMA moves it (ops/kda.py).
            "conv": jnp.zeros(
                (lk, s, self.conv_kernel - 1, 3 * self.kda_heads,
                 self.kda_dim), self.dtype
            ),
            "kda": jnp.zeros(
                (lk, s, self.kda_heads, self.kda_dim, self.kda_dim),
                jnp.float32,
            ),
        }

    def param_shardings(self, data_axis: str | None = None,
                        model_axis: str = "tp") -> dict:
        """Replicated: this family is served a chip a replica (its experts
        by share); no tensor-parallel plan yet."""
        shapes = jax.eval_shape(
            lambda: self.init_dummy_params(jax.random.PRNGKey(0))
        )
        return jax.tree.map(lambda leaf: P(*([None] * leaf.ndim)), shapes)

    def kv_cache_sharding(self, model_axis: str = "tp") -> dict:
        return {
            "paged": P(None, None, None, None),
            "conv": P(None, None, None, None, None),
            "kda": P(None, None, None, None, None),
        }
