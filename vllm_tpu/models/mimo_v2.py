"""MiMo-V2 (Xiaomi, ``model_type`` ``mimo_v2``; MiMo-V2-Flash, MiMo-V2.5):
window and full attention layers of UNLIKE KV-head counts, a key head of
192 lanes beside a value head of 128, a learned sink logit a head in the
window layers' softmax, sparse experts without a shared one. The language
model only, from token ids: the vision and audio towers and the
multi-token-prediction layers are not served (no key of the published
config states their shapes).

No public implementation of this family is on this machine: the equations
below are written from the published ``config.json`` (the catalog's row
``MiMo-V2.5``), and what its keys do not say follows the family's
conventions and is listed under ``assumed`` in
``benchmark/configs/mimo-v2.5.json``.

Pre-norm residual decoder, RMSNorm (``layernorm_epsilon``), no bias. Layer
``l`` is a WINDOW layer where ``hybrid_layer_pattern[l]`` is 1 and a FULL
layer where it is 0; sparse where ``moe_layer_freq[l]`` is 1, else a dense
SwiGLU.

- attention of kind ``c``: ``H_c`` query heads and ``KH_c`` key heads of
  ``head_dim`` (192), ``KH_c`` value heads of ``v_head_dim`` (128); the
  window kind reads ``swa_*`` (8 KV heads where a full layer has 4). Rope,
  rotate-half, on the first ``int(partial_rotary_factor * head_dim)`` lanes
  of q and k, theta ``rope_theta`` in a full layer and ``swa_rope_theta``
  in a window layer. ``v`` times ``attention_value_scale``. Scores
  ``q k / sqrt(head_dim)``, causal; a window layer keeps key ``j`` for
  query ``i`` only where ``i - j < sliding_window`` and, where
  ``add_swa_attention_sink_bias``, head ``a``'s learned ``s_a`` joins the
  softmax's denominator: ``p_ij = exp(s_ij) / (exp(s_a) + sum_k exp(s_ik))``
  (``paged_attention(sinks=...)``: inside both kernels).
- sparse layer: sigmoid scores in float32 over ALL the router's outputs,
  the ``num_experts_per_tok`` largest of ``score + bias`` chosen
  (``noaux_tc``, one group), their weights the scores without the bias,
  renormalised, times ``routed_scaling_factor`` (null: 1).

Cache contract: KV GROUPS over one page pool with a block size a GROUP
(``core/kv_cache_utils.group_kv_cache_specs``). A window layer's token
costs twice a full layer's bytes, so a page holds 16 tokens of a window
group or 32 of a full group, in the split layout of ``ops/attention.py::
split_kv_cache_shape``: ONE array ``[layers of a group, pages, KH * BS,
Dk_pad + Dv]`` for both kinds (8 x 16 and 4 x 32 rows of 256 + 128 lanes),
each layer's calls told its kind's KV-head count. ``md.block_tables
[G, R, B]`` / ``md.slot_mapping [G, T]`` carry a table and a slot mapping a
group; the window groups' pages go back to the pool as a request passes
them.

Expert share: ``config.json`` may carry ``ep_size`` / ``ep_rank`` as
``models/laguna.py`` reads them (``layers/moe.py::share_of_sparse_layer``):
``n_routed_experts`` is then what this process holds.

How the layers run. The pattern is no one period repeated (the cut the
benchmark serves is F W W W W F W W W W), so the layers are split into
RUNS of consecutive layers of one kind of attention and of FFN: a run of
one layer is traced in line, a longer one is a ``lax.scan`` over its
layers whose body reads layer ``first + i``'s leaves IN PLACE out of the
whole stacks (weights are stacked BY KIND, because the kinds differ in
shape; one consumer a slice, no slab of a run's weights sliced out and
written back: PERF.md, PR 40) and its KV group and place out of two small
tables. The cut is 2 layers in line and 2 scans: 4 layer bodies a step
program where 10 would be unrolled (its two step programs take 34 and
38 s to compile on a v5e: PERF.md, PR 45). Param tree::

    embed [V, D], final_norm [D], lm_head [D, V]
    norms/   input_norm post_norm [L, D]
    full/    wq [nF, D, H*Dk] wk [nF, D, KHf*Dk] wv [nF, D, KHf*Dv]
             wo [nF, H*Dv, D]   (+ sinks [nF, H] f32 where the config says)
    window/  the same at KHw, [nW, ...], sinks [nW, H] f32
    dense/   wgate wup [nD, D, F] wdown [nD, F, D]
    moe/     router [nM, D, E_all] router_bias [nM, E_all] f32
    experts/ we_gate we_up [nM, E, D, Fm] we_down [nM, E, Fm, D]
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from vllm_tpu.core.kv_cache_utils import (
    FullAttentionSpec,
    KVCacheSpec,
    SlidingWindowSpec,
    group_kv_cache_specs,
)
from vllm_tpu.layers.activation import silu_and_mul
from vllm_tpu.layers.layernorm import rms_norm
from vllm_tpu.layers.moe import share_of_sparse_layer
from vllm_tpu.layers.rotary import RotaryEmbedding
from vllm_tpu.logger import init_logger
from vllm_tpu.ops.attention import (
    AttentionMetadata,
    kv_dequant_scale,
    paged_attention,
    split_kv_cache_shape,
    write_kv,
)

logger = init_logger(__name__)

# A leaf's place in the dummy draw's key: fold_in(fold_in(rng, id), layer).
LEAF_IDS = {
    name: i for i, name in enumerate((
        "embed", "lm_head", "wq", "wk", "wv", "wo", "sinks",
        "wgate", "wup", "wdown", "router", "router_bias",
        "we_gate", "we_up", "we_down",
    ))
}

# Widths of the dummy draw that are not 1 / sqrt(fan_in) (times that), as
# models/laguna.py has them and for its reasons: the query wider, so that
# some tens of keys carry a row and a window can be told from the whole
# context; the routed experts' way out narrower, so that one expert chosen
# differently in bf16 does not move every later layer's choices.
DRAW_WIDTHS = {"wq": 2.0, "we_down": 0.25}
# The two leaves that are no matrices. A sink logit is drawn uniform in
# [4.5, 7): with the query at width 2 a window row's scores have a
# deviation of 2 and 128 of them a log-sum-exp near 6.85, so that the sink
# takes a tenth to a half of the row's softmax (more in a row's first
# positions) and a model that leaves it out is seen. The selection bias is
# normal at 0.01: near the eighth of 256 sigmoid scores neighbours stand
# some 0.007 apart, so that it changes some tenth of the choices.
SINK_RANGE = (4.5, 7.0)
ROUTER_BIAS_WIDTH = 0.01

FULL, WINDOW = "full", "window"


class MiMoV2ForCausalLM:
    supports_lora = False
    enable_lora = False
    returns_expert_counts = True  # apply() -> (hidden, cache, counts)
    # The cache manager frees by group; no ONE window for the whole model.
    sliding_window = None

    def __init__(self, hf_config: Any, dtype=jnp.bfloat16,
                 quantization: str | None = None) -> None:
        if quantization:
            logger.warning(
                "weight quantization is not supported for %s; running "
                "unquantized", type(self).__name__,
            )
        c = hf_config
        self.hf_config = c
        self.dtype = dtype
        self.quantization = None
        self.num_layers = L = c.num_hidden_layers
        self.hidden_size = c.hidden_size
        self.vocab_size = c.vocab_size
        self.rms_eps = getattr(c, "layernorm_epsilon", 1e-5)
        self.tie_embeddings = getattr(c, "tie_word_embeddings", False)
        self.intermediate_size = c.intermediate_size
        self.attn_window = int(c.sliding_window)
        self.value_scale = float(getattr(c, "attention_value_scale", None) or 1.0)
        if getattr(c, "attention_bias", False):
            raise NotImplementedError("attention_bias")
        if getattr(c, "hidden_act", "silu") != "silu":
            raise NotImplementedError(f"hidden_act {c.hidden_act}")

        pattern = list(c.hybrid_layer_pattern)[:L]
        sparse = [bool(f) for f in list(c.moe_layer_freq)[:L]]
        if len(pattern) != L or len(sparse) != L:
            raise ValueError(
                "hybrid_layer_pattern and moe_layer_freq must name every "
                f"one of the {L} layers")
        self.kinds = [WINDOW if p else FULL for p in pattern]
        self.sparse = sparse
        # A kind's geometry: (q heads, KV heads, key width, value width).
        self.geometry = {
            FULL: (c.num_attention_heads, c.num_key_value_heads,
                   c.head_dim, c.v_head_dim),
            WINDOW: (getattr(c, "swa_num_attention_heads", c.num_attention_heads),
                     getattr(c, "swa_num_key_value_heads", c.num_key_value_heads),
                     getattr(c, "swa_head_dim", c.head_dim),
                     getattr(c, "swa_v_head_dim", c.v_head_dim)),
        }
        if self.geometry[FULL][2:] != self.geometry[WINDOW][2:]:
            raise NotImplementedError(
                "one key width and one value width over both kinds of layer "
                f"(one page's lanes); got {self.geometry}")
        self.head_dim, self.v_head_dim = self.geometry[FULL][2:]
        self.scale = self.head_dim ** -0.5
        self.has_sinks = {
            FULL: bool(getattr(c, "add_full_attention_sink_bias", False)),
            WINDOW: bool(getattr(c, "add_swa_attention_sink_bias", False)),
        }
        self.num_heads = self.geometry[FULL][0]
        self.num_window_attn_layers = self.kinds.count(WINDOW)
        self.num_sink_attn_layers = sum(
            self.has_sinks[kind] for kind in self.kinds)

        scaling = getattr(c, "rope_scaling", None) or {}
        if scaling.get("rope_type", scaling.get("type", "default")) != "default":
            raise NotImplementedError(f"rope_scaling {scaling}")
        rotary = int(getattr(c, "partial_rotary_factor", 1.0) * self.head_dim)
        # Two tables: the kinds differ in theta. Under a worker they hold
        # the positions it serves (``layers/rotary.py::serving_positions``).
        self.rope_of = {
            kind: RotaryEmbedding(
                self.head_dim, c.max_position_embeddings, theta=theta,
                rotary_dim=rotary)
            for kind, theta in (
                (FULL, c.rope_theta),
                (WINDOW, getattr(c, "swa_rope_theta", c.rope_theta)))
        }

        # Experts. ``n_routed_experts`` is what this process holds.
        self.num_experts = c.n_routed_experts
        self.ep_size = int(getattr(c, "ep_size", 1) or 1)
        self.ep_rank = int(getattr(c, "ep_rank", 0) or 0)
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"ep_rank {self.ep_rank} of ep_size {self.ep_size}")
        self.router_width = self.num_experts * self.ep_size
        self.first_expert = (
            self.ep_rank * self.num_experts if self.ep_size > 1 else None
        )
        self.top_k = c.num_experts_per_tok
        self.moe_intermediate = c.moe_intermediate_size
        self.routed_scaling = getattr(c, "routed_scaling_factor", None) or 1.0
        self.renormalize = getattr(c, "norm_topk_prob", True)
        if (getattr(c, "scoring_func", "sigmoid") != "sigmoid"
                or getattr(c, "topk_method", "noaux_tc") != "noaux_tc"
                or getattr(c, "n_shared_experts", None)):
            raise NotImplementedError(
                "this family routes by sigmoid scores with a selection bias "
                "(noaux_tc) and has no shared expert")
        self.select = dict(
            top_k=self.top_k, sigmoid=True, method="noaux_tc",
            n_group=getattr(c, "n_group", 1) or 1,
            topk_group=getattr(c, "topk_group", 1) or 1,
            renormalize=self.renormalize, scaling=self.routed_scaling,
        )
        self.num_moe_layers = sum(sparse)

        # A layer's place in its kind's stack and in its FFN's.
        count = {FULL: 0, WINDOW: 0, True: 0, False: 0}
        self.attn_index, self.ffn_index = [], []
        for kind, sp in zip(self.kinds, sparse):
            self.attn_index.append(count[kind])
            self.ffn_index.append(count[sp])
            count[kind] += 1
            count[sp] += 1
        # RUNS of consecutive layers alike in attention and in FFN:
        # (first layer, length).
        self.runs: list[tuple[int, int]] = []
        for i in range(L):
            same = self.runs and (self.kinds[i], sparse[i]) == (
                self.kinds[i - 1], sparse[i - 1])
            if same:
                self.runs[-1] = (self.runs[-1][0], self.runs[-1][1] + 1)
            else:
                self.runs.append((i, 1))

        # Where a layer's K/V live: its KV group and its place in it.
        groups = self._kv_groups(16)
        self.num_kv_groups = len(groups)
        self.kv_group_places = groups[0].num_places
        place = {
            int(name.split(".")[1]): (g, i)
            for g, group in enumerate(groups)
            for i, name in enumerate(group.layer_names)
        }
        self.kv_place = [place[i] for i in range(L)]

    # ------------------------------------------------------------------
    # Layout
    # ------------------------------------------------------------------

    def _leaf_shapes(self, kind: str) -> dict[str, tuple[tuple, int]]:
        """Leaf -> (shape, fan_in; 0 = not drawn from a normal at
        1 / sqrt(fan_in))."""
        D = self.hidden_size
        if kind in (FULL, WINDOW):
            H, KH, Dk, Dv = self.geometry[kind]
            out = {
                "wq": ((D, H * Dk), D), "wk": ((D, KH * Dk), D),
                "wv": ((D, KH * Dv), D), "wo": ((H * Dv, D), H * Dv),
            }
            if self.has_sinks[kind]:
                out["sinks"] = ((H,), 0)
            return out
        if kind == "dense":
            F = self.intermediate_size
            return {"wgate": ((D, F), D), "wup": ((D, F), D),
                    "wdown": ((F, D), F)}
        if kind == "moe":
            return {"router": ((D, self.router_width), D),
                    "router_bias": ((self.router_width,), 0)}
        if kind == "norms":
            return {"input_norm": ((D,), 0), "post_norm": ((D,), 0)}
        if kind == "experts":
            E, F = self.num_experts, self.moe_intermediate
            return {"we_gate": ((E, D, F), D), "we_up": ((E, D, F), D),
                    "we_down": ((E, F, D), F)}
        raise KeyError(kind)

    # ------------------------------------------------------------------
    # Params
    # ------------------------------------------------------------------

    def init_dummy_params(self, rng: jax.Array, dtype=None) -> dict:
        dtype = dtype or self.dtype

        @partial(jax.jit, static_argnums=(1, 2, 3))
        def normal(key, shape, scale, out_dtype):
            return (
                jax.random.normal(key, shape, jnp.float32) * scale
            ).astype(out_dtype)

        def draw(name: str, layer: int, shape, fan_in):
            key = jax.random.fold_in(
                jax.random.fold_in(rng, LEAF_IDS.get(name, 0)), layer)
            if name == "sinks":
                lo, hi = SINK_RANGE
                return jax.random.uniform(key, shape, jnp.float32, lo, hi)
            if name == "router_bias":
                return normal(key, shape, ROUTER_BIAS_WIDTH, jnp.float32)
            if not fan_in:
                return jnp.ones(shape, dtype)  # the norms' scales
            return normal(
                key, shape, DRAW_WIDTHS.get(name, 1.0) / math.sqrt(fan_in),
                dtype)

        def stack_of(kind: str, layers: list[int]) -> dict:
            return {
                name: jnp.stack([draw(name, i, shape, fan_in) for i in layers])
                for name, (shape, fan_in) in self._leaf_shapes(kind).items()
            }

        # An expert stack is drawn a layer at a time into its place: the
        # float32 draw of one layer is live, not the stack's.
        @partial(jax.jit, donate_argnums=(0,))
        def put(stack, w, i):
            return jax.lax.dynamic_update_index_in_dim(stack, w, i, 0)

        D, L = self.hidden_size, self.num_layers
        of = lambda test: [i for i in range(L) if test(i)]
        params: dict = {
            "embed": draw("embed", 0, (self.vocab_size, D), D),
            "final_norm": jnp.ones((D,), dtype),
            "norms": stack_of("norms", list(range(L))),
        }
        if not self.tie_embeddings:
            params["lm_head"] = draw("lm_head", 0, (D, self.vocab_size), D)
        for kind in (FULL, WINDOW):
            layers = of(lambda i: self.kinds[i] == kind)
            if layers:
                params[kind] = stack_of(kind, layers)
        dense = of(lambda i: not self.sparse[i])
        if dense:
            params["dense"] = stack_of("dense", dense)
        sparse = of(lambda i: self.sparse[i])
        if sparse:
            params["moe"] = stack_of("moe", sparse)
            experts = {}
            for name, (shape, fan_in) in self._leaf_shapes("experts").items():
                stack = jnp.zeros((len(sparse),) + shape, dtype)
                for row, layer in enumerate(sparse):
                    stack = put(stack, draw(name, layer, shape, fan_in), row)
                experts[name] = stack
            params["experts"] = experts
        return params

    # The published checkpoints fuse q, k and v into one matrix a layer
    # (``attention_projection_layout`` ``fused_qkv``): split at load.
    SPLIT_SUFFIXES = ("self_attn.qkv_proj.weight",)

    def split_hf_tensor(self, hf_name: str, arr) -> list[tuple[str, Any]]:
        layer = int(hf_name.split(".")[2])
        H, KH, Dk, Dv = self.geometry[self.kinds[layer]]
        rows = np.cumsum([H * Dk, KH * Dk, KH * Dv])
        if arr.shape[0] != rows[-1]:
            raise ValueError(
                f"{hf_name}: {arr.shape[0]} rows, expected {rows[-1]} "
                f"({H} q heads and {KH} key heads of {Dk}, {KH} value heads "
                f"of {Dv})")
        stem = hf_name.removesuffix("qkv_proj.weight")
        return [
            (f"{stem}q_proj.weight", arr[: rows[0]]),
            (f"{stem}k_proj.weight", arr[rows[0]: rows[1]]),
            (f"{stem}v_proj.weight", arr[rows[1]:]),
        ]

    def hf_weight_map(self) -> dict:
        m = {
            "model.embed_tokens.weight": ("embed", False),
            "model.norm.weight": ("final_norm", False),
        }
        if not self.tie_embeddings:
            m["lm_head.weight"] = ("lm_head", True)
        attn = {"q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "o_proj": "wo"}
        glu = (("gate_proj", "gate"), ("up_proj", "up"), ("down_proj", "down"))
        first = self.first_expert or 0
        for i, kind in enumerate(self.kinds):
            hf, at, ffn = f"model.layers.{i}", self.attn_index[i], self.ffn_index[i]
            m[f"{hf}.input_layernorm.weight"] = (f"norms.input_norm.{i}", False)
            m[f"{hf}.post_attention_layernorm.weight"] = (
                f"norms.post_norm.{i}", False)
            for name, ours in attn.items():
                m[f"{hf}.self_attn.{name}.weight"] = (f"{kind}.{ours}.{at}", True)
            if self.has_sinks[kind]:
                m[f"{hf}.self_attn.attention_sink_bias"] = (
                    f"{kind}.sinks.{at}", False)
            if not self.sparse[i]:
                for name, ours in glu:
                    m[f"{hf}.mlp.{name}.weight"] = (f"dense.w{ours}.{ffn}", True)
                continue
            m[f"{hf}.mlp.gate.weight"] = (f"moe.router.{ffn}", True)
            m[f"{hf}.mlp.gate.e_score_correction_bias"] = (
                f"moe.router_bias.{ffn}", False)
            for j in range(self.num_experts):
                for name, ours in glu:
                    m[f"{hf}.mlp.experts.{first + j}.{name}.weight"] = (
                        f"experts.we_{ours}.{ffn}.{j}", True)
        return m

    def load_params(self, path: str, dtype=None, shardings=None) -> dict:
        from vllm_tpu.models.loader import load_params_from

        params = load_params_from(self, path, dtype or self.dtype, shardings)
        # The two leaves the softmax and the choice read in float32.
        for kind, name in ((FULL, "sinks"), (WINDOW, "sinks"),
                           ("moe", "router_bias")):
            if name in params.get(kind, {}):
                params[kind][name] = params[kind][name].astype(jnp.float32)
        return params

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------

    def _attention(self, h, ap, kv, kind, group, place, md):
        """``(out [T, D], kv)`` of one attention layer: ``group`` its KV
        group, ``place`` its layer index in the cache (both static in a run
        of one layer, traced in a scan)."""
        t = h.shape[0]
        H, KH, Dk, Dv = self.geometry[kind]
        window = kind == WINDOW
        with jax.named_scope("attn_window" if window else "attn_full"):
            # The projections end at a barrier as [T, heads * width] rows,
            # as models/llama.py's (PERF.md, PR 27).
            q, k, v = jax.lax.optimization_barrier(
                (h @ ap["wq"], h @ ap["wk"], h @ ap["wv"]))
            q = q.reshape(t, H, Dk)
            k = k.reshape(t, KH, Dk)
            v = v.reshape(t, KH, Dv)
            if self.value_scale != 1.0:
                v = v * jnp.asarray(self.value_scale, v.dtype)
            q, k = self.rope_of[kind](md.positions, q, k)
            md_g = dataclasses.replace(
                md, block_tables=md.block_tables[group],
                slot_mapping=md.slot_mapping[group],
            )
            kv = write_kv(kv, place, k, v, md_g.slot_mapping)
            kv_scale = kv_dequant_scale(kv)
            out = paged_attention(
                q, kv, place, md_g, self.scale,
                sliding_window=self.attn_window if window else None,
                k_scale=kv_scale, v_scale=kv_scale,
                kernel_suffix="_window" if window else "",
                sinks=ap.get("sinks"), num_kv_heads=KH,
            )
        return out.reshape(t, H * Dv) @ ap["wo"], kv

    def apply(
        self,
        params: dict,
        kv_cache: jnp.ndarray,  # [places of a group, pages, KH * BS, lanes]
        input_ids: jnp.ndarray,  # [T]
        md: AttentionMetadata,
        token_lora_slot: jnp.ndarray | None = None,  # unused
    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        assert md.block_tables.ndim == 3 and (
            md.block_tables.shape[0] == self.num_kv_groups), (
            "a block table a KV group", md.block_tables.shape)
        x = params["embed"][input_ids].astype(self.dtype)
        t = x.shape[0]
        live = md.live_tokens()
        experts = params.get("experts")
        groups = jnp.asarray([g for g, _ in self.kv_place], jnp.int32)
        places = jnp.asarray([p for _, p in self.kv_place], jnp.int32)

        def layer(x, kv, kind, sparse, i, at, ffn, group, place):
            """Layer ``i``: the ``at``-th of its kind, the ``ffn``-th of
            its FFN's; every index an int, or traced in a scan."""
            pick = lambda tree, idx: jax.tree.map(lambda leaf: leaf[idx], tree)
            norms = pick(params["norms"], i)
            h = rms_norm(x, norms["input_norm"], self.rms_eps)
            out, kv = self._attention(
                h, pick(params[kind], at), kv, kind, group, place, md)
            x = x + out
            h2 = rms_norm(x, norms["post_norm"], self.rms_eps)
            if not sparse:
                fp = pick(params["dense"], ffn)
                with jax.named_scope("dense_mlp"):
                    gate_up = jnp.concatenate(
                        [h2 @ fp["wgate"], h2 @ fp["wup"]], -1)
                    return x + silu_and_mul(gate_up) @ fp["wdown"], kv, None
            with jax.named_scope("moe"):
                out, counts = share_of_sparse_layer(
                    h2, pick(params["moe"], ffn), experts, ffn, live,
                    select=self.select, num_experts=self.num_experts,
                    first_expert=self.first_expert, shared=False,
                )
            return x + out, kv, counts

        kv = kv_cache
        counts = []
        for first, n in self.runs:
            kind, sparse = self.kinds[first], self.sparse[first]
            at, ffn = self.attn_index[first], self.ffn_index[first]
            if n == 1:
                x, kv, c = layer(x, kv, kind, sparse, first, at, ffn,
                                 *self.kv_place[first])
                if c is not None:
                    counts.append(c[None])
                continue

            def one(carry, i, kind=kind, sparse=sparse, first=first, at=at,
                    ffn=ffn):
                x, kv = carry
                x, kv, c = layer(x, kv, kind, sparse, first + i, at + i,
                                 ffn + i, groups[first + i], places[first + i])
                return (x, kv), c

            (x, kv), c = jax.lax.scan(
                one, (x, kv), jnp.arange(n, dtype=jnp.int32))
            if sparse:
                counts.append(c)
        x = rms_norm(x, params["final_norm"], self.rms_eps)
        counts = (jnp.concatenate(counts) if counts
                  else jnp.zeros((0, self.num_experts), jnp.int32))
        return x, kv, counts  # counts [moe layers, E held]

    def compute_logits(self, params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
        head = params["embed"].T if self.tie_embeddings else params["lm_head"]
        with jax.named_scope("lm_head"):
            return (hidden @ head.astype(hidden.dtype)).astype(jnp.float32)

    # ------------------------------------------------------------------
    # Runner contracts
    # ------------------------------------------------------------------

    def get_kv_cache_spec(self, block_size: int, dtype_bytes: int) -> dict[str, KVCacheSpec]:
        """Two specs: the kinds differ in KV heads, so in bytes a token;
        ``block_size`` is the window kind's (the most bytes a token), the
        grouping gives the full kind its multiple."""
        out = {}
        for i, kind in enumerate(self.kinds):
            _, KH, Dk, Dv = self.geometry[kind]
            common = dict(block_size=block_size, num_kv_heads=KH, head_size=Dk,
                          v_head_size=Dv, dtype_bytes=dtype_bytes)
            out[f"layers.{i}"] = (
                FullAttentionSpec(**common) if kind == FULL
                else SlidingWindowSpec(**common, sliding_window=self.attn_window))
        return out

    def _kv_groups(self, block_size: int) -> list:
        """The layers' KV groups at the engine's ``block_size`` (tokens a
        page do not depend on the dtype: every kind scales alike)."""
        return group_kv_cache_specs(self.get_kv_cache_spec(block_size, 2))

    def kv_cache_shape(self, num_blocks: int, block_size: int) -> tuple:
        """ONE array for every group: ``[places of a group, pages, KH * BS,
        Dk_pad + Dv]``, the rows a page the same for both kinds."""
        shapes = {
            split_kv_cache_shape(
                g.num_places, num_blocks, g.block_size,
                g.kv_cache_spec.num_kv_heads, self.head_dim, self.v_head_dim)
            for g in self._kv_groups(block_size)
        }
        if len(shapes) != 1:
            raise NotImplementedError(
                f"KV groups of unlike rows a page: {sorted(shapes)}")
        return shapes.pop()

    def ragged_kernel_geometry(self, block_size: int) -> tuple:
        """The FULL layers' call of the ragged kernel, for the runner's
        count of its pairs: (q heads, KV heads, the key's stored lanes,
        tokens a page of the full group)."""
        H, KH, Dk, _ = self.geometry[FULL]
        full = next(g for g in self._kv_groups(block_size)
                    if g.sliding_window is None)
        return H, KH, -(-Dk // 128) * 128, full.block_size

    def param_shardings(self, data_axis: str | None = None,
                        model_axis: str = "tp") -> dict:
        """Replicated: this family is served a chip a replica (its experts
        by share); no tensor-parallel plan yet."""
        shapes = jax.eval_shape(
            lambda: self.init_dummy_params(jax.random.PRNGKey(0))
        )
        return jax.tree.map(lambda leaf: P(*([None] * leaf.ndim)), shapes)

    def kv_cache_sharding(self, model_axis: str = "tp") -> P:
        return P(None, None, None, None)
