"""LFM2-MoE: a gated short convolution whose only state is its own tail in
most layers, rotary GQA attention with a norm a head in the others, a dense
SwiGLU in the first layers and sparse experts (sigmoid scores, a selection
bias) after them.

Reference analog: ``transformers/models/lfm2/modeling_lfm2.py`` (the dense
sibling: ``Lfm2ShortConv``, ``Lfm2Attention``, ``Lfm2DecoderLayer``) for
the mixers and the layer's wiring; the sparse block by the published
``config.json``'s keys (``model_type`` ``lfm2_moe``). With ``rms`` of eps
``norm_eps`` and no bias anywhere::

    x <- x + OP_l(rms_op(x));   x <- x + FF_l(rms_ff(x))

``OP_l`` by ``layer_types[l]``. ``conv``: ``[B, C, u] = h W_in`` (three of
``D`` in that order), ``g = B * u``, a depthwise causal convolution of
``conv_L_cache`` taps over time with no activation (``c_t = sum_j w_j
g_{t - K + 1 + j}``, ``g`` zero before the request's first token), ``(C *
c) W_out``. ``full_attention``: q, k, v as heads of ``D / heads`` lanes,
an RMSNorm over each head's lanes of q and of k (one weight for the
heads), THEN rope (rotate-half over the whole head), causal softmax of ``q
k / sqrt(head)``, ``W_o``. ``FF_l``: ``(SiLU(h W1) * (h W3)) W2`` for ``l <
num_dense_layers``; after them ``s = sigmoid(h W_r)`` in float32, the
``num_experts_per_tok`` largest of ``s + b`` (``use_expert_bias``; ``b``
for the CHOICE alone), their weights ``s`` at the chosen, divided by their
sum + 1e-6 (``norm_topk_prob``), times ``routed_scaling_factor``, over
experts of the same SwiGLU form. ``x = embed[ids]`` going in, ``logits =
rms_out(x) embed^T`` coming out (the family's ``embedding_norm`` stands
AFTER the last layer; the head is the embedding).

Cache contract: the hybrid one (``models/kimi_linear.py``). Pages of K/V
for the attention layers alone, in the packed layout where a head is under
a lane tile (``[La, NB, BS, KH, 2 * 64]``, k||v in one row), and ONE slot
array for the convolution layers, ``conv [Lc, S + 1, K - 1, D / lanes,
lanes]`` in the served dtype: a request's whole recurrent state is the K -
1 last values of ``g`` a layer (8 KiB at 2 x 2048 bf16), which
``ops/kda.py::kda_short_conv`` reads and writes in place by one DMA a live
row; the last slot is the padding rows' scratch. There is no state matrix.

Layers. Every leaf is stacked over the layers OF ITS KIND (``conv/*
[Lc, ...]``, ``attn/* [La, ...]``, ``norms/* [L, ...]``, ``dense/*``,
``moe/*`` and ``experts/* [Lm, E, ...]``), and layer ``l`` reads row
``(layers of that kind before l)`` of each, so the tree does not depend on
how ``apply`` is traced. ``apply`` runs the longest periodic stretch
(``models/kimi_linear.py::layer_plan``) as ONE ``lax.scan`` over whole
periods, each layer of the body slicing its own row out of the whole
stacks (one consumer a slice, as PR 40 found it must be), and traces the
layers before and after it in line. The published 24 layers (conv conv |
attention conv conv conv x 4 | attention conv conv attention conv conv):
layers 0-1 in line, 2-17 four iterations of a four-layer body, 18-23 in
line, 12 layer bodies traced. The benchmark's 16 (the first sixteen): 0-1
in line, 2-13 three iterations, 14-15 in line, 8 bodies traced.
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
from vllm_tpu.layers.moe import share_of_sparse_layer
from vllm_tpu.layers.rotary import RotaryEmbedding, _apply_rotate_half
from vllm_tpu.logger import init_logger
from vllm_tpu.models.kimi_linear import layer_plan
from vllm_tpu.ops.attention import (
    AttentionMetadata,
    kv_cache_shape,
    kv_dequant_scale,
    paged_attention,
    write_kv,
)
from vllm_tpu.ops.kda import kda_short_conv

logger = init_logger(__name__)

# A leaf's place in the dummy draw's key: fold_in(fold_in(rng, id), layer).
LEAF_IDS = {
    name: i for i, name in enumerate((
        "embed", "w_in", "conv_w", "w_out", "wq", "wk", "wv", "wo",
        "w1", "w3", "w2", "router", "router_bias",
        "we_gate", "we_up", "we_down",
    ))
}

# What the dummy draw does not take at normal(0, 1 / sqrt(fan_in)).
# ``q_norm``: a norm a head leaves every score ``q k / sqrt(head)`` one
# wide whatever ``wq`` is drawn at, and a softmax over a thousand such keys
# is nearly flat (some 370 of them carry a row: the mixer returns the mean
# of its values). The query's norm weight is this constant: at 2 some 18 of
# a thousand keys carry a row. At 4 one key does, and a rounding of q or k
# in bfloat16 moves that one weight by a twentieth: the served logprobs
# then stand 0.20 from the float32 reference's (my chip run, PR 53), which
# hides every control but the crudest. ``router_bias``: a mean and a spread
# (of a unit normal), float32. At zeros the choice by ``s + b`` is the
# choice by ``s`` and nothing could tell which of the two weighs the
# experts. The spread is what changes the choice, and is kept small so that
# no expert is starved (a trained bias evens the load out; a wide random
# one would skew it); the mean changes no choice at all and is what a
# comparison sees: the chosen scores lie near 0.8 and are a few hundredths
# apart, so ``s / sum(s)`` and ``(s + b) / sum(s + b)`` differ by a quarter
# with it and by little without. The taps are drawn like any matrix, K
# their fan-in: all three matter alike.
Q_NORM_DRAW = 2.0
ROUTER_BIAS_DRAW = (-0.6, 0.1)  # mean, spread
RENORM_GUARD = 1e-6  # the family's, added to the chosen scores' sum


class Lfm2MoeForCausalLM:
    supports_lora = False
    enable_lora = False
    is_hybrid_ssm = True  # per-request state slots (the convolution's tail)
    max_state_slots = 256  # set by the worker
    returns_expert_counts = True  # apply() -> (hidden, cache, counts)
    # ``cache["paged"]`` is plain K/V pages under ``paged_attention``: the
    # runner counts the attention kernels' work as it does a dense model's.
    plain_kv_pages = True
    first_expert = None  # every expert of the router is held here

    # The selection bias stays f32 at load.
    KEEP_F32_SUFFIXES = ("router_bias",)

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
        self.rms_eps = getattr(c, "norm_eps", 1e-5)
        if getattr(c, "conv_bias", False):
            raise NotImplementedError("conv_bias with this family")
        if not getattr(c, "tie_embedding", getattr(c, "tie_word_embeddings", True)):
            raise NotImplementedError("this family's head is its embedding")

        # Which layer is which: the first ``num_hidden_layers`` entries.
        kinds = list(c.layer_types)[: self.num_layers]
        if len(kinds) != self.num_layers or set(kinds) - {"conv", "full_attention"}:
            raise ValueError(
                f"layer_types must name {self.num_layers} layers as conv or "
                f"full_attention, got {list(c.layer_types)}")
        self.conv_layer_indices = [i for i, k in enumerate(kinds) if k == "conv"]
        self.attn_layer_indices = [i for i, k in enumerate(kinds) if k != "conv"]
        if not self.attn_layer_indices or not self.conv_layer_indices:
            raise ValueError(
                "the hybrid cache wants both kinds of layer: pages for "
                "attention and slots for the convolution")
        self.num_attn_layers = len(self.attn_layer_indices)
        self.num_linear_attn_layers = len(self.conv_layer_indices)

        # The convolution: one stream of ``hidden_size`` channels.
        self.conv_kernel = c.conv_L_cache
        D = self.hidden_size
        self.conv_lanes = 128 if D % 128 == 0 else D

        # Attention geometry.
        self.num_heads = c.num_attention_heads
        self.num_kv_heads = getattr(c, "num_key_value_heads", self.num_heads)
        self.head_dim = getattr(c, "head_dim", None) or D // self.num_heads
        self.scale = self.head_dim ** -0.5
        self.rope = RotaryEmbedding(
            head_dim=self.head_dim,
            max_position=getattr(c, "max_position_embeddings", 8192),
            theta=getattr(c, "rope_theta", 1000000.0),
            rope_scaling=getattr(c, "rope_scaling", None),
        )

        # Feed-forward: dense in the first layers, every expert held after.
        self.intermediate_size = c.intermediate_size
        self.first_dense = getattr(c, "num_dense_layers", 0)
        self.num_experts = c.num_experts
        self.top_k = c.num_experts_per_tok
        self.moe_intermediate = c.moe_intermediate_size
        self.renormalize = bool(getattr(c, "norm_topk_prob", True))
        self.routed_scaling = float(getattr(c, "routed_scaling_factor", 1.0))
        self.expert_bias = bool(getattr(c, "use_expert_bias", True))
        self.moe_layer_indices = list(range(self.first_dense, self.num_layers))
        self.num_moe_layers = len(self.moe_layer_indices)

        self.kinds = [
            ("conv" if k == "conv" else "attn",
             "dense" if i < self.first_dense else "moe")
            for i, k in enumerate(kinds)
        ]
        self.head_layers, self.period, self.reps, self.tail_layers = (
            layer_plan(self.kinds))

    # ------------------------------------------------------------------
    # Layout
    # ------------------------------------------------------------------

    def _leaf_shapes(self, kind: str) -> dict[str, tuple[tuple, int]]:
        """Leaf -> (shape, fan_in; 0 = not drawn from a plain normal)."""
        D = self.hidden_size
        A, KH, dh = self.num_heads, self.num_kv_heads, self.head_dim
        if kind == "norms":
            return {"op_norm": ((D,), 0), "ffn_norm": ((D,), 0)}
        if kind == "conv":
            return {"w_in": ((D, 3 * D), D),
                    "conv_w": ((self.conv_kernel, D), self.conv_kernel),
                    "w_out": ((D, D), D)}
        if kind == "attn":
            return {"wq": ((D, A * dh), D), "wk": ((D, KH * dh), D),
                    "wv": ((D, KH * dh), D), "q_norm": ((dh,), 0),
                    "k_norm": ((dh,), 0), "wo": ((A * dh, D), A * dh)}
        if kind == "dense":
            F = self.intermediate_size
            return {"w1": ((D, F), D), "w3": ((D, F), D), "w2": ((F, D), F)}
        if kind == "moe":
            E = self.num_experts
            return {"router": ((D, E), D),
                    **({"router_bias": ((E,), 0)} if self.expert_bias else {})}
        if kind == "experts":
            E, F = self.num_experts, self.moe_intermediate
            return {"we_gate": ((E, D, F), D), "we_up": ((E, D, F), D),
                    "we_down": ((E, F, D), F)}
        raise KeyError(kind)

    def layers_of(self, kind: str) -> list[int]:
        """The layers that hold leaves of ``kind``, in order: a layer's
        row in the kind's stacks is its place in this list."""
        if kind == "norms":
            return list(range(self.num_layers))
        col = 0 if kind in ("conv", "attn") else 1
        want = "moe" if kind == "experts" else kind
        return [i for i, k in enumerate(self.kinds) if k[col] == want]

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
            if name == "q_norm":
                return jnp.full(shape, Q_NORM_DRAW, dtype)
            if name.endswith("norm"):
                return jnp.ones(shape, dtype)
            key = jax.random.fold_in(
                jax.random.fold_in(rng, LEAF_IDS[name]), layer)
            if name == "router_bias":
                return ROUTER_BIAS_DRAW[0] + normal(
                key, shape, ROUTER_BIAS_DRAW[1], jnp.float32)
            return normal(key, shape, 1.0 / math.sqrt(fan_in), dtype)

        # A stack is drawn a layer at a time into its place: the float32
        # draw of one layer is live beside the stacks, not a second stack.
        @partial(jax.jit, donate_argnums=(0,))
        def put(stack, w, i):
            return jax.lax.dynamic_update_index_in_dim(stack, w, i, 0)

        D = self.hidden_size
        params: dict = {
            "embed": draw("embed", 0, (self.vocab_size, D), D),
            "final_norm": jnp.ones((D,), dtype),
        }
        for kind in ("norms", "conv", "attn", "dense", "moe", "experts"):
            layers = self.layers_of(kind)
            if not layers:
                continue
            params[kind] = {}
            for name, (shape, fan_in) in self._leaf_shapes(kind).items():
                first = draw(name, layers[0], shape, fan_in)
                stack = jnp.zeros((len(layers),) + shape, first.dtype)
                for row, layer in enumerate(layers):
                    stack = put(stack, first if row == 0 else
                                draw(name, layer, shape, fan_in), row)
                params[kind][name] = stack
        return params

    def hf_weight_map(self) -> dict:
        m = {
            "model.embed_tokens.weight": ("embed", False),
            "model.embedding_norm.weight": ("final_norm", False),
        }
        by_kind = {
            "norms": {"operator_norm.weight": ("op_norm", False),
                      "ffn_norm.weight": ("ffn_norm", False)},
            "conv": {"conv.in_proj.weight": ("w_in", True),
                     "conv.conv.weight": ("conv_w", False),
                     "conv.out_proj.weight": ("w_out", True)},
            "attn": {"self_attn.q_proj.weight": ("wq", True),
                     "self_attn.k_proj.weight": ("wk", True),
                     "self_attn.v_proj.weight": ("wv", True),
                     "self_attn.out_proj.weight": ("wo", True),
                     "self_attn.q_layernorm.weight": ("q_norm", False),
                     "self_attn.k_layernorm.weight": ("k_norm", False)},
            "dense": {f"feed_forward.{w}.weight": (w, True)
                      for w in ("w1", "w2", "w3")},
            "moe": {"feed_forward.gate.weight": ("router", True),
                    **({"feed_forward.expert_bias": ("router_bias", False)}
                       if self.expert_bias else {})},
        }
        for kind, names in by_kind.items():
            for row, layer in enumerate(self.layers_of(kind)):
                for name, (ours, tr) in names.items():
                    m[f"model.layers.{layer}.{name}"] = (
                        f"{kind}.{ours}.{row}", tr)
        for row, layer in enumerate(self.layers_of("experts")):
            for j in range(self.num_experts):
                e = f"model.layers.{layer}.feed_forward.experts.{j}"
                for name, ours in (("w1", "we_gate"), ("w3", "we_up"),
                                   ("w2", "we_down")):
                    m[f"{e}.{name}.weight"] = (f"experts.{ours}.{row}.{j}", True)
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

    def _conv_mixer(self, h, lp, conv, row, md, tok_req, qsl, fresh):
        """``(out [T, D], conv stack)`` of one convolution layer; ``row``
        its row in the slot stack."""
        t, D = h.shape[0], self.hidden_size
        with jax.named_scope("short_conv"):
            p = h @ lp["w_in"]  # [T, 3D]: B, C, then the convolution's input
            gated = p[:, :D] * p[:, 2 * D:]
            y, conv = kda_short_conv(
                (gated,), conv, (lp["conv_w"],), row, md.state_slots, fresh,
                tok_req, qsl, md.num_seqs[0], decode_only=md.decode_only)
            y = (p[:, D: 2 * D].astype(jnp.float32) * y.reshape(t, D))
            return y.astype(self.dtype) @ lp["w_out"], conv

    def _attention(self, h, lp, paged, row, md, cos, sin):
        t = h.shape[0]
        A, KH, dh = self.num_heads, self.num_kv_heads, self.head_dim
        with jax.named_scope("qkv_proj"):
            q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]
        # As models/llama.py: the projections end here, so that each reads
        # its stack in place (the norms a head fused into them made XLA copy
        # the three matrices out of their stacks every iteration).
        q, k, v = jax.lax.optimization_barrier((q, k, v))
        q = rms_norm(q.reshape(t, A, dh), lp["q_norm"], self.rms_eps)
        k = rms_norm(k.reshape(t, KH, dh), lp["k_norm"], self.rms_eps)
        v = v.reshape(t, KH, dh)
        q = _apply_rotate_half(q, cos, sin, dh)
        k = _apply_rotate_half(k, cos, sin, dh)
        kv_scale = kv_dequant_scale(paged)
        with jax.named_scope("attn"):
            paged = write_kv(paged, row, k, v, md.slot_mapping)
            attn = paged_attention(
                q, paged, row, md, self.scale, sliding_window=None,
                k_scale=kv_scale, v_scale=kv_scale)
        with jax.named_scope("o_proj"):
            return attn.reshape(t, A * dh) @ lp["wo"], paged

    def _moe(self, h2, lp, experts, row, live):
        """The routed sum over every expert (all held here) and the live
        tokens routed to each ``[E]``."""
        return share_of_sparse_layer(
            h2, lp, experts, row, live,
            select=dict(
                top_k=self.top_k, sigmoid=True, renormalize=self.renormalize,
                scaling=self.routed_scaling, renorm_guard=RENORM_GUARD,
            ),
            num_experts=self.num_experts, first_expert=None, shared=False,
        )

    def apply(
        self,
        params: dict,
        kv_cache: dict,  # {"paged", "conv"}
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
        cos = self.rope.cos[md.positions][:, None, :]
        sin = self.rope.sin[md.positions][:, None, :]
        experts = params.get("experts")

        def layer(x, cache, mixer, ffn, rows):
            """One layer; ``rows[kind]`` its row in the kind's stacks, an
            int in line and a traced index inside the scan. Each leaf is
            sliced out of its whole stack here, for its one consumer."""
            pick = lambda kind: jax.tree.map(
                lambda leaf: leaf[rows[kind]], params[kind])
            norms = pick("norms")
            h = rms_norm(x, norms["op_norm"], self.rms_eps)
            if mixer == "conv":
                out, conv = self._conv_mixer(
                    h, pick("conv"), cache["conv"],
                    jnp.asarray(rows["conv"], jnp.int32), md, tok_req, qsl,
                    fresh)
                cache = {**cache, "conv": conv}
            else:
                out, paged = self._attention(
                    h, pick("attn"), cache["paged"],
                    jnp.asarray(rows["attn"], jnp.int32), md, cos, sin)
                cache = {**cache, "paged": paged}
            x = x + out
            h2 = rms_norm(x, norms["ffn_norm"], self.rms_eps)
            if ffn == "dense":
                fp = pick("dense")
                with jax.named_scope("mlp"):
                    gate_up = jnp.concatenate([h2 @ fp["w1"], h2 @ fp["w3"]], -1)
                    return x + silu_and_mul(gate_up) @ fp["w2"], cache, None
            out, counts = self._moe(h2, pick("moe"), experts, rows["moe"], live)
            return x + out, cache, counts

        before = lambda kind, i: sum(1 for j in self.layers_of(kind) if j < i)
        counts_of: dict[int, jnp.ndarray] = {}

        def single(x, cache, i):
            mixer, ffn = self.kinds[i]
            rows = {"norms": i, mixer: before(mixer, i), ffn: before(ffn, i)}
            x, cache, counts = layer(x, cache, mixer, ffn, rows)
            if counts is not None:
                counts_of[i] = counts
            return x, cache

        cache = kv_cache
        for i in self.head_layers:
            x, cache = single(x, cache, i)
        if self.reps:
            s, p = len(self.head_layers), self.period
            pattern = self.kinds[s: s + p]
            n_of = {kind: sum(kind in k for k in pattern)
                    for kind in ("conv", "attn", "dense", "moe")}
            base = {kind: before(kind, s) for kind in n_of}

            def period_fn(carry, rep):
                x, cache = carry
                seen = dict.fromkeys(n_of, 0)
                counts = []
                for off, (mixer, ffn) in enumerate(pattern):
                    rows = {"norms": s + rep * p + off}
                    for kind in (mixer, ffn):
                        rows[kind] = base[kind] + rep * n_of[kind] + seen[kind]
                        seen[kind] += 1
                    x, cache, c = layer(x, cache, mixer, ffn, rows)
                    if c is not None:
                        counts.append(c)
                return (x, cache), (jnp.stack(counts) if counts else None)

            (x, cache), per_period = jax.lax.scan(
                period_fn, (x, cache), jnp.arange(self.reps, dtype=jnp.int32))
            if per_period is not None:
                moe_offs = [o for o in range(p) if pattern[o][1] == "moe"]
                for rep in range(self.reps):
                    for j, o in enumerate(moe_offs):
                        counts_of[s + rep * p + o] = per_period[rep, j]
        for i in self.tail_layers:
            x, cache = single(x, cache, i)
        x = rms_norm(x, params["final_norm"], self.rms_eps)
        counts = (jnp.stack([counts_of[i] for i in sorted(counts_of)])
                  if counts_of else jnp.zeros((0, self.num_experts), jnp.int32))
        return x, cache, counts  # counts [moe layers, E]

    def compute_logits(self, params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
        with jax.named_scope("lm_head"):
            return (hidden @ params["embed"].T.astype(hidden.dtype)).astype(
                jnp.float32)

    # ------------------------------------------------------------------
    # Runner contracts
    # ------------------------------------------------------------------

    def get_kv_cache_spec(self, block_size: int, dtype_bytes: int) -> dict[str, KVCacheSpec]:
        """Pages for the attention layers alone (one KV group); the
        convolution layers' slots are budgeted by ``fixed_state_bytes``."""
        spec = FullAttentionSpec(
            block_size=block_size, num_kv_heads=self.num_kv_heads,
            head_size=self.head_dim, dtype_bytes=dtype_bytes,
        )
        return {f"layers.{i}": spec for i in self.attn_layer_indices}

    def state_bytes_per_slot(self) -> int:
        """One request's state over the convolution layers: the K - 1 last
        gated inputs of every channel, in the served dtype."""
        tail = (self.conv_kernel - 1) * self.hidden_size
        return (self.num_linear_attn_layers * tail
                * jnp.dtype(self.dtype).itemsize)

    def fixed_state_bytes(self, max_slots: int) -> int:
        return (max_slots + 1) * self.state_bytes_per_slot()

    def alloc_kv_cache(self, num_blocks: int, block_size: int, dtype) -> dict:
        s = self.max_state_slots + 1  # last slot = padding scratch
        return {
            "paged": jnp.zeros(
                kv_cache_shape(self.num_attn_layers, num_blocks, block_size,
                               self.num_kv_heads, self.head_dim), dtype),
            # A slot is K-1 tokens of the channels as whole lane tiles, so
            # one DMA moves it (ops/kda.py).
            "conv": jnp.zeros(
                (self.num_linear_attn_layers, s, self.conv_kernel - 1,
                 self.hidden_size // self.conv_lanes, self.conv_lanes),
                self.dtype),
        }

    def param_shardings(self, data_axis: str | None = None,
                        model_axis: str = "tp") -> dict:
        """Replicated: this family is served a chip a replica; no
        tensor-parallel plan yet."""
        shapes = jax.eval_shape(
            lambda: self.init_dummy_params(jax.random.PRNGKey(0))
        )
        return jax.tree.map(lambda leaf: P(*([None] * leaf.ndim)), shapes)

    def kv_cache_sharding(self, model_axis: str = "tp") -> dict:
        return {
            "paged": P(None, None, None, None, None),
            "conv": P(None, None, None, None, None),
        }
