"""Laguna (poolside): window and full attention layers of UNLIKE query-head
counts over one KV-head count, a gate a head on the attention output,
sparse experts with a shared one.

No public implementation of ``model_type`` ``laguna`` is on this machine:
the equations below are written from the published ``config.json`` (the
catalog's rows ``Laguna-XS.2`` and ``Laguna-S-2.1``), and what its keys do
not say follows the family's conventions and is listed under ``assumed``
in ``benchmark/configs/laguna-xs.2.json``.

Pre-norm residual decoder, RMSNorm, no bias. Layer ``l``:

- attention: ``n_l = num_attention_heads_per_layer[l]`` query heads (48 in
  a full layer, 64 in a window layer of XS.2) over ``num_key_value_heads``
  KV heads of ``head_dim`` in every layer. A full layer rotates the first
  ``partial_rotary_factor * head_dim`` lanes with YaRN, a window layer all
  of them with plain rope (``rope_parameters``, two tables); a window
  layer's query ``i`` sees key ``j`` only where ``i - j < sliding_window``.
  ``g = sigmoid(h Wg)``, one scalar a head and token from the normed input,
  multiplies the head's attention output before ``Wo``.
- layer 0 a dense SwiGLU; every other layer ``num_experts`` routed experts
  (sigmoid scores in float32, top ``num_experts_per_tok``, renormalised,
  times ``moe_routed_scaling_factor``) plus one shared expert.

Cache contract: KV GROUPS over one page pool (``core/kv_cache_utils.
group_kv_cache_specs``). The two kinds of layer share one page shape a
layer (the KV heads are the same), so the full layers are one group and
the window layers ``k`` groups of as many layers each (``k`` window layers
to a full one): a page id holds 16 tokens of ONE group's layers, the cache
is ONE array ``[layers of a group, pages, 16, 2 * KH, D]`` indexed by a
layer's place in its group, and ``md.block_tables [G, R, B]`` /
``md.slot_mapping [G, T]`` carry a table and a slot mapping a group. The
window groups' pages go back to the pool as a request passes them.

Expert share: ``config.json`` may carry ``ep_size`` / ``ep_rank`` as
``models/kimi_linear.py`` reads them (``layers/moe.py::
share_of_sparse_layer``).

Layers run as: layer 0 alone, then ``lax.scan`` over whole periods of
(window x k, full), then the window layers that are left. Weights are
stacked BY KIND, because the kinds differ in shape, and each layer slices
its own leaves out of the whole stacks (one consumer a slice: PERF.md,
PR 40). Param tree::

    embed [V, D], final_norm [D], lm_head [D, V]
    first/    norms/ attn/ dense/            layer 0, leaves unstacked
    periods/  norms/ input_norm post_norm    [P, k + 1, D]
              window/ wq [P, k, D, Hw*Dh] wk wv [P, k, D, KH*Dh]
                      wg [P, k, D, Hw] wo [P, k, Hw*Dh, D]
              full/   wq [P, D, Hf*Dh] wk wv wg [P, D, Hf] wo
              moe/    router [P, k + 1, D, E_all] ws_gate ws_up ws_down
    tail/     norms/ [n, D]  window/ [n, ...]  moe/ [n, ...]
    experts/  we_gate we_up [L - 1, E, D, F]  we_down [L - 1, E, F, D]
              (row = layer - 1; kept whole beside the scan)
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
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
    write_kv,
)

logger = init_logger(__name__)

# A leaf's place in the dummy draw's key: fold_in(fold_in(rng, id), layer).
LEAF_IDS = {
    name: i for i, name in enumerate((
        "embed", "lm_head", "wq", "wk", "wv", "wg", "wo",
        "wgate", "wup", "wdown",
        "router", "ws_gate", "ws_up", "ws_down",
        "we_gate", "we_up", "we_down",
    ))
}

# Widths of the dummy draw that are not 1 / sqrt(fan_in) (times that), as
# models/kimi_linear.py has them and for its reasons. With unit-variance
# scores a softmax over a thousand keys is nearly flat: a layer returns the
# mean of its values, and a window of 512 reads nearly as the whole context;
# the query is drawn wider so that some tens of keys carry a row. Not
# wider than 2: at 4 (Kimi-Linear's, in its 3 latent layers) one key
# carries a row in all 40 layers here, bf16's rounding flips which, and the
# served logprobs stood 1.1 from the float32 reference's on the chip
# (PERF.md, PR 43). The routed experts' way out is drawn narrower so that
# one expert chosen differently in bf16 does not move every later layer's
# choices.
DRAW_WIDTHS = {"wq": 2.0, "we_down": 0.25}

FULL, WINDOW = "full_attention", "sliding_attention"


class LagunaForCausalLM:
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
        self.num_layers = c.num_hidden_layers
        self.hidden_size = c.hidden_size
        self.vocab_size = c.vocab_size
        self.rms_eps = getattr(c, "rms_norm_eps", 1e-6)
        self.tie_embeddings = getattr(c, "tie_word_embeddings", False)
        self.head_dim = c.head_dim
        self.num_kv_heads = c.num_key_value_heads
        self.scale = self.head_dim ** -0.5
        self.attn_window = int(c.sliding_window)
        self.intermediate_size = c.intermediate_size

        # The layers' plan: [full] + P x ([window] * k + [full]) + [window] * n.
        kinds = list(c.layer_types)
        heads = list(getattr(c, "num_attention_heads_per_layer", None)
                     or [c.num_attention_heads] * self.num_layers)
        ffn = list(getattr(c, "mlp_layer_types", None)
                   or ["dense"] + ["sparse"] * (self.num_layers - 1))
        fulls = [i for i, kind in enumerate(kinds) if kind == FULL]
        k = fulls[1] - 1 if len(fulls) > 1 else self.num_layers - 1
        periods = len(fulls) - 1
        tail = self.num_layers - 1 - periods * (k + 1)
        planned = [FULL] + ([WINDOW] * k + [FULL]) * periods + [WINDOW] * tail
        if (len(kinds) != self.num_layers or kinds != planned or k < 1
                or not 0 <= tail <= k
                or ffn != ["dense"] + ["sparse"] * (self.num_layers - 1)):
            raise NotImplementedError(
                "this family is served as a dense full-attention layer 0, "
                "then whole periods of k window layers and a full one, then "
                f"at most k window layers, all sparse; got layer_types "
                f"{kinds} and mlp_layer_types {ffn}"
            )
        self.kinds = kinds
        self.window_per_period, self.periods, self.tail = k, periods, tail
        by_kind = {kind: {heads[i] for i in range(self.num_layers)
                          if kinds[i] == kind} for kind in (FULL, WINDOW)}
        if any(len(v) != 1 for v in by_kind.values()):
            raise NotImplementedError(
                f"one query-head count a kind of layer; got {by_kind}")
        self.heads_of = {kind: v.pop() for kind, v in by_kind.items()}
        # The runner's count of the ragged kernel's pairs reads the full
        # layers' geometry under these names.
        self.num_heads = self.heads_of[FULL]
        self.num_window_attn_layers = kinds.count(WINDOW)

        # Two rope tables, built once: YaRN over the first
        # ``partial_rotary_factor * head_dim`` lanes in a full layer, plain
        # rope over every lane in a window layer. Under a worker they hold
        # the positions it serves (``layers/rotary.py::serving_positions``:
        # the published 262,144 would be 33 + 67 MB a table).
        self.rope_of = {
            kind: self._rope(dict(c.rope_parameters[kind]))
            for kind in (FULL, WINDOW)
        }

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
        self.top_k = c.num_experts_per_tok
        self.moe_intermediate = c.moe_intermediate_size
        self.shared_intermediate = getattr(
            c, "shared_expert_intermediate_size", 0) or 0
        self.routed_scaling = getattr(c, "moe_routed_scaling_factor", 1.0)
        self.renormalize = getattr(c, "norm_topk_prob", True)
        if getattr(c, "moe_apply_router_weight_on_input", False):
            raise NotImplementedError("router weight on the expert's input")
        self.num_moe_layers = self.num_layers - 1

        # Where a layer's K/V live: its KV group and its place in it.
        groups = group_kv_cache_specs(self.get_kv_cache_spec(16, 2))
        self.num_kv_groups = len(groups)
        self.kv_place = {
            int(name.split(".")[1]): (g, i)
            for g, group in enumerate(groups)
            for i, name in enumerate(group.layer_names)
        }
        self.kv_group_layers = len(groups[0].layer_names)

    def _rope(self, r: dict) -> RotaryEmbedding:
        rotary = int(self.head_dim * r.get("partial_rotary_factor", 1))
        scaling = None
        if r.get("rope_type", "default") == "yarn":
            # layers/rotary.py multiplies YaRN's own factor by a
            # configured one; the published attention_factor IS YaRN's
            # (0.1 ln(factor) + 1), so it is left out here.
            own = 0.1 * math.log(r["factor"]) + 1.0
            if abs(r.get("attention_factor", own) - own) > 1e-6:
                raise NotImplementedError(
                    f"attention_factor {r['attention_factor']} is not "
                    f"YaRN's own {own} for factor {r['factor']}")
            scaling = {key: r[key] for key in (
                "rope_type", "factor", "beta_fast", "beta_slow",
                "original_max_position_embeddings") if key in r}
        elif r.get("rope_type", "default") != "default":
            raise NotImplementedError(f"rope_type {r['rope_type']}")
        return RotaryEmbedding(
            self.head_dim, self.hf_config.max_position_embeddings,
            theta=r["rope_theta"],
            rope_scaling=scaling, rotary_dim=rotary,
        )

    # ------------------------------------------------------------------
    # Layout
    # ------------------------------------------------------------------

    def _leaf_shapes(self, kind: str) -> dict[str, tuple[tuple, int]]:
        """Leaf -> (shape, fan_in; 0 = not drawn from a normal)."""
        D, Dh = self.hidden_size, self.head_dim
        if kind in (FULL, WINDOW):
            H, KH = self.heads_of[kind], self.num_kv_heads
            return {
                "wq": ((D, H * Dh), D), "wk": ((D, KH * Dh), D),
                "wv": ((D, KH * Dh), D), "wg": ((D, H), D),
                "wo": ((H * Dh, D), H * Dh),
            }
        if kind == "dense":
            F = self.intermediate_size
            return {"wgate": ((D, F), D), "wup": ((D, F), D),
                    "wdown": ((F, D), F)}
        if kind == "moe":
            Fs = self.shared_intermediate
            out = {"router": ((D, self.router_width), D)}
            if Fs:
                out |= {"ws_gate": ((D, Fs), D), "ws_up": ((D, Fs), D),
                        "ws_down": ((Fs, D), Fs)}
            return out
        if kind == "norms":
            return {"input_norm": ((D,), 0), "post_norm": ((D,), 0)}
        if kind == "experts":
            E, F = self.num_experts, self.moe_intermediate
            return {"we_gate": ((E, D, F), D), "we_up": ((E, D, F), D),
                    "we_down": ((E, F, D), F)}
        raise KeyError(kind)

    def _place(self, layer: int, kind: str) -> tuple[str, tuple]:
        """Where layer ``layer``'s leaves of ``kind`` (``"norms"``, ``"attn"``
        or ``"moe"``) live: the dotted path of their sub-tree, and a leaf's
        indices in its stack there."""
        k = self.window_per_period
        if layer == 0:
            return f"first.{kind}", ()
        rep, off = divmod(layer - 1, k + 1)
        where = "periods" if rep < self.periods else "tail"
        lead = (rep,) if where == "periods" else ()
        if kind == "attn":
            if self.kinds[layer] == FULL:
                return f"{where}.full", lead
            return f"{where}.window", lead + (off,)
        return f"{where}.{kind}", lead + (off,)

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
            if not fan_in:
                return jnp.ones(shape, dtype)  # the norms' scales
            key = jax.random.fold_in(
                jax.random.fold_in(rng, LEAF_IDS[name]), layer)
            return normal(
                key, shape, DRAW_WIDTHS.get(name, 1.0) / math.sqrt(fan_in),
                dtype)

        def leaves(layer: int, kind: str) -> dict:
            return {name: draw(name, layer, shape, fan_in)
                    for name, (shape, fan_in) in self._leaf_shapes(kind).items()}

        def stacked(rows: list) -> dict:
            """Leaves of layers given as nested lists, stacked likewise."""
            if isinstance(rows, dict):
                return rows
            below = [stacked(row) for row in rows]
            return {name: jnp.stack([b[name] for b in below])
                    for name in below[0]}

        # An expert stack is drawn a layer at a time into its place: the
        # float32 draw of one layer is live, not the stack's.
        @partial(jax.jit, donate_argnums=(0,))
        def put(stack, w, i):
            return jax.lax.dynamic_update_index_in_dim(stack, w, i, 0)

        D, k = self.hidden_size, self.window_per_period
        params: dict = {
            "embed": draw("embed", 0, (self.vocab_size, D), D),
            "final_norm": jnp.ones((D,), dtype),
            "first": {"norms": leaves(0, "norms"), "attn": leaves(0, FULL),
                      "dense": leaves(0, "dense")},
        }
        if not self.tie_embeddings:
            params["lm_head"] = draw("lm_head", 0, (D, self.vocab_size), D)
        first_of = lambda rep: 1 + rep * (k + 1)
        if self.periods:
            reps = range(self.periods)
            params["periods"] = {
                "norms": stacked([[leaves(first_of(r) + o, "norms")
                                   for o in range(k + 1)] for r in reps]),
                "window": stacked([[leaves(first_of(r) + o, WINDOW)
                                    for o in range(k)] for r in reps]),
                "full": stacked([leaves(first_of(r) + k, FULL) for r in reps]),
                "moe": stacked([[leaves(first_of(r) + o, "moe")
                                 for o in range(k + 1)] for r in reps]),
            }
        if self.tail:
            start = first_of(self.periods)
            params["tail"] = {
                kind: stacked([leaves(start + o, name)
                               for o in range(self.tail)])
                for kind, name in (("norms", "norms"), ("window", WINDOW),
                                   ("moe", "moe"))
            }
        experts = {}
        for name, (shape, fan_in) in self._leaf_shapes("experts").items():
            stack = jnp.zeros((self.num_moe_layers,) + shape, dtype)
            for layer in range(1, self.num_layers):
                stack = put(stack, draw(name, layer, shape, fan_in), layer - 1)
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
            where, idx = self._place(layer, kind)
            return ".".join([where, name, *map(str, idx)])

        attn = {"q_proj": "wq", "k_proj": "wk", "v_proj": "wv",
                "g_proj": "wg", "o_proj": "wo"}
        glu = (("gate_proj", "gate"), ("up_proj", "up"), ("down_proj", "down"))
        first = self.first_expert or 0
        for i in range(self.num_layers):
            hf = f"model.layers.{i}"
            m[f"{hf}.input_layernorm.weight"] = (
                path(i, "norms", "input_norm"), False)
            m[f"{hf}.post_attention_layernorm.weight"] = (
                path(i, "norms", "post_norm"), False)
            for name, ours in attn.items():
                m[f"{hf}.self_attn.{name}.weight"] = (path(i, "attn", ours), True)
            if i == 0:
                for name, ours in glu:
                    m[f"{hf}.mlp.{name}.weight"] = (f"first.dense.w{ours}", True)
                continue
            m[f"{hf}.mlp.gate.weight"] = (path(i, "moe", "router"), True)
            for name, ours in glu if self.shared_intermediate else ():
                m[f"{hf}.mlp.shared_expert.{name}.weight"] = (
                    path(i, "moe", f"ws_{ours}"), True)
            for j in range(self.num_experts):
                for name, ours in glu:
                    m[f"{hf}.mlp.experts.{first + j}.{name}.weight"] = (
                        f"experts.we_{ours}.{i - 1}.{j}", True)
        return m

    def load_params(self, path: str, dtype=None, shardings=None) -> dict:
        from vllm_tpu.models.loader import load_params_from

        return load_params_from(self, path, dtype or self.dtype, shardings)

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------

    def _attention(self, h, ap, kv, kind, group, place, md):
        """``(out [T, D], kv)`` of one attention layer: ``group`` its KV
        group (static), ``place`` its layer index in the cache."""
        t = h.shape[0]
        H, KH, Dh = self.heads_of[kind], self.num_kv_heads, self.head_dim
        window = kind == WINDOW
        with jax.named_scope("attn_window" if window else "attn_full"):
            # The projections end at a barrier as [T, heads * head_dim]
            # rows, as models/llama.py's: left free, XLA folds the head
            # split into the dots and slices AND transposes the weights out
            # of their stacks into temporaries a layer (PERF.md, PR 27).
            q, k, v = jax.lax.optimization_barrier(
                (h @ ap["wq"], h @ ap["wk"], h @ ap["wv"]))
            q = q.reshape(t, H, Dh)
            k = k.reshape(t, KH, Dh)
            v = v.reshape(t, KH, Dh)
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
            )
        with jax.named_scope("gate"):
            gate = jax.nn.sigmoid((h @ ap["wg"]).astype(jnp.float32))
            out = (out.astype(jnp.float32) * gate[..., None]).astype(self.dtype)
        return out.reshape(t, H * Dh) @ ap["wo"], kv

    def _sparse(self, h2, fp, experts, row, live):
        with jax.named_scope("moe"):
            return share_of_sparse_layer(
                h2, fp, experts, row, live,
                select=dict(
                    top_k=self.top_k, sigmoid=True, method="noaux_tc",
                    renormalize=self.renormalize, scaling=self.routed_scaling,
                ),
                num_experts=self.num_experts, first_expert=self.first_expert,
                shared=bool(self.shared_intermediate),
            )

    def apply(
        self,
        params: dict,
        kv_cache: jnp.ndarray,  # [layers of a group, pages, BS, 2 * KH, Dh]
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
        experts = params["experts"]
        k = self.window_per_period

        def layer(x, kv, kind, norms, ap, fp, group, place, row):
            h = rms_norm(x, norms["input_norm"], self.rms_eps)
            out, kv = self._attention(h, ap, kv, kind, group, place, md)
            x = x + out
            h2 = rms_norm(x, norms["post_norm"], self.rms_eps)
            if row is None:
                with jax.named_scope("mlp"):
                    gate_up = jnp.concatenate(
                        [h2 @ fp["wgate"], h2 @ fp["wup"]], -1)
                    return x + silu_and_mul(gate_up) @ fp["wdown"], kv, None
            out, counts = self._sparse(h2, fp, experts, row, live)
            return x + out, kv, counts

        first = params["first"]
        x, kv, _ = layer(
            x, kv_cache, FULL, first["norms"], first["attn"], first["dense"],
            *self.kv_place[0], None)
        counts = []
        if self.periods:
            stacks = params["periods"]

            def period_fn(carry, rep):
                x, kv = carry
                # One layer's leaves out of the whole stacks (the module
                # docstring says why not a slab of the period's).
                pick = lambda kind, *at: jax.tree.map(
                    lambda leaf: leaf[(rep, *at)], stacks[kind])
                out = []
                for off in range(k + 1):
                    window = off < k
                    # Period ``rep``'s window layers are place ``rep`` of
                    # their groups, its full layer place ``rep + 1`` of the
                    # full group (layer 0 is its place 0).
                    x, kv, c = layer(
                        x, kv, WINDOW if window else FULL, pick("norms", off),
                        pick("window", off) if window else pick("full"),
                        pick("moe", off),
                        self.kv_place[1 + off][0] if window else 0,
                        rep if window else rep + 1,
                        rep * (k + 1) + off,
                    )
                    out.append(c)
                return (x, kv), jnp.stack(out)

            (x, kv), per_period = jax.lax.scan(
                period_fn, (x, kv),
                jnp.arange(self.periods, dtype=jnp.int32),
            )
            counts.append(per_period.reshape(-1, self.num_experts))
        if self.tail:
            tail = params["tail"]
            start = 1 + self.periods * (k + 1)
            for off in range(self.tail):
                pick = lambda kind: jax.tree.map(
                    lambda leaf: leaf[off], tail[kind])
                x, kv, c = layer(
                    x, kv, WINDOW, pick("norms"), pick("window"), pick("moe"),
                    *self.kv_place[start + off], jnp.int32(start + off - 1))
                counts.append(c[None])
        x = rms_norm(x, params["final_norm"], self.rms_eps)
        return x, kv, jnp.concatenate(counts)  # counts [moe layers, E held]

    def compute_logits(self, params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
        head = params["embed"].T if self.tie_embeddings else params["lm_head"]
        with jax.named_scope("lm_head"):
            return (hidden @ head.astype(hidden.dtype)).astype(jnp.float32)

    # ------------------------------------------------------------------
    # Runner contracts
    # ------------------------------------------------------------------

    def get_kv_cache_spec(self, block_size: int, dtype_bytes: int) -> dict[str, KVCacheSpec]:
        common = dict(block_size=block_size, num_kv_heads=self.num_kv_heads,
                      head_size=self.head_dim, dtype_bytes=dtype_bytes)
        return {
            f"layers.{i}": FullAttentionSpec(**common) if kind == FULL
            else SlidingWindowSpec(**common, sliding_window=self.attn_window)
            for i, kind in enumerate(self.kinds)
        }

    def param_shardings(self, data_axis: str | None = None,
                        model_axis: str = "tp") -> dict:
        """Replicated: this family is served a chip a replica (its experts
        by share); no tensor-parallel plan yet."""
        shapes = jax.eval_shape(
            lambda: self.init_dummy_params(jax.random.PRNGKey(0))
        )
        return jax.tree.map(lambda leaf: P(*([None] * leaf.ndim)), shapes)

    def kv_cache_sharding(self, model_axis: str = "tp") -> P:
        return P(None, None, None, None, None)
