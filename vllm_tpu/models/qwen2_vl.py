"""Qwen2-VL: ViT vision tower + Qwen2 decoder with multimodal 3D rope.

Reference analog: ``vllm/model_executor/models/qwen2_vl.py``. The second
VLM family next to Llava, adding the two things Llava doesn't exercise:
a NON-CLIP vision tower (2D-rotary ViT with a 2x2 spatial patch merger)
and M-ROPE — the decoder's rotary frequencies are split into
(temporal, height, width) sections, each driven by its own position
stream; text tokens keep all three equal, image tokens spread over the
(constant t, row, column) grid, and positions after an image resume at
``max(prev) + 1`` (``get_rope_index`` semantics, replicated on the host
in :func:`mrope_positions`).

v1 scope: fixed image geometry (every image resized to one static
``image_size`` — dynamic-resolution grids are a bucket-explosion
tradeoff deferred like Llava's) and single images (no video).
"""

from __future__ import annotations

import math
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from vllm_tpu.layers.layernorm import rms_norm
from vllm_tpu.logger import init_logger
from vllm_tpu.models.llama import Qwen2ForCausalLM
from vllm_tpu.multimodal import MMInput
from vllm_tpu.ops.attention import AttentionMetadata

logger = init_logger(__name__)


def _layer_norm(x, w, b, eps=1e-6):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    return (
        (xf - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
        + b.astype(jnp.float32)
    ).astype(x.dtype)


def _rotate_half(x):
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], axis=-1)


def mrope_positions(
    prompt_len: int,
    spans: list[tuple],  # (offset, llm_h, llm_w) or (offset, t, lh, lw)
) -> tuple[np.ndarray, int]:
    """Host-side ``get_rope_index`` for one request.

    Returns ``(pos3 [3, prompt_len] i32, delta)``: image/video tokens get
    (t, row, col) positions over their POST-MERGE grid (images: one
    temporal index; videos: one per temporal group); text resumes at
    ``max(previous) + 1``; decode position ``p`` (0-based engine
    position) maps to ``p + delta`` on all three streams.
    """
    pos3 = np.zeros((3, prompt_len), np.int32)
    cursor = 0  # next position value for text
    idx = 0
    for span in sorted(spans):
        if len(span) == 3:
            off, tg, lh, lw, t_step = span[0], 1, span[1], span[2], 1
        elif len(span) == 4:
            (off, tg, lh, lw), t_step = span, 1
        else:
            off, tg, lh, lw, t_step = span
        # Text run before the image/video.
        n_text = off - idx
        for j in range(n_text):
            pos3[:, idx + j] = cursor + j
        cursor += n_text
        idx = off
        # Grid: t per temporal group (scaled by t_step = tokens_per_second
        # x second_per_grid, the Qwen2.5-VL interval; 1 for images and the
        # 2-VL family), h rows, w cols (tiled per group).
        n_spatial = lh * lw
        n_tok = tg * n_spatial
        t_pos = np.repeat(np.arange(tg) * t_step, n_spatial) + cursor
        h_pos = np.tile(np.repeat(np.arange(lh), lw), tg) + cursor
        w_pos = np.tile(np.tile(np.arange(lw), lh), tg) + cursor
        pos3[0, idx : idx + n_tok] = t_pos
        pos3[1, idx : idx + n_tok] = h_pos
        pos3[2, idx : idx + n_tok] = w_pos
        cursor += max((tg - 1) * t_step + 1, lh, lw)
        idx += n_tok
    for j in range(prompt_len - idx):
        pos3[:, idx + j] = cursor + j
    max_pos = int(pos3.max()) if prompt_len else -1
    delta = max_pos + 1 - prompt_len
    return pos3, delta


class Qwen2VLForConditionalGeneration:
    is_multimodal = True
    needs_mrope = True
    supports_lora = False
    enable_lora = False

    # Fixed input geometry (HF's dynamic resolution is deferred — every
    # image is resized square; parity tests feed the same size to HF).
    default_image_size = 224
    # Fixed video frame count (static tower shapes): clips are linearly
    # resampled to this many frames; temporal groups = frames / tps.
    default_video_frames = 8
    # Temporal m-rope interval per group (Qwen2.5-VL scales by
    # tokens_per_second; the 2-VL family steps by 1).
    video_t_step = 1

    def __init__(self, hf_config: Any, dtype=jnp.bfloat16,
                 quantization: str | None = None) -> None:
        if quantization:
            logger.warning(
                "weight quantization is not yet supported for multimodal "
                "models; running %s unquantized", type(self).__name__,
            )
        self.hf_config = hf_config
        self.dtype = dtype
        self.quantization = None
        tc, vc = hf_config.text_config, hf_config.vision_config
        self.lang = Qwen2ForCausalLM(tc, dtype)

        # Runner contracts proxy the decoder.
        self.num_layers = self.lang.num_layers
        self.num_kv_heads = self.lang.num_kv_heads
        self.head_dim = self.lang.head_dim
        self.hidden_size = self.lang.hidden_size
        self.vocab_size = self.lang.vocab_size
        self.sliding_window = None

        # M-rope section map: frequency j is driven by position stream
        # section(j) (t/h/w), per rope_scaling.mrope_section.
        rs = getattr(tc, "rope_scaling", None) or {}
        sections = rs.get("mrope_section") or [self.head_dim // 6] * 3
        assert sum(sections) == self.head_dim // 2, (sections, self.head_dim)
        smap = np.concatenate([
            np.full(n, i % 3, np.int32) for i, n in enumerate(sections)
        ])
        self._mrope_section_map = jnp.asarray(smap)  # [Dh/2]
        theta = getattr(tc, "rope_theta", 1e6)
        self._inv_freq = jnp.asarray(
            1.0 / theta ** (
                np.arange(0, self.head_dim, 2, np.float64) / self.head_dim
            ),
            jnp.float32,
        )

        # Vision geometry (static).
        self.vision_dim = vc.embed_dim if hasattr(vc, "embed_dim") else vc.hidden_size
        self.vision_depth = vc.depth
        self.vision_heads = vc.num_heads
        self.vision_head_dim = self.vision_dim // vc.num_heads
        self.vision_mlp = (
            int(vc.intermediate_size)
            if getattr(vc, "intermediate_size", None)
            else int(self.vision_dim * vc.mlp_ratio)
        )
        self.vision_act = getattr(vc, "hidden_act", "quick_gelu")
        self.patch_size = vc.patch_size
        self.temporal_patch_size = getattr(vc, "temporal_patch_size", 2)
        self.merge = getattr(vc, "spatial_merge_size", 2)
        self.in_channels = getattr(vc, "in_channels", 3)
        self.image_size = self.default_image_size
        grid = self.image_size // self.patch_size
        assert grid % self.merge == 0
        self.grid = grid
        self.llm_grid = grid // self.merge
        self.num_patches = grid * grid
        self.tokens_per_image = self.llm_grid * self.llm_grid
        self.image_token_id = hf_config.image_token_id
        self._vision_rope = self._build_vision_rope()

    def _build_vision_rope(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Static [N, vision_head_dim] cos/sin for the fixed grid, with
        the merge-window-major patch order the HF processor emits."""
        g, m = self.grid, self.merge
        hpos = np.arange(g)[:, None].repeat(g, 1)
        wpos = np.arange(g)[None, :].repeat(g, 0)

        def merge_order(a):
            return a.reshape(g // m, m, g // m, m).transpose(0, 2, 1, 3).reshape(-1)

        hp, wp = merge_order(hpos), merge_order(wpos)
        dim = self.vision_head_dim // 2
        inv = 1.0 / 10000.0 ** (np.arange(0, dim, 2, np.float64) / dim)
        freqs_h = hp[:, None] * inv[None]  # [N, dim/2]
        freqs_w = wp[:, None] * inv[None]
        emb = np.concatenate([freqs_h, freqs_w], axis=1)  # [N, dim]
        emb = np.concatenate([emb, emb], axis=1)  # [N, 2*dim = head_dim]
        return (
            jnp.asarray(np.cos(emb), jnp.float32),
            jnp.asarray(np.sin(emb), jnp.float32),
        )

    # Input-processor contract.
    @classmethod
    def mm_info(cls, hf_config: Any) -> dict:
        vc = hf_config.vision_config
        merge = getattr(vc, "spatial_merge_size", 2)
        grid = cls.default_image_size // vc.patch_size
        tpi = (grid // merge) ** 2
        tps = getattr(vc, "temporal_patch_size", 2)
        t_groups = cls.default_video_frames // tps
        return {
            "image_token_id": hf_config.image_token_id,
            "tokens_per_image": tpi,
            "image_size": cls.default_image_size,
            "video_token_id": getattr(hf_config, "video_token_id", None),
            "tokens_per_video": t_groups * tpi,
            "video_frames": cls.default_video_frames,
        }

    # ------------------------------------------------------------------
    # Params
    # ------------------------------------------------------------------

    def init_dummy_params(self, rng: jax.Array, dtype=None) -> dict:
        dtype = dtype or self.dtype
        params = self.lang.init_dummy_params(jax.random.fold_in(rng, 1), dtype)
        Dv, Lv, F = self.vision_dim, self.vision_depth, self.vision_mlp
        patch_in = (
            self.in_channels * self.temporal_patch_size
            * self.patch_size * self.patch_size
        )
        Dt = self.hidden_size
        mh = Dv * self.merge * self.merge
        key = iter(jax.random.split(rng, 12))

        def init(shape, fan_in):
            return (
                jax.random.normal(next(key), shape, jnp.float32)
                / math.sqrt(fan_in)
            ).astype(dtype)

        params["vision"] = {
            "patch_w": init((patch_in, Dv), patch_in),
            "blocks": {
                "ln1_w": jnp.ones((Lv, Dv), dtype),
                "ln1_b": jnp.zeros((Lv, Dv), dtype),
                "qkv_w": init((Lv, Dv, 3 * Dv), Dv),
                "qkv_b": jnp.zeros((Lv, 3 * Dv), dtype),
                "proj_w": init((Lv, Dv, Dv), Dv),
                "proj_b": jnp.zeros((Lv, Dv), dtype),
                "ln2_w": jnp.ones((Lv, Dv), dtype),
                "ln2_b": jnp.zeros((Lv, Dv), dtype),
                "fc1_w": init((Lv, Dv, F), Dv),
                "fc1_b": jnp.zeros((Lv, F), dtype),
                "fc2_w": init((Lv, F, Dv), F),
                "fc2_b": jnp.zeros((Lv, Dv), dtype),
            },
            "merger_ln_w": jnp.ones((Dv,), dtype),
            "merger_ln_b": jnp.zeros((Dv,), dtype),
            "merger_fc1_w": init((mh, mh), mh),
            "merger_fc1_b": jnp.zeros((mh,), dtype),
            "merger_fc2_w": init((mh, Dt), mh),
            "merger_fc2_b": jnp.zeros((Dt,), dtype),
        }
        return params

    def hf_weight_map(self) -> dict:
        m = {}
        for hf_name, dest in self.lang.hf_weight_map().items():
            m[hf_name] = dest
            # Qwen2-VL nests the decoder under model.language_model in
            # newer transformers; the loader also tries legacy prefixes.
            if hf_name.startswith("model."):
                m["model.language_model." + hf_name[len("model."):]] = dest
        v = "model.visual"
        m[f"{v}.patch_embed.proj.weight"] = ("vision.patch_w", False)
        for i in range(self.vision_depth):
            b = f"{v}.blocks.{i}"
            d = f"vision.blocks"
            m[f"{b}.norm1.weight"] = (f"{d}.ln1_w.{i}", False)
            m[f"{b}.norm1.bias"] = (f"{d}.ln1_b.{i}", False)
            m[f"{b}.attn.qkv.weight"] = (f"{d}.qkv_w.{i}", True)
            m[f"{b}.attn.qkv.bias"] = (f"{d}.qkv_b.{i}", False)
            m[f"{b}.attn.proj.weight"] = (f"{d}.proj_w.{i}", True)
            m[f"{b}.attn.proj.bias"] = (f"{d}.proj_b.{i}", False)
            m[f"{b}.norm2.weight"] = (f"{d}.ln2_w.{i}", False)
            m[f"{b}.norm2.bias"] = (f"{d}.ln2_b.{i}", False)
            m[f"{b}.mlp.fc1.weight"] = (f"{d}.fc1_w.{i}", True)
            m[f"{b}.mlp.fc1.bias"] = (f"{d}.fc1_b.{i}", False)
            m[f"{b}.mlp.fc2.weight"] = (f"{d}.fc2_w.{i}", True)
            m[f"{b}.mlp.fc2.bias"] = (f"{d}.fc2_b.{i}", False)
        m[f"{v}.merger.ln_q.weight"] = ("vision.merger_ln_w", False)
        m[f"{v}.merger.ln_q.bias"] = ("vision.merger_ln_b", False)
        m[f"{v}.merger.mlp.0.weight"] = ("vision.merger_fc1_w", True)
        m[f"{v}.merger.mlp.0.bias"] = ("vision.merger_fc1_b", False)
        m[f"{v}.merger.mlp.2.weight"] = ("vision.merger_fc2_w", True)
        m[f"{v}.merger.mlp.2.bias"] = ("vision.merger_fc2_b", False)
        # Legacy checkpoints store the tower at top-level "visual.".
        for k in list(m):
            if k.startswith("model.visual."):
                m["visual." + k[len("model.visual."):]] = m[k]
        return m

    def postprocess_weight(self, leaf_path: str, arr):
        if leaf_path == "vision.patch_w":
            # Conv3d with kernel == stride is a linear over the flattened
            # patch: [E, C, Tp, P, P] -> [C*Tp*P*P, E].
            return arr.reshape(arr.shape[0], -1).T
        return arr

    def load_params(self, path: str, dtype=None, shardings: Any | None = None) -> dict:
        from vllm_tpu.models.loader import load_params_from

        return load_params_from(
            self, path, dtype or self.dtype, shardings
        )

    # ------------------------------------------------------------------
    # Vision tower (runs once per image via the runner's encoder hook)
    # ------------------------------------------------------------------

    def _patchify(self, images: jnp.ndarray) -> jnp.ndarray:
        """CHW images [B, C, S, S] -> HF patch layout [B, N, C*Tp*P*P]:
        merge-window-major patch order, per-patch vector (C, Tp, Ph, Pw)
        with the image duplicated across the temporal patch axis —
        exactly ``Qwen2VLImageProcessor``'s reshape."""
        b = images.shape[0]
        m, p, ghm = self.merge, self.patch_size, self.grid // self.merge
        x = images.reshape(b, self.in_channels, ghm, m, p, ghm, m, p)
        x = x.transpose(0, 2, 5, 3, 6, 1, 4, 7)  # B,ghm,gwm,m1,m2,C,P,P
        x = x[..., None, :, :]  # temporal axis after C
        x = jnp.broadcast_to(
            x, x.shape[:-3] + (self.temporal_patch_size,) + x.shape[-2:]
        )
        return x.reshape(b, self.num_patches, -1)

    def _patchify_video(self, frames: jnp.ndarray) -> jnp.ndarray:
        """[B, F, C, S, S] -> [B, Fg*N, C*Tp*P*P]: temporal-group-major,
        merge-window-major within each group, REAL consecutive-frame
        temporal patches (the image path duplicates its one frame)."""
        b, f = frames.shape[:2]
        tps = self.temporal_patch_size
        fg = f // tps
        m, p, ghm = self.merge, self.patch_size, self.grid // self.merge
        x = frames.reshape(
            b, fg, tps, self.in_channels, ghm, m, p, ghm, m, p
        )
        x = x.transpose(0, 1, 4, 7, 5, 8, 3, 2, 6, 9)
        return x.reshape(b, fg * self.num_patches, -1)

    def encode_videos(self, params: dict, frames: jnp.ndarray) -> jnp.ndarray:
        """[B, F, 3, S, S] -> merged features [B, tokens_per_video, Dt].
        The tower attends across the WHOLE clip (HF semantics); vision
        rope is spatial-only, tiled per temporal group."""
        fg = frames.shape[1] // self.temporal_patch_size
        patches = self._patchify_video(frames)
        cos, sin = self._vision_rope
        return self._tower(
            params, patches,
            jnp.tile(cos, (fg, 1)), jnp.tile(sin, (fg, 1)),
            n_groups=fg,
        )

    def encode_images(self, params: dict, images: jnp.ndarray) -> jnp.ndarray:
        """Preprocessed CHW images ``[B, C, S, S]`` -> merged features
        ``[B, tokens_per_image, Dt]``."""
        patches = self._patchify(images)
        assert patches.shape[1] == self.num_patches
        cos, sin = self._vision_rope
        return self._tower(params, patches, cos, sin, n_groups=1)

    def _tower(self, params: dict, patches: jnp.ndarray, cos, sin,
               n_groups: int) -> jnp.ndarray:
        """Shared ViT body over [B, n_groups*N, patch_dim] patches."""
        vp = params["vision"]
        b, n, _ = patches.shape
        x = patches.astype(self.dtype) @ vp["patch_w"]  # [B, N, Dv]
        hd = self.vision_head_dim
        H = self.vision_heads

        def block(x, lp):
            h = _layer_norm(x, lp["ln1_w"], lp["ln1_b"])
            qkv = h @ lp["qkv_w"] + lp["qkv_b"]  # [B, N, 3Dv]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, n, H, hd).astype(jnp.float32)
            k = k.reshape(b, n, H, hd).astype(jnp.float32)
            v = v.reshape(b, n, H, hd).astype(jnp.float32)
            q = q * cos[None, :, None, :] + _rotate_half(q) * sin[None, :, None, :]
            k = k * cos[None, :, None, :] + _rotate_half(k) * sin[None, :, None, :]
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
            probs = jax.nn.softmax(scores, axis=-1)
            attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
            attn = attn.reshape(b, n, self.vision_dim).astype(self.dtype)
            x = x + (attn @ lp["proj_w"] + lp["proj_b"])
            h2 = _layer_norm(x, lp["ln2_w"], lp["ln2_b"])
            f = h2 @ lp["fc1_w"] + lp["fc1_b"]
            ff = f.astype(jnp.float32)
            if self.vision_act == "quick_gelu":
                ff = ff * jax.nn.sigmoid(1.702 * ff)
            else:
                ff = jax.nn.gelu(ff, approximate=False)
            x = x + (ff.astype(self.dtype) @ lp["fc2_w"] + lp["fc2_b"])
            return x, None

        x, _ = jax.lax.scan(block, x, vp["blocks"])
        x = _layer_norm(x, vp["merger_ln_w"], vp["merger_ln_b"])
        mh = self.vision_dim * self.merge * self.merge
        x = x.reshape(b, n_groups * self.tokens_per_image, mh)
        x = x @ vp["merger_fc1_w"] + vp["merger_fc1_b"]
        x = jax.nn.gelu(x.astype(jnp.float32), approximate=False).astype(
            self.dtype
        )
        return x @ vp["merger_fc2_w"] + vp["merger_fc2_b"]  # [B, TPI, Dt]

    # ------------------------------------------------------------------
    # Decoder forward with m-rope
    # ------------------------------------------------------------------

    def _mrope_cos_sin(self, pos3: jnp.ndarray):
        """pos3 [3, T] -> (cos, sin) [T, Dh/2] in the shared stack's
        HALF-WIDTH rotate-half convention (frequency j covers halves
        x1[j]/x2[j]), each frequency driven by its section's stream."""
        sel = pos3[self._mrope_section_map]  # [Dh/2, T]
        freqs = sel.astype(jnp.float32).T * self._inv_freq[None]  # [T, Dh/2]
        return jnp.cos(freqs), jnp.sin(freqs)

    def apply(
        self,
        params: dict,
        kv_cache: jnp.ndarray,
        input_ids: jnp.ndarray,  # [T]
        md: AttentionMetadata,
        token_lora_slot: jnp.ndarray | None = None,
        inputs_embeds: jnp.ndarray | None = None,
        mm_embeds: jnp.ndarray | None = None,  # [T, Dt] overlay
        mm_mask: jnp.ndarray | None = None,  # [T] bool
        mrope_positions: jnp.ndarray | None = None,  # [3, T]
    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        from vllm_tpu.layers.quant import embedding_lookup

        x = embedding_lookup(params["embed"], input_ids, self.dtype)
        if mm_embeds is not None:
            x = jnp.where(mm_mask[:, None], mm_embeds.astype(self.dtype), x)
        if mrope_positions is None:
            # Text-only fallback: all three streams equal the 1D position.
            mrope_positions = jnp.broadcast_to(
                md.positions[None], (3,) + md.positions.shape
            )
        cos, sin = self._mrope_cos_sin(mrope_positions)

        # The stock Qwen2 layer stack with the m-rope cos/sin injected
        # as precomputed per-token tables.
        lang = self.lang
        layer_fn = lang._make_layer_fn(
            md, x.shape[0], rope_cos_sin=(cos, sin),
        )
        (x, new_kv), _ = jax.lax.scan(
            layer_fn,
            (x, kv_cache),
            (params["layers"], jnp.arange(lang.num_layers, dtype=jnp.int32)),
        )
        x = rms_norm(x, params["final_norm"], lang.rms_eps)
        return x, new_kv

    def compute_logits(self, params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
        return self.lang.compute_logits(params, hidden)

    # ------------------------------------------------------------------
    # Runner contracts (proxy the decoder)
    # ------------------------------------------------------------------

    def get_kv_cache_spec(self, block_size: int, dtype_bytes: int):
        return self.lang.get_kv_cache_spec(block_size, dtype_bytes)

    def param_shardings(self, data_axis: str | None = None, model_axis: str = "tp") -> dict:
        out = self.lang.param_shardings(data_axis, model_axis)
        # Vision tower replicated; structure from eval_shape (no arrays).
        shapes = jax.eval_shape(
            lambda: self.init_dummy_params(jax.random.PRNGKey(0))
        )
        out["vision"] = jax.tree_util.tree_map(
            lambda _: P(), shapes["vision"]
        )
        return out

    def kv_cache_sharding(self, model_axis: str = "tp"):
        return self.lang.kv_cache_sharding(model_axis)


