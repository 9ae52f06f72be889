"""Prompt -> EngineCoreRequest: tokenization + validation.

Reference analog: ``vllm/v1/engine/input_processor.py:234 process_inputs``.
"""

from __future__ import annotations

import time
from typing import Any, Union

from vllm_tpu.config import EngineConfig
from vllm_tpu.logger import init_logger
from vllm_tpu.request import EngineCoreRequest
from vllm_tpu.sampling_params import SamplingParams

logger = init_logger(__name__)

# A prompt is a string, a dict {"prompt_token_ids": [...]}, or a dict
# {"prompt": "..."} (reference: TextPrompt/TokensPrompt).
PromptType = Union[str, dict]


def get_tokenizer(model_config) -> Any:
    from vllm_tpu.utils.tekken import load_tekken_if_present

    tekken = load_tekken_if_present(model_config.tokenizer)
    if tekken is not None:
        # Mistral-family checkpoint shipping only tekken.json — the
        # self-contained reader (no mistral_common in the image).
        return tekken
    from transformers import AutoTokenizer

    return AutoTokenizer.from_pretrained(
        model_config.tokenizer,
        revision=model_config.revision,
        trust_remote_code=model_config.trust_remote_code,
    )


class InputProcessor:
    def __init__(self, config: EngineConfig, tokenizer: Any | None = None) -> None:
        self.config = config
        self._tokenizer = tokenizer
        self._tokenizer_loaded = tokenizer is not None
        self._mm_info_cache: dict | None = None
        self._encdec_info_cache: dict | None = None
        self._model_class_cache: Any = None

    def _model_class(self) -> Any:
        """Resolved model class (admission checks: encoder-only, pooler
        head availability)."""
        if self._model_class_cache is None:
            from vllm_tpu.models.registry import get_model_class
            from vllm_tpu.worker.worker import load_hf_config

            self._model_class_cache = get_model_class(
                load_hf_config(self.config.model_config)
            )
        return self._model_class_cache

    def _encdec_info(self) -> dict | None:
        """Encoder-decoder facts from the model class (None for decoder-
        only models)."""
        if self._encdec_info_cache is None:
            from vllm_tpu.models.registry import get_model_class
            from vllm_tpu.worker.worker import load_hf_config

            hf_config = load_hf_config(self.config.model_config)
            cls = get_model_class(hf_config)
            if getattr(cls, "is_encoder_decoder", False):
                self._encdec_info_cache = dict(
                    decoder_start_token_id=hf_config.decoder_start_token_id,
                    max_encoder_len=getattr(
                        hf_config, "max_position_embeddings", None
                    ) or hf_config.max_source_positions,
                    # Whisper-class: the prompt is DECODER-side; audio
                    # features arrive via multi_modal_data["audio"].
                    audio=getattr(cls, "audio_encoder_decoder", False),
                    num_mel_bins=getattr(hf_config, "num_mel_bins", None),
                )
            else:
                self._encdec_info_cache = {}
        return self._encdec_info_cache or None

    def _mm_info(self) -> dict:
        """Placeholder-expansion facts from the model class (weights are
        never loaded in the frontend)."""
        if self._mm_info_cache is None:
            from vllm_tpu.models.registry import get_model_class
            from vllm_tpu.worker.worker import load_hf_config

            hf_config = load_hf_config(self.config.model_config)
            cls = get_model_class(hf_config)
            if not getattr(cls, "is_multimodal", False):
                raise ValueError(
                    f"{cls.__name__} does not accept multi_modal_data"
                )
            self._mm_info_cache = cls.mm_info(hf_config)
        return self._mm_info_cache

    @property
    def tokenizer(self) -> Any | None:
        if not self._tokenizer_loaded:
            self._tokenizer_loaded = True
            try:
                self._tokenizer = get_tokenizer(self.config.model_config)
            except Exception as e:  # tokenizer-less checkpoints (tests)
                logger.warning(
                    "no usable tokenizer for %s (%s: %s); only token-id "
                    "prompts will be accepted",
                    self.config.model_config.tokenizer,
                    type(e).__name__,
                    e,
                )
                self._tokenizer = None
        return self._tokenizer

    def process(
        self,
        request_id: str,
        prompt: PromptType,
        params: SamplingParams,
        arrival_time: float | None = None,
        priority: int = 0,
        pooling_params=None,
    ) -> EngineCoreRequest:
        if isinstance(prompt, str):
            prompt_text: str | None = prompt
            tokenizer = self.tokenizer
            if tokenizer is None:
                raise ValueError("no tokenizer; pass prompt_token_ids")
            prompt_token_ids = tokenizer.encode(prompt)
        elif isinstance(prompt, dict):
            if "prompt_token_ids" in prompt:
                prompt_token_ids = list(prompt["prompt_token_ids"])
                prompt_text = prompt.get("prompt")
            elif "prompt" in prompt:
                inner = prompt["prompt"]
                tokenizer = self.tokenizer
                if not isinstance(inner, str) or tokenizer is None:
                    raise ValueError("no tokenizer; pass prompt_token_ids")
                prompt_text = inner
                prompt_token_ids = tokenizer.encode(inner)
            else:
                raise ValueError(f"invalid prompt dict keys: {list(prompt)}")
        else:
            raise TypeError(f"invalid prompt type {type(prompt)}")

        mm_inputs = None
        encdec = self._encdec_info()
        if encdec is not None and encdec.get("audio"):
            # Whisper-class audio encoder-decoder: the prompt IS the
            # decoder prompt (forced decoder ids); the mel features ride
            # the encoder-input plumbing via multi_modal_data["audio"].
            from vllm_tpu.multimodal import MMInput

            mm_data = (
                prompt.get("multi_modal_data")
                if isinstance(prompt, dict) else None
            ) or {}
            audio = mm_data.get("audio")
            if audio is None:
                raise ValueError(
                    "audio encoder-decoder model needs "
                    'multi_modal_data={"audio": mel_features}'
                )
            import numpy as np

            feats = np.asarray(audio, np.float32)
            mels = encdec.get("num_mel_bins")
            if feats.ndim != 2:
                raise ValueError(
                    f"audio features must be 2-D mel frames, got "
                    f"shape {feats.shape}"
                )
            if mels and feats.shape[0] == mels and feats.shape[1] != mels:
                feats = feats.T  # HF [n_mels, frames] -> [frames, n_mels]
            if not prompt_token_ids:
                prompt_token_ids = [encdec["decoder_start_token_id"]]
            mm_inputs = [MMInput(
                offset=0, num_tokens=1, encoder_features=feats,
            )]
        elif encdec is not None:
            # Encoder-decoder model: the user's prompt is the ENCODER
            # input; generation happens decoder-side from the start
            # token. The encoder tokens ride the encoder-input plumbing
            # (scheduled once, span = the first decoder position).
            from vllm_tpu.multimodal import MMInput

            if len(prompt_token_ids) > encdec["max_encoder_len"]:
                raise ValueError(
                    f"encoder input of {len(prompt_token_ids)} tokens "
                    f"exceeds max_encoder_len={encdec['max_encoder_len']}"
                )
            mm_inputs = [MMInput(
                offset=0, num_tokens=1,
                encoder_token_ids=list(prompt_token_ids),
            )]
            prompt_token_ids = [encdec["decoder_start_token_id"]]
        mm_data = (
            prompt.get("multi_modal_data")
            if isinstance(prompt, dict) and encdec is None
            else None
        )
        if mm_data:
            from vllm_tpu.multimodal import expand_mm_prompt

            images = mm_data.get("image")
            videos = mm_data.get("video")
            unknown = set(mm_data) - {"image", "video"}
            if unknown or (images is None and videos is None):
                raise ValueError(
                    f"unsupported multi_modal_data keys: {list(mm_data)}"
                )
            if images is not None and not isinstance(images, list):
                images = [images]
            info = self._mm_info()
            if videos is not None:
                if info.get("video_token_id") is None:
                    raise ValueError(
                        "this model does not accept video inputs"
                    )
                # Normalize to a LIST OF CLIPS: a clip is a 4-D array or
                # a list of frames; a bare list of frames is one clip.
                if isinstance(videos, list):
                    is_clip_list = videos and (
                        isinstance(videos[0], list)
                        or getattr(videos[0], "ndim", 0) == 4
                    )
                    videos = videos if is_clip_list else [videos]
                else:
                    videos = [videos]
            # A span larger than the whole encoder budget could never be
            # scheduled — the engine would trim its chunk to zero forever.
            budget = self.config.scheduler_config.encoder_cache_budget
            worst = max(
                info["tokens_per_image"] if images else 0,
                info.get("tokens_per_video", 0) if videos else 0,
            )
            if worst > budget:
                raise ValueError(
                    f"one multimodal item needs {worst} encoder tokens "
                    f"but encoder_cache_budget is {budget}"
                )
            prompt_token_ids, mm_inputs = expand_mm_prompt(
                prompt_token_ids, images or [],
                image_token_id=info["image_token_id"],
                tokens_per_image=info["tokens_per_image"],
                image_size=info["image_size"],
                videos=videos,
                video_token_id=info.get("video_token_id"),
                tokens_per_video=info.get("tokens_per_video"),
                video_frames=info.get("video_frames"),
            )

        max_len = self.config.scheduler_config.max_model_len
        if not prompt_token_ids:
            raise ValueError("empty prompt")
        if len(prompt_token_ids) >= max_len:
            raise ValueError(
                f"prompt ({len(prompt_token_ids)} tokens) is longer than "
                f"max_model_len-1 ({max_len - 1})"
            )
        # A prompt whose KV footprint exceeds the whole cache could never be
        # scheduled — the engine would spin on it forever. Reject upfront.
        cache = self.config.cache_config
        if cache.num_gpu_blocks is not None:
            # Every pool stripe reserves its first block as a null page
            # (one stripe = one null block when cp is off).
            capacity = (
                cache.num_gpu_blocks - cache.num_kv_stripes
            ) * cache.block_size
            if len(prompt_token_ids) + 1 > capacity:
                raise ValueError(
                    f"prompt ({len(prompt_token_ids)} tokens) exceeds total "
                    f"KV cache capacity ({capacity} tokens); raise "
                    f"gpu_memory_utilization or num_gpu_blocks_override"
                )

        model_cls = self._model_class()
        encoder_only = getattr(model_cls, "is_encoder_only", False)
        pooling_only = encoder_only or getattr(
            model_cls, "pooling_only", False
        )
        if pooling_only and pooling_params is None:
            raise ValueError(
                "this model serves pooling/scoring requests only "
                "(no generation); pass pooling_params"
            )
        if pooling_params is not None:
            sc = self.config.scheduler_config
            chunk_cap = sc.max_num_batched_tokens
            if sc.long_prefill_token_threshold > 0:
                chunk_cap = min(chunk_cap, sc.long_prefill_token_threshold)
            # Mean pooling segments one chunk; encoder-only bidirectional
            # attention cannot be chunk-prefilled at all.
            if (
                pooling_params.pooling_type == "mean" or encoder_only
            ) and len(prompt_token_ids) > chunk_cap:
                raise ValueError(
                    f"{'encoder-only' if encoder_only else 'mean'} pooling "
                    "requires the prompt to fit one scheduler chunk "
                    f"({chunk_cap} tokens)"
                )
            if pooling_params.pooling_type in ("cls", "classify") and not (
                hasattr(model_cls, "pooled_extra")
            ):
                raise ValueError(
                    f"pooling_type {pooling_params.pooling_type!r} needs an "
                    "encoder-only model with a pooler head"
                )
            has_classifier = getattr(model_cls, "classifier_head", False)
            if pooling_params.pooling_type == "classify" and not has_classifier:
                raise ValueError(
                    "pooling_type 'classify' needs a SequenceClassification "
                    "checkpoint"
                )
            if pooling_params.pooling_type == "cls" and has_classifier:
                raise ValueError(
                    "pooling_type 'cls' returns the pooler vector; this "
                    "checkpoint has a classification head — use 'classify' "
                    "(or load the base *Model checkpoint for embeddings)"
                )
            params = SamplingParams(max_tokens=1)
        params = self._finalize_params(params, len(prompt_token_ids))
        eos_token_id = None
        if self.tokenizer is not None:
            eos_token_id = self.tokenizer.eos_token_id

        from vllm_tpu.tracing import new_trace_id, trace_enabled

        req = EngineCoreRequest(
            request_id=request_id,
            prompt_token_ids=prompt_token_ids,
            sampling_params=params,
            arrival_time=arrival_time if arrival_time is not None else time.monotonic(),
            eos_token_id=eos_token_id,
            priority=priority,
            pooling_params=pooling_params,
            mm_inputs=mm_inputs,
            # Trace correlation is assigned HERE, at the frontend: the id
            # rides the core-client wire so engine-core / worker spans for
            # this request fuse with the frontend's in a merged timeline.
            trace_id=new_trace_id() if trace_enabled() else None,
        )
        req.prompt_text = prompt_text  # carried for outputs
        return req

    def _finalize_params(self, params: SamplingParams, prompt_len: int) -> SamplingParams:
        from dataclasses import replace

        max_len = self.config.scheduler_config.max_model_len
        cap = max_len - prompt_len
        max_tokens = params.max_tokens if params.max_tokens is not None else cap
        bad_words_token_ids = params.bad_words_token_ids
        if params.bad_words and bad_words_token_ids is None:
            if self.tokenizer is None:
                raise ValueError("bad_words requires a tokenizer")
            # Both surface forms (word-initial and mid-text) like the
            # reference (vllm/v1/sample/logits_processor bad-words prep).
            seqs = []
            for w in params.bad_words:
                for variant in (w, " " + w):
                    ids = self.tokenizer.encode(
                        variant, add_special_tokens=False
                    )
                    if ids and ids not in seqs:
                        seqs.append(ids)
            bad_words_token_ids = seqs
        from vllm_tpu.models.generation import fill_request_params

        params = fill_request_params(
            params, self.config.resolve_generation().model_config.generation
        )
        return replace(
            params,
            max_tokens=min(max_tokens, cap),
            bad_words_token_ids=bad_words_token_ids,
        )
