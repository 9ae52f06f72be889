"""Architecture registry.

Reference analog: ``vllm/model_executor/models/registry.py:70`` (320+
architectures over lazy imports). Keyed by the HF ``architectures[0]``
string; entries are lazy so importing the registry stays cheap.
"""

from __future__ import annotations

import importlib
from typing import Any

from vllm_tpu.logger import init_logger

logger = init_logger(__name__)

# arch name -> (module, class)
_REGISTRY: dict[str, tuple[str, str]] = {
    "LlamaForCausalLM": ("vllm_tpu.models.llama", "LlamaForCausalLM"),
    "MistralForCausalLM": ("vllm_tpu.models.llama", "MistralForCausalLM"),
    "Qwen2ForCausalLM": ("vllm_tpu.models.llama", "Qwen2ForCausalLM"),
    "Qwen3ForCausalLM": ("vllm_tpu.models.llama", "Qwen3ForCausalLM"),
    "Qwen3MoeForCausalLM": ("vllm_tpu.models.qwen3_moe", "Qwen3MoeForCausalLM"),
    "Qwen2MoeForCausalLM": ("vllm_tpu.models.qwen3_moe", "Qwen2MoeForCausalLM"),
    "SDARMoeForCausalLM": ("vllm_tpu.models.qwen3_moe", "SDARMoeForCausalLM"),
    "GemmaForCausalLM": ("vllm_tpu.models.gemma", "GemmaForCausalLM"),
    "Gemma2ForCausalLM": ("vllm_tpu.models.gemma", "Gemma2ForCausalLM"),
    "Gemma3ForCausalLM": ("vllm_tpu.models.gemma", "Gemma3ForCausalLM"),
    "Gemma3ForConditionalGeneration": ("vllm_tpu.models.gemma", "Gemma3TextOnlyFromVLM"),
    "MixtralForCausalLM": ("vllm_tpu.models.mixtral", "MixtralForCausalLM"),
    "DeepseekV2ForCausalLM": ("vllm_tpu.models.deepseek", "DeepseekV2ForCausalLM"),
    "DeepseekV3ForCausalLM": ("vllm_tpu.models.deepseek", "DeepseekV3ForCausalLM"),
    "Mamba2ForCausalLM": ("vllm_tpu.models.mamba2", "Mamba2ForCausalLM"),
    "MambaForCausalLM": ("vllm_tpu.models.mamba1", "MambaForCausalLM"),
    "BambaForCausalLM": ("vllm_tpu.models.bamba", "BambaForCausalLM"),
    "JambaForCausalLM": ("vllm_tpu.models.jamba", "JambaForCausalLM"),
    "Qwen3NextForCausalLM": ("vllm_tpu.models.qwen3_next", "Qwen3NextForCausalLM"),
    "KimiLinearForCausalLM": ("vllm_tpu.models.kimi_linear", "KimiLinearForCausalLM"),
    "FalconH1ForCausalLM": ("vllm_tpu.models.falcon_h1", "FalconH1ForCausalLM"),
    "Lfm2MoeForCausalLM": ("vllm_tpu.models.lfm2_moe", "Lfm2MoeForCausalLM"),
    "LagunaForCausalLM": ("vllm_tpu.models.laguna", "LagunaForCausalLM"),
    "MiMoV2ForCausalLM": ("vllm_tpu.models.mimo_v2", "MiMoV2ForCausalLM"),
    "Phi3ForCausalLM": ("vllm_tpu.models.phi3", "Phi3ForCausalLM"),
    "GraniteForCausalLM": ("vllm_tpu.models.granite", "GraniteForCausalLM"),
    "Olmo2ForCausalLM": ("vllm_tpu.models.olmo2", "Olmo2ForCausalLM"),
    "StableLmForCausalLM": ("vllm_tpu.models.stablelm", "StableLmForCausalLM"),
    "LlavaForConditionalGeneration": ("vllm_tpu.models.llava", "LlavaForConditionalGeneration"),
    "Qwen2VLForConditionalGeneration": ("vllm_tpu.models.qwen2_vl", "Qwen2VLForConditionalGeneration"),
    "Qwen2_5_VLForConditionalGeneration": ("vllm_tpu.models.qwen2_5_vl", "Qwen25VLForConditionalGeneration"),
    "InternVLForConditionalGeneration": ("vllm_tpu.models.internvl", "InternVLForConditionalGeneration"),
    "GPT2LMHeadModel": ("vllm_tpu.models.gpt_like", "GPT2LMHeadModel"),
    "GPTBigCodeForCausalLM": ("vllm_tpu.models.gpt_like", "GPTBigCodeForCausalLM"),
    "OPTForCausalLM": ("vllm_tpu.models.gpt_like", "OPTForCausalLM"),
    "GPTNeoXForCausalLM": ("vllm_tpu.models.gpt_like", "GPTNeoXForCausalLM"),
    "FalconForCausalLM": ("vllm_tpu.models.gpt_like", "FalconForCausalLM"),
    "PhiForCausalLM": ("vllm_tpu.models.gpt_like", "PhiForCausalLM"),
    # (MBart is NOT aliased here: it needs per-language forced-BOS
    # decoder prompts and its config may leave decoder_start_token_id
    # unset — advertising it would serve wrong-language output.)
    "BartForConditionalGeneration": ("vllm_tpu.models.bart", "BartForConditionalGeneration"),
    "WhisperForConditionalGeneration": ("vllm_tpu.models.whisper", "WhisperForConditionalGeneration"),
    "CohereForCausalLM": ("vllm_tpu.models.cohere", "CohereForCausalLM"),
    "OlmoForCausalLM": ("vllm_tpu.models.olmo", "OlmoForCausalLM"),
    "GlmForCausalLM": ("vllm_tpu.models.glm", "GlmForCausalLM"),
    "NemotronForCausalLM": ("vllm_tpu.models.nemotron", "NemotronForCausalLM"),
    "Starcoder2ForCausalLM": ("vllm_tpu.models.gpt_like", "Starcoder2ForCausalLM"),
    "GPTJForCausalLM": ("vllm_tpu.models.gpt_like", "GPTJForCausalLM"),
    "BertModel": ("vllm_tpu.models.bert", "BertModel"),
    "BertForSequenceClassification": ("vllm_tpu.models.bert", "BertForSequenceClassification"),
    "RobertaModel": ("vllm_tpu.models.bert", "RobertaModel"),
    "RobertaForSequenceClassification": ("vllm_tpu.models.bert", "RobertaForSequenceClassification"),
    "XLMRobertaModel": ("vllm_tpu.models.bert", "RobertaModel"),
    "XLMRobertaForSequenceClassification": ("vllm_tpu.models.bert", "RobertaForSequenceClassification"),
    "OlmoeForCausalLM": ("vllm_tpu.models.moe_zoo", "OlmoeForCausalLM"),
    "GraniteMoeForCausalLM": ("vllm_tpu.models.moe_zoo", "GraniteMoeForCausalLM"),
    "DbrxForCausalLM": ("vllm_tpu.models.moe_zoo", "DbrxForCausalLM"),
    "GptOssForCausalLM": ("vllm_tpu.models.gpt_oss", "GptOssForCausalLM"),
    "LlamaForSequenceClassification": ("vllm_tpu.models.seq_classify", "LlamaForSequenceClassification"),
    "MistralForSequenceClassification": ("vllm_tpu.models.seq_classify", "MistralForSequenceClassification"),
    "Qwen2ForSequenceClassification": ("vllm_tpu.models.seq_classify", "Qwen2ForSequenceClassification"),
    "Qwen3ForSequenceClassification": ("vllm_tpu.models.seq_classify", "Qwen3ForSequenceClassification"),
    "Gemma2ForSequenceClassification": ("vllm_tpu.models.seq_classify", "Gemma2ForSequenceClassification"),
}


class ModelRegistry:
    @staticmethod
    def register(arch: str, module: str, cls: str) -> None:
        """Out-of-tree model plugin hook (reference: plugin system)."""
        _REGISTRY[arch] = (module, cls)

    @staticmethod
    def get_supported_archs() -> list[str]:
        return sorted(_REGISTRY)

    @staticmethod
    def resolve(hf_config: Any) -> type:
        archs = getattr(hf_config, "architectures", None) or []
        for arch in archs:
            if arch in _REGISTRY:
                module, cls = _REGISTRY[arch]
                return getattr(importlib.import_module(module), cls)
        raise ValueError(
            f"no supported architecture in {archs}; supported: "
            f"{ModelRegistry.get_supported_archs()}"
        )


def get_model_class(hf_config: Any) -> type:
    return ModelRegistry.resolve(hf_config)


def load_hf_config(model_config) -> Any:
    if model_config.hf_config is not None:
        return model_config.hf_config
    if model_config.model.endswith(".gguf"):
        from vllm_tpu.models.gguf import config_from_gguf

        cfg = config_from_gguf(model_config.model)
        model_config.hf_config = cfg
        return cfg
    from transformers import AutoConfig, PretrainedConfig

    kw = dict(revision=model_config.revision,
              trust_remote_code=model_config.trust_remote_code)
    try:
        cfg = AutoConfig.from_pretrained(model_config.model, **kw)
    except ValueError:
        # A model_type transformers has no class for: take config.json as
        # it is if this registry serves one of its architectures.
        raw, _ = PretrainedConfig.get_config_dict(model_config.model, **kw)
        known = set(ModelRegistry.get_supported_archs())
        if not known & set(raw.get("architectures") or ()):
            raise
        cfg = PretrainedConfig.from_dict(raw)
    if model_config.hf_overrides:
        for k, v in model_config.hf_overrides.items():
            setattr(cfg, k, v)
    model_config.hf_config = cfg
    return cfg
