"""How a model generates, when not one token a sequence a step.

``generation == "block_diffusion"`` (the SDAR family): a decoding request
holds a block of ``block_length`` positions that is run whole several
times. A pass feeds every uncommitted position the mask token's embedding,
samples all of them, and a commit rule turns some into tokens; committed
tokens never change. When none is left uncommitted the clean block is run
once more and its K/V stored: in one row with the next block, all masks,
behind it, which is that block's first denoising pass too, wherever the
step's token budget and the model's length have room for both; else in a
store pass of its own. Attention is causal over blocks: a position sees
whole blocks to its left and its own block in both directions, so in a row
of two blocks the second sees the first's fresh K/V as a prefill chunk's
later blocks see its earlier ones.

The mechanism is a class attribute of the model (``generation``), its
numbers come from the model's ``config.json``, and everything downstream
(scheduler, runner, sampler, front end) keys on the mechanism, never on a
model's name.
"""

from __future__ import annotations

from typing import Any

BLOCK_DIFFUSION = "block_diffusion"
# The commit rules of the family's public generate script; a row's rule is
# data of the step program (its index in this tuple).
REMASKING_RULES = ("sequential", "low_confidence_static",
                   "low_confidence_dynamic")


def validate_diffusion_params(remasking: str | None,
                              denoising_steps: int | None,
                              confidence_threshold: float | None) -> None:
    if remasking is not None and remasking not in REMASKING_RULES:
        raise ValueError(
            f"remasking must be one of {REMASKING_RULES}, got {remasking!r}")
    if denoising_steps is not None and (
            isinstance(denoising_steps, bool)
            or not isinstance(denoising_steps, int) or denoising_steps < 1):
        raise ValueError(
            f"denoising_steps must be an integer >= 1, got {denoising_steps!r}")
    if confidence_threshold is not None and not (
            0.0 <= float(confidence_threshold) <= 1.0):
        raise ValueError(
            "confidence_threshold must be in [0, 1], got "
            f"{confidence_threshold!r}")


def generation_of(model_cls: type, hf_config: Any) -> dict | None:
    """The generation group of a model, or None for an autoregressive one."""
    kind = getattr(model_cls, "generation", "autoregressive")
    if kind != BLOCK_DIFFUSION:
        return None
    block = int(getattr(hf_config, "block_length", 4))
    mask_id = getattr(hf_config, "mask_token_id", None)
    if mask_id is None:
        raise ValueError(
            "generation == 'block_diffusion' needs mask_token_id in the "
            "model's config.json")
    if block < 1 or block & (block - 1) or 16 % block:
        raise ValueError(
            f"block_length {block} must be a power of two that divides the "
            "KV page of 16 tokens")
    gen = dict(
        kind=BLOCK_DIFFUSION, block_length=block, mask_token_id=int(mask_id),
        remasking=getattr(hf_config, "remasking_strategy", "low_confidence_dynamic"),
        denoising_steps=int(getattr(hf_config, "denoising_steps", block)),
        confidence_threshold=float(getattr(hf_config, "confidence_threshold", 0.9)),
    )
    validate_diffusion_params(gen["remasking"], gen["denoising_steps"],
                              gen["confidence_threshold"])
    if block % gen["denoising_steps"]:
        raise ValueError(
            f"denoising_steps {gen['denoising_steps']} must divide "
            f"block_length {block}")
    return gen


def resolve_generation(config) -> None:
    """Fill ``model_config.generation`` / ``scheduler_config.block_length``
    and refuse the settings that do not compose with the mechanism."""
    mc, sc = config.model_config, config.scheduler_config
    if mc.generation is None:
        from vllm_tpu.models.registry import get_model_class, load_hf_config

        hf_config = load_hf_config(mc)
        mc.generation = generation_of(get_model_class(hf_config), hf_config) or {}
    gen = mc.generation
    if not gen:
        return
    why = (f"generation == {gen['kind']!r} (a step runs whole blocks of "
           f"{gen['block_length']} positions and commits a data-dependent "
           "number of them)")
    if config.speculative_config.enabled:
        raise ValueError(
            f"speculative decoding is incompatible with {why}: a draft row "
            "of 1 + k tokens and a block row cannot share a position")
    if config.parallel_config.pipeline_parallel_size > 1:
        raise ValueError(f"pipeline parallelism is incompatible with {why}")
    if config.parallel_config.context_parallel_size > 1:
        raise ValueError(f"context parallelism is incompatible with {why}")
    if config.lora_config.enable_lora:
        raise ValueError(f"LoRA serving is incompatible with {why}")
    block = gen["block_length"]
    if sc.max_model_len % block or sc.max_num_batched_tokens < block:
        raise ValueError(
            f"max_model_len ({sc.max_model_len}) must be a multiple of the "
            f"block length {block}, and max_num_batched_tokens at least one "
            f"block, under {why}")
    sc.block_length = block
    # The decode attention kernel (T == R) and cascade attention assume one
    # query position a row.
    sc.enable_decode_attention = False
    sc.enable_cascade_attention = False


def fill_request_params(params, gen: dict | None):
    """A request's diffusion parameters with the model's defaults filled
    in; refuses them on an autoregressive model, and refuses what the
    mechanism cannot honour on a diffusion one."""
    asked = (params.remasking, params.denoising_steps, params.confidence_threshold)
    if not gen:
        if any(v is not None for v in asked):
            raise ValueError(
                "remasking, denoising_steps and confidence_threshold need a "
                "model with generation == 'block_diffusion'")
        return params
    from dataclasses import replace

    why = f"generation == {gen['kind']!r}"
    if params.structured_outputs is not None and params.structured_outputs.is_set:
        raise ValueError(
            f"structured output is incompatible with {why}: a grammar "
            "advances one token at a time, a block commits positions out of order")
    if (params.presence_penalty or params.frequency_penalty
            or params.repetition_penalty != 1.0 or params.logit_bias
            or params.allowed_token_ids or params.bad_words or params.min_p
            or params.min_tokens or params.prompt_logprobs is not None):
        raise ValueError(
            "penalties, logit_bias, allowed_token_ids, bad_words, min_p, "
            f"min_tokens and prompt_logprobs are not supported with {why}")
    steps = params.denoising_steps or gen["denoising_steps"]
    if gen["block_length"] % steps:
        raise ValueError(
            f"denoising_steps {steps} must divide the block length "
            f"{gen['block_length']}")
    return replace(
        params,
        remasking=params.remasking or gen["remasking"],
        denoising_steps=steps,
        confidence_threshold=(
            gen["confidence_threshold"] if params.confidence_threshold is None
            else float(params.confidence_threshold)),
    )
