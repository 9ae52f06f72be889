"""In-jit sampler over padded request rows.

Reference analog: ``vllm/v1/sample/sampler.py`` (pipeline order documented
:22-60) + the CUDA sampling kernels in ``csrc/sampler.cu`` — here the whole
pipeline is one traced function; XLA fuses it behind the logits matmul.

Pipeline: penalties -> logit bias/allowed tokens (grammar bitmask enters the
same way) -> temperature -> top-k -> top-p -> min-p -> Gumbel-max sample,
with greedy rows (temperature 0) taking argmax. Gumbel-max avoids a full
cumulative-sort sample: sampling = argmax(logits/T + Gumbel noise) after the
top-k/top-p mask, which is exactly categorical sampling over the masked
distribution (the Model-Runner-V2 trick, ``docs/design/model_runner_v2.md``).

Unlike the reference, top-k/top-p are SORT-FREE: the masking, reductions and
the seeded Gumbel stream are the shared primitives of
``ops/sampler_kernel.py`` (rank-space bisection + counter-based Threefry),
so this XLA path is bit-exact against the fused Pallas sampling kernel —
``dispatch_sample`` below routes between them per the usual eligibility +
escape-hatch rules (mirrors ``ops/attention.py:dispatch_ragged_attention``).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from vllm_tpu.ops import sampler_kernel as _sk

_NEG_INF = jnp.float32(_sk.MASK_VALUE)


@jax.tree_util.register_dataclass
@dataclass
class SamplingMetadata:
    """Per-request-row sampling state, padded to the request bucket [R]."""

    temperature: jnp.ndarray  # [R] f32; 0 => greedy
    top_k: jnp.ndarray  # [R] i32; 0 => disabled
    top_p: jnp.ndarray  # [R] f32; 1 => disabled
    min_p: jnp.ndarray  # [R] f32; 0 => disabled
    # Penalties ([R] f32); neutral values 0/0/1 disable.
    presence_penalty: jnp.ndarray
    frequency_penalty: jnp.ndarray
    repetition_penalty: jnp.ndarray
    # Per-row PRNG keys [R, 2] u32 (seeded requests get stable streams).
    prng_keys: jnp.ndarray
    # [R, V] i32 output-token counts; empty placeholder when no penalties
    # are active in the batch (static `needs_penalties` selects the trace).
    output_token_counts: jnp.ndarray
    prompt_token_mask: jnp.ndarray  # [R, V] bool, or empty placeholder


def apply_penalties(logits: jnp.ndarray, md: SamplingMetadata) -> jnp.ndarray:
    """Repetition / presence / frequency penalties (HF/OpenAI semantics,
    reference: ``vllm/v1/sample/ops/penalties.py``)."""
    return _sk.penalize_block(
        logits,
        md.output_token_counts,
        md.prompt_token_mask,
        md.repetition_penalty[:, None],
        md.frequency_penalty[:, None],
        md.presence_penalty[:, None],
    )


def _pad_vocab(logits: jnp.ndarray) -> jnp.ndarray:
    """Pad the vocab axis to the width the epilogue computes at, with -inf
    (zero weight, never wins an argmax): ``compute_width``, the kernel's
    own."""
    v = logits.shape[-1]
    v2 = _sk.compute_width(v)
    if v2 == v:
        return logits
    return jnp.pad(logits, ((0, 0), (0, v2 - v)), constant_values=-jnp.inf)


def _sum_fn(vocab: int):
    """None (the halving tree the kernel shares, so the two agree bit for
    bit) at power-of-two widths; at the wide ones one fused pass in any
    order, since the kernel's ``tiled_row_sum`` would have XLA write every
    partial sum of a 31-pass bisection out. There the two paths' sums
    may differ in their last bits (1e-6 relative on the chip, tokens
    equal: ``tools/probe_sampler.py``)."""
    if _sk.compute_width(vocab) <= _sk.MAX_POW2_WIDTH:
        return None
    return _sk.plain_sum


def _mask_top_k(logits: jnp.ndarray, top_k: jnp.ndarray) -> jnp.ndarray:
    """Keep each row's top-k logits (0 disables) — sort-free radix
    selection of the k-th value; ties with it are kept, matching the old
    sorted formulation."""
    v = logits.shape[-1]
    x = _pad_vocab(logits)
    out = _sk.mask_top_k_block(x, top_k[:, None].astype(jnp.int32), v)
    return out[:, :v]


def _mask_top_p_min_p(
    logits: jnp.ndarray, top_p: jnp.ndarray, min_p: jnp.ndarray
) -> jnp.ndarray:
    """Nucleus + min-p truncation without softmax-sort-cumsum: bisect the
    weight-space cutoff (see ``ops/sampler_kernel.py``)."""
    v = logits.shape[-1]
    x = _pad_vocab(logits)
    out = _sk.mask_top_p_min_p_block(
        x, top_p[:, None], min_p[:, None], _sum_fn(v)
    )
    return out[:, :v]


def sample(
    logits: jnp.ndarray,  # [R, V] f32
    md: SamplingMetadata,
    *,
    needs_penalties: bool = False,
    needs_top_k: bool = True,
    needs_top_p_min_p: bool = True,
    needs_gumbel: bool = True,
    return_prob: bool = False,
    sum_fn=None,
) -> tuple[jnp.ndarray, ...]:
    """Returns (sampled [R] i32, logprobs [R, V] f32 log-softmax of the
    pre-masking distribution — what logprob reporting uses), and with
    ``return_prob`` a third, [R] f32: the probability of each drawn token
    under the distribution it was drawn from (``sample_block``).
    ``sum_fn`` overrides the order of the float sums (default: this
    width's, ``_sum_fn``); the tests hand in the kernel's.

    The ``needs_*`` flags are static: an all-greedy or vanilla-temperature
    batch skips the [R, V] truncation passes — and, with
    ``needs_gumbel=False``, the [R, V] Gumbel draw — entirely (separate
    jit trace per combo). An all-greedy batch (the throughput-bench shape)
    is a single fused argmax behind the logits matmul.
    """
    raw_logprobs = jax.nn.log_softmax(logits, axis=-1)
    sum_fn = sum_fn or _sum_fn(logits.shape[-1])

    if needs_penalties:
        logits = apply_penalties(logits, md)

    if not needs_gumbel:
        # Statically all-greedy: temperature scaling, masking, and noise
        # cannot change an argmax; skip them (~5 [R, V] passes saved).
        pick = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if not return_prob:
            return pick, raw_logprobs
        x = _pad_vocab(logits)
        w = jnp.exp(x - _sk.row_max(x))
        drawn = jnp.take_along_axis(w, pick[:, None], axis=-1)
        return pick, raw_logprobs, (drawn / (sum_fn or _sk.row_sum)(w))[:, 0]

    keys = md.prng_keys.astype(jnp.uint32)
    sampled = _sk.sample_block(
        _pad_vocab(logits),
        md.temperature[:, None],
        md.top_k[:, None].astype(jnp.int32),
        md.top_p[:, None],
        md.min_p[:, None],
        keys[:, 0:1],
        keys[:, 1:2],
        vocab=logits.shape[-1],
        needs_top_k=needs_top_k,
        needs_top_p_min_p=needs_top_p_min_p,
        return_prob=return_prob,
        sum_fn=sum_fn,
    )
    if return_prob:
        return sampled[0][:, 0], raw_logprobs, sampled[1][:, 0]
    return sampled[:, 0], raw_logprobs


def sampler_kernel_eligible(
    vocab: int,
    *,
    needs_gumbel: bool,
    enable_kernel: bool = True,
    allow_interpret: bool = False,
    tp_size: int = 1,
) -> tuple[bool, bool]:
    """(use_kernel, interpret) for a batch shape — the single eligibility
    rule, shared by ``dispatch_sample`` (trace time) and the runner's
    launch/fallback counters (host side). All-greedy batches
    (``needs_gumbel=False``) are NOT kernel work: the XLA argmax path is
    already a single fused reduction behind the logits matmul. Neither is
    a tensor-parallel step (``tp_size > 1``): ``lm_head`` is sharded over
    the vocabulary and the kernel needs whole vocabulary rows (and, being
    a Mosaic call, cannot be partitioned by GSPMD), so the fused XLA
    epilogue, which GSPMD partitions, serves those."""
    import vllm_tpu.envs as envs

    if not needs_gumbel or not enable_kernel or tp_size > 1:
        return False, False
    if envs.VLLM_TPU_DISABLE_PALLAS or envs.VLLM_TPU_DISABLE_SAMPLER_KERNEL:
        return False, False
    on_tpu = jax.default_backend() == "tpu"
    interpret = (
        bool(allow_interpret and envs.VLLM_TPU_PALLAS_INTERPRET)
        and not on_tpu
    )
    if not (on_tpu or interpret):
        return False, False
    if not interpret:
        # Mosaic path: 128-lane-aligned vocab, big enough to beat the
        # fused XLA epilogue, and a row block's working set at the width
        # it computes at inside the least VMEM the kernel is served with.
        if vocab % 128 != 0 or vocab < 2048:
            return False, False
        if _sk.compute_width(vocab) > _sk.MAX_KERNEL_WIDTH:
            return False, False
    return True, interpret


@jax.named_scope("sampler")
def dispatch_sample(
    logits: jnp.ndarray,
    md: SamplingMetadata,
    *,
    needs_penalties: bool = False,
    needs_top_k: bool = True,
    needs_top_p_min_p: bool = True,
    needs_gumbel: bool = True,
    enable_kernel: bool = True,
    allow_interpret: bool = False,
    tp_size: int = 1,
    return_prob: bool = False,
    num_active: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, ...]:
    """Kernel-vs-reference dispatch for the sampling epilogue (the
    ``dispatch_ragged_attention`` pattern): the fused Pallas kernel when
    eligible — one HBM logits read, no sorts — else the XLA sort-free
    reference above. Both produce bit-identical samples (at a width past
    ``MAX_POW2_WIDTH`` see ``_sum_fn``); A/B with
    ``VLLM_TPU_DISABLE_SAMPLER_KERNEL=1`` before filing kernel bugs.
    ``return_prob`` adds the drawn token's probability as a third output
    on either path. ``num_active`` (an i32 scalar) promises that nobody
    reads the rows at or past it: the kernel does no work for them and
    returns zeros there, the reference samples them all the same."""
    import vllm_tpu.envs as envs

    use_kernel, interpret = sampler_kernel_eligible(
        logits.shape[-1],
        needs_gumbel=needs_gumbel,
        enable_kernel=enable_kernel,
        allow_interpret=allow_interpret,
        tp_size=tp_size,
    )
    if not use_kernel:
        return sample(
            logits,
            md,
            needs_penalties=needs_penalties,
            needs_top_k=needs_top_k,
            needs_top_p_min_p=needs_top_p_min_p,
            needs_gumbel=needs_gumbel,
            return_prob=return_prob,
        )

    # Logprob reporting reads the pre-masking distribution; computed here
    # (not in-kernel) so it dead-code-eliminates when the caller drops it.
    raw_logprobs = jax.nn.log_softmax(logits, axis=-1)

    params_f = jnp.pad(
        jnp.stack(
            [
                md.temperature,
                md.top_p,
                md.min_p,
                md.repetition_penalty,
                md.frequency_penalty,
                md.presence_penalty,
            ],
            axis=1,
        ),
        ((0, 0), (0, 122)),
    )
    keys_i = lax.bitcast_convert_type(
        md.prng_keys.astype(jnp.uint32), jnp.int32
    )
    params_i = jnp.pad(
        jnp.stack(
            [md.top_k.astype(jnp.int32), keys_i[:, 0], keys_i[:, 1]],
            axis=1,
        ),
        ((0, 0), (0, 125)),
    )
    if needs_penalties:
        counts = md.output_token_counts.astype(jnp.int32)
        pmask = md.prompt_token_mask.astype(jnp.int8)
    else:
        counts = jnp.zeros((1, 128), jnp.int32)
        pmask = jnp.zeros((1, 128), jnp.int8)

    if interpret:
        # Small blocks, so that a tiny shape still takes several grid steps
        # and tiles; at most 16 tiles a row, so that a wide one does not
        # unroll hundreds of copies.
        v2 = _sk.compute_width(logits.shape[-1])
        blk_kw = dict(row_block=2, logits_tile=max(256, v2 // 16))
    else:
        blk_kw = {}
        if envs.VLLM_TPU_SAMPLER_ROW_BLOCK > 0:
            blk_kw["row_block"] = envs.VLLM_TPU_SAMPLER_ROW_BLOCK
        if envs.VLLM_TPU_SAMPLER_LOGITS_TILE > 0:
            blk_kw["logits_tile"] = envs.VLLM_TPU_SAMPLER_LOGITS_TILE

    sampled = _sk.fused_sample(
        logits,
        params_f,
        params_i,
        counts,
        pmask,
        num_active,
        needs_penalties=needs_penalties,
        needs_top_k=needs_top_k,
        needs_top_p_min_p=needs_top_p_min_p,
        return_prob=return_prob,
        interpret=interpret,
        **blk_kw,
    )
    if return_prob:
        return sampled[0], raw_logprobs, sampled[1]
    return sampled, raw_logprobs
