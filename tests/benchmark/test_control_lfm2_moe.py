"""The controls of ``correct`` for ``lfm2-8b-a1b.chat``: the comparison that
passes the program as configured must fail it (i) with the convolution's
tail STORED a precision under the served one, (ii) with a convolution that
forgets its tail, at a chunk's edge or at a decode step, (iii) with the
experts weighed by ``s + b``, the choice's scores, instead of ``s``. None is
a flag of the program; on the chip they are edits of the chip's copy
(PERF.md section 4), here patches. At a tiny size on the CPU, through the
harness's own ``compare``; the cell's limits were set from the chip's
readings. Served in float32, so that a sound run differs from the reference
in the order of sums only and what a control adds stands alone."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from benchmark.harness.checker import compare
from benchmark.harness.manifest import BENCH_DIR, load_module

from .test_reference_lfm2_moe import served_samples
from .tiny_lfm2_moe import TINY_LFM2_MOE

REF = load_module(BENCH_DIR / "reference" / "lfm2_moe.py")
SEEDS = (3, 4)
LENGTHS = (24, 70)  # the second is prefilled over three steps of 32 tokens
SOUND: dict[int, dict] = {}


def sound(tmp_path, seed):
    if seed not in SOUND:
        SOUND[seed] = compare(TINY_LFM2_MOE, REF, seed,
                              served_samples(tmp_path, seed, lengths=LENGTHS), "float32")
    return SOUND[seed]


def tail_in_bfloat16(monkeypatch):
    from vllm_tpu.models.lfm2_moe import Lfm2MoeForCausalLM as M

    alloc = M.alloc_kv_cache

    def lower(self, *args):
        cache = alloc(self, *args)
        return {**cache, "conv": cache["conv"].astype(jnp.bfloat16)}

    monkeypatch.setattr(M, "alloc_kv_cache", lower)


def forgets_its_tail(where):
    """The model's call of the shared convolution with every row named
    fresh in the steps of one kind: ``chunk`` (a step that holds a prompt's
    chunk: the tail is lost at every chunk's edge and nowhere else) or
    ``decode`` (a decode-only step: every generated token sees two zeros
    behind it)."""

    def patch(monkeypatch):
        from vllm_tpu.models import lfm2_moe

        conv = lfm2_moe.kda_short_conv

        def forgetful(xs, stack, weights, layer, slots, fresh, *rest, decode_only=False):
            if decode_only == (where == "decode"):
                fresh = jnp.ones_like(fresh)
            return conv(xs, stack, weights, layer, slots, fresh, *rest,
                        decode_only=decode_only)

        monkeypatch.setattr(lfm2_moe, "kda_short_conv", forgetful)

    return patch


def experts_weighed_by_the_choice(monkeypatch):
    """``select_experts_grouped`` with the selection bias left in the
    weights: the same experts, weighed by ``s + b`` over their sum."""
    from vllm_tpu.layers import moe

    select = moe.select_experts_grouped

    def biased(logits, bias, *, top_k, renorm_guard=1e-20, **kw):
        _, ids = select(logits, bias, top_k=top_k, **kw)
        picked = jnp.take_along_axis(jax.nn.sigmoid(logits) + bias[None, :], ids, axis=-1)
        return picked / (picked.sum(-1, keepdims=True) + renorm_guard), ids

    monkeypatch.setattr(moe, "select_experts_grouped", biased)


CONTROLS = {
    "the tail stored in bfloat16": tail_in_bfloat16,
    "the tail forgotten at a chunk's edge": forgets_its_tail("chunk"),
    "the tail forgotten at a decode step": forgets_its_tail("decode"),
    "the experts weighed by s + b": experts_weighed_by_the_choice,
}


@pytest.mark.parametrize("control", list(CONTROLS))
def test_a_control_fails_the_comparison_that_passes_the_program(tmp_path, monkeypatch, control):
    """Each on two seeds, over the form of the committed limits (the median,
    the worst sequence's mean): a sound run reads under 5e-4 everywhere, a
    control at least ten times the sound runs' largest in both. The tail
    forgotten at a chunk's edge touches the second sequence alone (its
    prompt crosses two edges): the worst sequence's mean is the limit that
    holds it."""
    ok = [sound(tmp_path, seed) for seed in SEEDS]
    assert max(r["max_abs"] for r in ok) < 5e-4, ok
    CONTROLS[control](monkeypatch)
    lower = [compare(TINY_LFM2_MOE, REF, seed,
                     served_samples(tmp_path, seed, lengths=LENGTHS), "float32")
             for seed in SEEDS]
    assert min(r["worst_sequence_mean_abs"] for r in lower) > 10 * max(
        r["worst_sequence_mean_abs"] for r in ok), (ok, lower)
    if "chunk" not in control:
        assert min(r["p50_abs"] for r in lower) > 10 * max(r["p50_abs"] for r in ok), (ok, lower)
