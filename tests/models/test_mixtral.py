"""Mixtral MoE tests: layer semantics + HF logits parity + e2e greedy.

Protocol of the reference's ``tests/kernels/moe`` (routing/grouped-GEMM vs
reference impl) + ``tests/models/language`` (HF parity on a tiny config).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vllm_tpu.layers.moe import _dense_moe, fused_moe, select_experts


def tiny_mixtral_config(**overrides):
    from transformers import MixtralConfig

    kwargs = dict(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_local_experts=4,
        num_experts_per_tok=2,
        max_position_embeddings=256,
        tie_word_embeddings=False,
    )
    kwargs.update(overrides)
    return MixtralConfig(**kwargs)


@pytest.fixture(scope="module")
def tiny_mixtral(tmp_path_factory):
    import torch
    from transformers import MixtralForCausalLM

    torch.manual_seed(0)
    model = MixtralForCausalLM(tiny_mixtral_config()).to(torch.float32)
    path = tmp_path_factory.mktemp("tiny_mixtral")
    model.save_pretrained(str(path), safe_serialization=True)
    return str(path)


def test_select_experts_matches_naive():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((5, 8)), jnp.float32)
    w, ids = select_experts(logits, top_k=2, renormalize=True)
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    for t in range(5):
        top2 = np.argsort(probs[t])[::-1][:2]
        np.testing.assert_array_equal(np.sort(np.asarray(ids[t])), np.sort(top2))
        np.testing.assert_allclose(np.asarray(w[t]).sum(), 1.0, rtol=1e-6)


def test_dense_moe_matches_per_token_loop():
    rng = np.random.default_rng(1)
    t, d, f, e, k = 6, 16, 24, 4, 2
    hidden = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    wg = jnp.asarray(rng.standard_normal((e, d, f)) * 0.1, jnp.float32)
    wu = jnp.asarray(rng.standard_normal((e, d, f)) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.standard_normal((e, f, d)) * 0.1, jnp.float32)
    router = jnp.asarray(rng.standard_normal((d, e)), jnp.float32)

    out = fused_moe(hidden, router, wg, wu, wd, top_k=k, use_grouped=False)

    # Naive per-token reference.
    logits = np.asarray(hidden @ router)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    expect = np.zeros((t, d), np.float32)
    for i in range(t):
        top = np.argsort(probs[i])[::-1][:k]
        ws = probs[i][top] / probs[i][top].sum()
        for wgt, ex in zip(ws, top):
            hx = np.asarray(hidden[i])
            gate = hx @ np.asarray(wg[ex])
            up = hx @ np.asarray(wu[ex])
            act = gate / (1 + np.exp(-gate)) * up
            expect[i] += wgt * (act @ np.asarray(wd[ex]))
    np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-4, atol=2e-4)


@pytest.fixture
def gmm_calls(monkeypatch):
    """The grouped matmul as ``_grouped_moe`` calls it (``moe._gmm``: the
    repo's kernel, ``ops/gmm_kernel.py``, at these widths), with every row
    past the groups POISONED in its answer (the kernel does not write them;
    what the buffer held is the caller's to keep out) and each call's
    ``group_sizes`` recorded."""
    from vllm_tpu.layers import moe

    real, sizes_seen = moe._gmm, []

    def poisoned(lhs, rhs, group_sizes, interpret=False):
        sizes_seen.append(np.asarray(group_sizes))
        out = real(lhs, rhs, group_sizes, interpret)
        ran = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(ran[:, None], out, jnp.nan)

    monkeypatch.setattr(moe, "_gmm", poisoned)
    return sizes_seen


@pytest.mark.parametrize("n_live, held", [
    (None, None), (11, None), (11, (1, 2))], ids=["every_token", "live_prefix",
                                                   "live_prefix_and_held"])
def test_grouped_matches_dense_interpret(gmm_calls, n_live, held):
    """The grouped path (interpret mode on CPU) == dense path on the
    live tokens, under a mask of live tokens and under a share; a pad
    token's output is exactly 0, whatever the rows the kernel left out
    hold."""
    from vllm_tpu.layers.moe import _grouped_moe, select_experts

    rng = np.random.default_rng(2)
    t, d, f, e, k = 16, 128, 128, 4, 2
    hidden = rng.standard_normal((t, d)) * 0.3
    if n_live is not None:
        hidden[n_live:] = hidden[n_live]  # pad slots are all alike
    hidden = jnp.asarray(hidden, jnp.float32)
    wg = jnp.asarray(rng.standard_normal((e, d, f)) * 0.05, jnp.float32)
    wu = jnp.asarray(rng.standard_normal((e, d, f)) * 0.05, jnp.float32)
    wd = jnp.asarray(rng.standard_normal((e, f, d)) * 0.05, jnp.float32)
    router = jnp.asarray(rng.standard_normal((d, e)), jnp.float32)

    logits = hidden @ router
    w, ids = select_experts(logits, k)
    live = None if n_live is None else jnp.arange(t) < n_live
    first, count = held or (0, e)
    dense = _dense_moe(
        hidden, *(x[first:first + count] for x in (wg, wu, wd)), w, ids,
        first_expert=first)
    grouped = np.asarray(_grouped_moe(
        hidden, wg, wu, wd, w, ids, interpret=True, held=held, live=live))
    assert np.isfinite(grouped).all()
    rows = slice(None, n_live)
    np.testing.assert_allclose(
        np.asarray(dense)[rows], grouped[rows], rtol=2e-4, atol=2e-4
    )
    assert np.abs(np.asarray(dense)[rows]).max() > 1e-3
    if n_live is not None:
        assert not grouped[n_live:].any()
    # What the kernel was handed: the pairs of live tokens on held experts.
    ids = np.asarray(ids)[rows]
    want = np.bincount(ids.reshape(-1), minlength=e)
    want[:first], want[first + count:] = 0, 0
    assert len(gmm_calls) == 3
    for sizes in gmm_calls:
        np.testing.assert_array_equal(sizes, want)


def test_the_mask_takes_the_pad_tiles_off_the_grouped_matmul(gmm_calls):
    """At ``sdar30b.chat``'s shape, 512 tokens of which 140 live, 8 of 128
    experts a token, the pad slots alike: without the mask their 372 x 8
    pairs stand on eight experts as whole row tiles; with it megablox's own
    metadata has only the live pairs' tiles left to visit. (Alike is the
    kindest case: on the chip a pad slot holds what the attention kernel
    left in its buffer, and the pad pairs spread over most experts.)"""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata,
    )

    from vllm_tpu.layers.moe import _grouped_moe, select_experts

    rng = np.random.default_rng(5)
    t, n_live, d, f, e, k = 512, 140, 128, 128, 128, 8
    hidden = rng.standard_normal((t, d))
    hidden[n_live:] = hidden[n_live]
    hidden = jnp.asarray(hidden, jnp.float32)
    router = jnp.asarray(rng.standard_normal((d, e)), jnp.float32)
    w, ids = select_experts(hidden @ router, k)
    # One expert's matrices for all: only the kernel's bookkeeping is read.
    wg = jnp.zeros((e, d, f), jnp.float32)
    wd = jnp.zeros((e, f, d), jnp.float32)

    def tiles(live):
        del gmm_calls[:]
        _grouped_moe(hidden, wg, wg, wd, w, ids, interpret=True, live=live)
        sizes = gmm_calls[0]
        _, n = make_group_metadata(
            group_sizes=jnp.asarray(sizes), m=t * k, tm=128,
            start_group=jnp.int32(0), num_nonzero_groups=e,
            visit_empty_groups=False)
        return sizes, int(n)

    all_sizes, all_tiles = tiles(None)
    live_sizes, live_tiles = tiles(jnp.arange(t) < n_live)
    assert all_sizes.sum() == t * k and live_sizes.sum() == n_live * k
    pad_experts = np.flatnonzero(all_sizes - live_sizes)
    assert len(pad_experts) == k
    assert ((all_sizes - live_sizes)[pad_experts] == t - n_live).all()
    # A visit is a (group, row tile) pair: groups hit plus row tiles at
    # most, less one. 372 rows an expert are three or four tiles each.
    assert live_tiles <= np.count_nonzero(live_sizes) + n_live * k // 128
    assert all_tiles - live_tiles >= k * ((t - n_live) // 128 - 1)
    assert live_tiles < all_tiles


def test_mixtral_e2e_greedy_matches_hf(tiny_mixtral):
    import torch
    from transformers import AutoModelForCausalLM

    from vllm_tpu import LLM, SamplingParams

    llm = LLM(
        model=tiny_mixtral,
        dtype="float32",
        max_model_len=128,
        block_size=16,
        num_gpu_blocks_override=64,
        max_num_seqs=4,
        max_num_batched_tokens=128,
    )
    rng = np.random.default_rng(3)
    prompt_ids = rng.integers(5, 120, size=9).tolist()
    [out] = llm.generate(
        [{"prompt_token_ids": prompt_ids}],
        SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True),
    )

    hf = AutoModelForCausalLM.from_pretrained(tiny_mixtral, torch_dtype=torch.float32)
    hf.eval()
    with torch.no_grad():
        ref = hf.generate(
            torch.tensor([prompt_ids]), max_new_tokens=6,
            do_sample=False, eos_token_id=None, pad_token_id=0,
        )[0][len(prompt_ids):].tolist()
    assert out.outputs[0].token_ids == ref
