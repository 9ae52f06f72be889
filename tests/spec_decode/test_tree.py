"""Tree-attention spec verification: topology, the two-part verify
attention, and rejection sampling over root-to-leaf paths.

Reference analog: ``tests/v1/attention`` tree_attn coverage +
``tree_attn.py:255`` bias semantics.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.spec_decode.test_ngram_spec import _sampling_md
from vllm_tpu.spec_decode.tree import build_tree


def test_topology_chain_degenerates():
    t = build_tree("1x1x1")
    assert t.width == 4
    assert t.parent == (0, 0, 1, 2)
    assert t.depth == (0, 1, 2, 3)
    assert t.paths() == [[1, 2, 3]]
    m = t.ancestor_mask()
    # Chain ancestor mask == lower-triangular causal mask.
    assert (m == np.tril(np.ones((4, 4), bool))).all()


def test_topology_cartesian():
    t = build_tree("2x2")
    assert t.width == 7  # root + 2 + 4
    assert t.children[0] == (1, 2)
    assert t.children[1] == (3, 4)
    assert t.children[2] == (5, 6)
    assert t.rank[3:] == (0, 1, 0, 1)
    assert len(t.paths()) == 4
    m = t.ancestor_mask()
    assert m[5].tolist() == [True, False, True, False, False, True, False]


def _tree_rig(rng, tree, kv_lens, kh=2, h=4, d=64, bs=8, num_blocks=64):
    """Per-request windows of W tree tokens appended to committed
    contexts of ``kv_lens`` tokens; returns (q, cache, md) with the tree
    metadata set, plus the flat window token positions."""
    from vllm_tpu.ops.attention import (
        AttentionMetadata,
        kv_cache_shape,
        write_kv,
    )

    w = tree.width
    r = len(kv_lens)
    t = r * w
    depth = np.asarray(tree.depth, np.int32)

    max_blocks = max(-(-(kv + w) // bs) for kv in kv_lens) + 1
    block_tables = np.zeros((r, max_blocks), np.int32)
    kv_cache = jnp.asarray(
        rng.standard_normal(kv_cache_shape(1, num_blocks, bs, kh, d)),
        jnp.float32,
    )
    positions = np.zeros(t, np.int32)
    token_req_idx = np.zeros(t, np.int32)
    slot_mapping = np.zeros(t, np.int32)
    seq_lens = np.asarray([kv + w for kv in kv_lens], np.int32)
    query_start_loc = np.arange(0, t + 1, w, dtype=np.int32)

    next_block = 1
    for i, kv in enumerate(kv_lens):
        nb_i = -(-(kv + w) // bs)
        blocks = np.arange(next_block, next_block + nb_i, dtype=np.int32)
        next_block += nb_i
        block_tables[i, :nb_i] = blocks
        sl = slice(i * w, (i + 1) * w)
        positions[sl] = kv + depth  # root at kv, nodes at kv + depth
        token_req_idx[sl] = i
        # Window token j writes slot (kv + j): canonical root slot, node
        # slots in window order.
        flat_pos = kv + np.arange(w)
        slot_mapping[sl] = blocks[flat_pos // bs] * bs + flat_pos % bs

    md = AttentionMetadata(
        positions=jnp.asarray(positions),
        slot_mapping=jnp.asarray(slot_mapping),
        block_tables=jnp.asarray(block_tables),
        seq_lens=jnp.asarray(seq_lens),
        query_start_loc=jnp.asarray(query_start_loc),
        token_req_idx=jnp.asarray(token_req_idx),
        logits_indices=jnp.asarray(query_start_loc[1:] - 1),
        num_seqs=jnp.asarray([r], jnp.int32),
    )
    # Tree extras (what the runner builds in-jit).
    amask = jnp.asarray(tree.ancestor_mask())
    tree_mask = jnp.tile(amask, (r, 1))  # [T, W]
    window_start = jnp.repeat(
        jnp.asarray(query_start_loc[:-1], jnp.int32), w
    )
    paged = dataclasses.replace(
        md,
        block_tables=md.block_tables[md.token_req_idx],
        seq_lens=jnp.asarray(np.asarray(kv_lens, np.int32))[
            md.token_req_idx
        ],
        query_start_loc=jnp.arange(t + 1, dtype=jnp.int32),
        token_req_idx=jnp.arange(t, dtype=jnp.int32),
        num_seqs=jnp.asarray([t], jnp.int32),
    )
    md = dataclasses.replace(
        md, tree_mask=tree_mask, tree_window_start=window_start,
        tree_paged=paged,
    )

    q = jnp.asarray(rng.standard_normal((t, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((t, kh, d)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((t, kh, d)), jnp.float32)
    kv_cache = write_kv(kv_cache, jnp.int32(0), k, v_new, md.slot_mapping)
    return q, k, v_new, kv_cache, md


@pytest.mark.parametrize("spec", ["1x1x1", "2x2", "3x2x1"])
def test_tree_attention_matches_per_path_chain(spec):
    """For every root-to-leaf path, the tree tokens' outputs equal plain
    chain attention over (context + that path) — the tree-bias contract
    of the reference backend."""
    from vllm_tpu.ops.attention import (
        paged_attention,
        ref_ragged_paged_attention,
    )

    tree = build_tree(spec)
    rng = np.random.default_rng(0)
    kv_lens = [19, 8]
    q, k, v_new, kv_cache, md = _tree_rig(rng, tree, kv_lens)
    scale = 64 ** -0.5
    got = np.asarray(
        paged_attention(q, kv_cache, jnp.int32(0), md, scale)
    )

    # Reference: for each path, rebuild a CHAIN case (root + path nodes
    # written contiguously) and compare token-for-token.
    w = tree.width
    for path in tree.paths():
        chain = [0] + path  # window indices, contiguous semantic chain
        for i, kv_len in enumerate(kv_lens):
            sel = [i * w + c for c in chain]
            q_c = q[np.asarray(sel)]
            # Chain rig: same committed context; chain tokens re-written
            # at canonical slots kv_len..kv_len+len(chain).
            from vllm_tpu.ops.attention import (
                AttentionMetadata as MD,
                write_kv,
            )

            bt = np.asarray(md.block_tables)[i : i + 1]
            flat_pos = kv_len + np.arange(len(chain))
            slots = (
                bt[0][flat_pos // 8] * 8 + flat_pos % 8
            ).astype(np.int32)
            kv_chain = write_kv(
                kv_cache, jnp.int32(0), k[np.asarray(sel)],
                v_new[np.asarray(sel)], jnp.asarray(slots),
            )
            md_c = MD(
                positions=jnp.asarray(flat_pos, jnp.int32),
                slot_mapping=jnp.asarray(slots),
                block_tables=jnp.asarray(bt),
                seq_lens=jnp.asarray([kv_len + len(chain)], jnp.int32),
                query_start_loc=jnp.asarray(
                    [0, len(chain)], jnp.int32
                ),
                token_req_idx=jnp.zeros(len(chain), jnp.int32),
                logits_indices=jnp.asarray([len(chain) - 1], jnp.int32),
                num_seqs=jnp.asarray([1], jnp.int32),
            )
            want = np.asarray(
                ref_ragged_paged_attention(
                    q_c, kv_chain, jnp.int32(0), md_c, scale
                )
            )
            np.testing.assert_allclose(
                got[np.asarray(sel)], want, rtol=2e-4, atol=2e-4,
            )


def _chain_verify_greedy(logits_row, draft_row, tree):
    """Host-side sequential oracle: greedy walk of the tree."""
    out, kv = [], []
    cur = 0
    for d in range(1, tree.num_levels + 1):
        tgt = int(np.argmax(logits_row[cur]))
        hit = None
        for c in tree.children[cur]:
            if int(draft_row[c]) == tgt:
                hit = c
                break
        if hit is None:
            out.append(tgt)
            return out, kv
        out.append(int(draft_row[hit]))
        kv.append(hit)
        cur = hit
    out.append(int(np.argmax(logits_row[cur])))
    return out, kv


@pytest.mark.parametrize("spec", ["1x1", "2x2", "3x1x2"])
def test_tree_rejection_greedy_matches_oracle(spec):
    from vllm_tpu.sample.tree_rejection import tree_rejection_sample

    tree = build_tree(spec)
    rng = np.random.default_rng(5)
    r, w, v = 8, tree.width, 50
    logits = rng.standard_normal((r, w, v)).astype(np.float32)
    draft = rng.integers(0, v, size=(r, w)).astype(np.int32)
    # Force some rows to follow full paths: copy argmax into a path.
    for i in range(0, r, 2):
        cur = 0
        for d in range(tree.num_levels):
            child = tree.children[cur][rng.integers(len(tree.children[cur]))]
            draft[i, child] = int(np.argmax(logits[i, cur]))
            cur = child
    md = _sampling_md(r, 0.0)
    out, num_out, kv_src = tree_rejection_sample(
        jnp.asarray(logits), jnp.asarray(draft), tree, md,
        needs_top_k=False, needs_top_p_min_p=False, needs_gumbel=False,
    )
    out, num_out = np.asarray(out), np.asarray(num_out)
    kv_src = np.asarray(kv_src)
    for i in range(r):
        want, want_kv = _chain_verify_greedy(logits[i], draft[i], tree)
        assert num_out[i] == len(want), (i, want)
        assert out[i, : len(want)].tolist() == want
        assert kv_src[i, : len(want_kv)].tolist() == want_kv


def test_tree_rejection_sampling_rows_run():
    """Sampling rows execute the residual scheme (smoke: valid tokens,
    bounded num_out, deterministic under a fixed seed)."""
    from vllm_tpu.sample.tree_rejection import tree_rejection_sample

    tree = build_tree("2x2")
    rng = np.random.default_rng(6)
    r, w, v = 4, tree.width, 40
    logits = rng.standard_normal((r, w, v)).astype(np.float32) * 3
    draft = rng.integers(0, v, size=(r, w)).astype(np.int32)
    md = _sampling_md(r, 0.8)
    out1 = tree_rejection_sample(
        jnp.asarray(logits), jnp.asarray(draft), tree, md,
        needs_top_k=False, needs_top_p_min_p=False, needs_gumbel=True,
    )
    out2 = tree_rejection_sample(
        jnp.asarray(logits), jnp.asarray(draft), tree, md,
        needs_top_k=False, needs_top_p_min_p=False, needs_gumbel=True,
    )
    o1, n1, _ = (np.asarray(x) for x in out1)
    o2, n2, _ = (np.asarray(x) for x in out2)
    assert (o1 == o2).all() and (n1 == n2).all()
    assert ((n1 >= 1) & (n1 <= tree.num_levels + 1)).all()
    assert ((o1 >= 0) & (o1 < v)).all()


# ----------------------------------------------------------------------
# Acceptance gain and e2e equivalence
# ----------------------------------------------------------------------


def test_tree_accepts_where_chain_rejects():
    """The measurable win of tree verification: when the top-1 draft is
    wrong but a sibling matches the target argmax, a '2x1' tree accepts
    through the second branch while the '1x1' chain (= chain
    verification) stops — acceptance is strictly higher on the same
    logits."""
    from vllm_tpu.sample.tree_rejection import tree_rejection_sample

    rng = np.random.default_rng(9)
    r, v = 6, 30
    chain = build_tree("1x1")
    tree = build_tree("2x1")

    logits_t = rng.standard_normal((r, tree.width, v)).astype(np.float32)
    tgt0 = np.argmax(logits_t[:, 0], -1)
    draft_t = rng.integers(0, v, (r, tree.width)).astype(np.int32)
    # Rank-0 child deliberately wrong; rank-1 child right; grandchild of
    # the right child also right.
    draft_t[:, 1] = (tgt0 + 1) % v
    draft_t[:, 2] = tgt0
    tgt_at_2 = np.argmax(logits_t[:, 2], -1)
    draft_t[:, 4] = tgt_at_2  # child of node 2

    md = _sampling_md(r, 0.0)
    _, n_tree, _ = tree_rejection_sample(
        jnp.asarray(logits_t), jnp.asarray(draft_t), tree, md,
        needs_top_k=False, needs_top_p_min_p=False, needs_gumbel=False,
    )
    # Chain sees only the rank-0 branch (nodes 1, 3): same logits roles.
    logits_c = logits_t[:, [0, 1, 3]]
    draft_c = draft_t[:, [0, 1, 3]]
    _, n_chain, _ = tree_rejection_sample(
        jnp.asarray(logits_c), jnp.asarray(draft_c), chain, md,
        needs_top_k=False, needs_top_p_min_p=False, needs_gumbel=False,
    )
    n_tree, n_chain = np.asarray(n_tree), np.asarray(n_chain)
    assert (n_tree >= 3).all()  # both tree drafts accepted + bonus
    assert (n_chain == 1).all()  # chain rejects at the first draft
    assert n_tree.mean() > n_chain.mean()


def test_medusa_tree_e2e_equivalence(tmp_path_factory, tmp_path):
    """Tree verification end-to-end: untrained medusa heads propose a
    2x2 tree; greedy output must equal the plain engine (acceptance may
    be near zero — correctness is what's asserted)."""
    from safetensors.numpy import save_file
    from transformers import AutoConfig

    from tests.models.utils import tiny_llama_dir
    from tests.spec_decode.test_proposers import _run

    path = tiny_llama_dir(tmp_path_factory.mktemp("tiny_llama_tree"))
    prompts = [
        {"prompt_token_ids": [5, 6, 7, 5, 6, 7, 5, 6]},
        {"prompt_token_ids": [9, 9, 9, 9, 9, 9]},
        {"prompt_token_ids": [3, 1, 4, 1, 5, 9, 2, 6]},
    ]
    ref = _run(path, prompts)
    cfg = AutoConfig.from_pretrained(path)
    d, v = cfg.hidden_size, cfg.vocab_size
    rng = np.random.default_rng(3)
    tensors = {}
    for hk in range(2):  # depth 2 == len("2x2".split("x"))
        tensors[f"{hk}.0.linear.weight"] = (
            rng.standard_normal((d, d)).astype(np.float32) * 0.02
        )
        tensors[f"{hk}.0.linear.bias"] = np.zeros(d, np.float32)
        tensors[f"{hk}.1.weight"] = (
            rng.standard_normal((v, d)).astype(np.float32) * 0.02
        )
    heads_dir = tmp_path / "medusa_tree"
    heads_dir.mkdir()
    save_file(tensors, str(heads_dir / "model.safetensors"))
    got = _run(
        path, prompts,
        speculative_method="medusa", speculative_model=str(heads_dir),
        spec_tree="2x2", num_speculative_tokens=1,  # derived -> 6 nodes
    )
    assert got == ref


def test_medusa_tree_e2e_with_self_heads(tmp_path_factory):
    """Tree e2e where acceptance actually happens: heads distilled from
    the target model's own lm_head (head d predicts from the same hidden
    state) accept at least SOME drafts across a long greedy run, and the
    output still matches the plain engine exactly."""
    import torch
    from safetensors.numpy import save_file
    from transformers import AutoModelForCausalLM

    from tests.models.utils import tiny_llama_dir
    from tests.spec_decode.test_proposers import _run
    from vllm_tpu import LLM, SamplingParams

    base = tmp_path_factory.mktemp("tiny_llama_tree2")
    path = tiny_llama_dir(base)
    prompts = [
        {"prompt_token_ids": [5, 6, 7, 5, 6, 7, 5, 6]},
        {"prompt_token_ids": [3, 1, 4, 1, 5, 9, 2, 6]},
    ]
    ref = _run(path, prompts)
    hf = AutoModelForCausalLM.from_pretrained(path)
    w_head = hf.lm_head.weight.detach().numpy().astype(np.float32)  # [V, D]
    d, v = w_head.shape[1], w_head.shape[0]
    tensors = {}
    for hk in range(2):
        # Identity-ish residual block (zero update) + the target's own
        # head: each medusa head then proposes the model's CURRENT
        # argmax, which often matches the next-step argmax on repetitive
        # greedy continuations.
        tensors[f"{hk}.0.linear.weight"] = np.zeros((d, d), np.float32)
        tensors[f"{hk}.0.linear.bias"] = np.full(d, -1e4, np.float32)
        tensors[f"{hk}.1.weight"] = w_head
    heads_dir = base / "medusa_self"
    heads_dir.mkdir()
    save_file(tensors, str(heads_dir / "model.safetensors"))

    llm = LLM(
        model=path, dtype="float32", max_model_len=128, block_size=16,
        num_gpu_blocks_override=64, max_num_seqs=8,
        max_num_batched_tokens=128,
        speculative_method="medusa", speculative_model=str(heads_dir),
        spec_tree="2x2",
    )
    outs = llm.generate(
        prompts,
        SamplingParams(temperature=0.0, max_tokens=16, ignore_eos=True),
    )
    assert [o.outputs[0].token_ids for o in outs] == ref
    stats = llm.llm_engine.engine_core.engine_core.scheduler
    assert stats._spec_num_draft_tokens > 0
