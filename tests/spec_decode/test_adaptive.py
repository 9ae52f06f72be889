"""Adaptive speculation: controller state machine, suffix-corpus
sharing wire format, and the e2e token-identity safety invariant.

The controller tests drive a fake clock and scripted occupancy — no
engine, no jax beyond the lazy per-position helper. The corpus-share
tests run a real PeerServer/PeerClient pair over localhost. The e2e
test proves the whole point of the design: adaptation changes
*proposals only*, so greedy decoding with the controller on is
token-identical to static drafting.
"""

from __future__ import annotations

import numpy as np
import pytest

from vllm_tpu.spec_decode.adaptive import (
    AdaptiveSpecController,
    SuffixCorpusShare,
)


class FakeClock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def make_ctl(k=4, **kw) -> tuple[AdaptiveSpecController, FakeClock]:
    clock = FakeClock()
    kw.setdefault("ema_half_life_s", 10.0)
    return AdaptiveSpecController(k, clock=clock, **kw), clock


# ----------------------------------------------------------------------
# Ratchet
# ----------------------------------------------------------------------


def test_first_request_drafts_at_full_budget():
    ctl, _ = make_ctl(k=4)
    # No evidence anywhere: optimistic full budget.
    assert ctl.draft_budget("r0") == 4


def test_ratchet_up_on_high_acceptance():
    ctl, clock = make_ctl(k=4)
    ctl.observe("r0", 4, 1)  # 25% -> ema below up threshold
    clock.advance(1.0)
    b0 = ctl.request_budget("r0")
    for _ in range(20):
        ctl.observe("r0", 4, 4)  # everything accepted
        clock.advance(1.0)
    assert ctl.request_budget("r0") == 4
    assert ctl.request_budget("r0") >= b0
    assert ctl.draft_budget("r0") == 4


def test_ratchet_down_on_rejection():
    ctl, clock = make_ctl(k=4)
    for _ in range(10):
        ctl.observe("r0", 4, 0)  # nothing ever accepted
        clock.advance(1.0)
    assert ctl.request_budget("r0") == 0
    assert ctl.draft_budget("r0") == 0


def test_zero_budget_probe_recovers():
    ctl, clock = make_ctl(k=4, probe_interval_s=5.0)
    for _ in range(10):
        ctl.observe("r0", 4, 0)
        clock.advance(1.0)
    assert ctl.draft_budget("r0") == 0
    # Before the probe interval: still shut off.
    clock.advance(1.0)
    assert ctl.draft_budget("r0") == 0
    # After it: one probe unit, so the request can regenerate evidence.
    clock.advance(5.0)
    assert ctl.draft_budget("r0") == 1
    # Text turned predictable: the probe's acceptance climbs the budget
    # back up.
    for _ in range(20):
        ctl.observe("r0", 1, 1)
        clock.advance(1.0)
    assert ctl.request_budget("r0") == 4


def test_new_request_seeds_from_global_ema():
    ctl, clock = make_ctl(k=4)
    # Fleet evidence says ~25% acceptance.
    for _ in range(10):
        ctl.observe("r0", 4, 1)
        clock.advance(1.0)
    rate = ctl.acceptance_rate()
    assert rate is not None and rate < 0.5
    # A fresh request starts near the fleet rate, not at full budget.
    seeded = ctl.draft_budget("r-new")
    assert 1 <= seeded <= 2


def test_forget_drops_request_state():
    ctl, clock = make_ctl(k=4)
    for _ in range(10):
        ctl.observe("r0", 4, 0)
        clock.advance(1.0)
    assert ctl.request_budget("r0") == 0
    ctl.forget("r0")
    assert ctl.request_budget("r0") is None


def test_parameter_validation():
    with pytest.raises(ValueError):
        AdaptiveSpecController(0)
    with pytest.raises(ValueError):
        AdaptiveSpecController(4, high_watermark=0.5, low_watermark=0.6)
    with pytest.raises(ValueError):
        AdaptiveSpecController(4, up_threshold=0.3, down_threshold=0.4)


# ----------------------------------------------------------------------
# Occupancy gate (scripted suspension fire + recover)
# ----------------------------------------------------------------------


def test_occupancy_suspension_fires_and_recovers():
    ctl, _ = make_ctl(k=4, high_watermark=0.85, low_watermark=0.60)
    assert ctl.draft_budget("r0") == 4
    # Batch fills past the high watermark: speculation suspends.
    assert ctl.observe_occupancy(0.90) is True
    assert ctl.suspended and ctl.suspensions_total == 1
    assert ctl.draft_budget("r0") == 0
    # Drains below the low watermark: resumes at the learned budget.
    assert ctl.observe_occupancy(0.50) is False
    assert not ctl.suspended
    assert ctl.draft_budget("r0") == 4
    assert ctl.suspensions_total == 1


def test_hysteresis_band_does_not_flap():
    ctl, _ = make_ctl(k=4, high_watermark=0.85, low_watermark=0.60)
    # Oscillating inside the band never changes state in either
    # direction from either side.
    for occ in (0.70, 0.80, 0.65, 0.84):
        assert ctl.observe_occupancy(occ) is False
    ctl.observe_occupancy(0.90)
    for occ in (0.80, 0.65, 0.61, 0.84):
        assert ctl.observe_occupancy(occ) is True
    assert ctl.suspensions_total == 1
    ctl.observe_occupancy(0.30)
    ctl.observe_occupancy(0.90)
    assert ctl.suspensions_total == 2


# ----------------------------------------------------------------------
# Per-position surfacing + tree pruning
# ----------------------------------------------------------------------


def test_per_position_acceptance_chain():
    from vllm_tpu.sample.rejection_sampler import per_position_acceptance

    assert per_position_acceptance(4, 2) == [True, True, False, False]
    assert per_position_acceptance(3, 3) == [True, True, True]
    assert per_position_acceptance(0, 0) == []


def test_per_position_acceptance_tree():
    from vllm_tpu.sample.rejection_sampler import per_position_acceptance
    from vllm_tpu.spec_decode.tree import build_tree

    tree = build_tree("2x2")  # 6 nodes: 2 at depth 1, 4 at depth 2
    # Full tree scheduled, path accepted to depth 1.
    assert per_position_acceptance(6, 1, tree=tree) == [True, False]
    # Pruned to the depth-1 level prefix (2 nodes): one level entry.
    assert per_position_acceptance(2, 1, tree=tree) == [True]


def test_tree_budget_counts_node_prefixes():
    from vllm_tpu.spec_decode.tree import build_tree

    tree = build_tree("2x2")
    ctl, clock = make_ctl(k=6, tree=tree)
    # Optimistic default: the whole tree.
    assert ctl.draft_budget("r0") == 6
    # Depth 1 always accepted, depth 2 never: the per-depth curve prunes
    # scheduling to the depth-1 node prefix (2 nodes) even though the
    # request-level ratchet would allow more.
    for _ in range(12):
        ctl.observe("r0", 6, 1)
        clock.advance(1.0)
    assert ctl.draft_budget("r0") == 2
    curve = ctl.position_curve()
    assert curve[0] is not None and curve[0] > 0.9
    assert curve[1] is not None and curve[1] < 0.15


@pytest.mark.parametrize("temp", [0.0, 0.8])
def test_tree_rejection_prune_is_a_noop_for_full_budgets(temp):
    """tree_rejection_sample(num_draft=full) must be bit-identical to
    the pre-pruning behavior (num_draft=None)."""
    import jax.numpy as jnp

    from tests.spec_decode.test_ngram_spec import _sampling_md
    from vllm_tpu.sample.tree_rejection import tree_rejection_sample
    from vllm_tpu.spec_decode.tree import build_tree

    tree = build_tree("2x2")
    r, w, v = 3, tree.width, 32
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((r, w, v)), jnp.float32)
    drafts = jnp.asarray(rng.integers(0, v, (r, w)), jnp.int32)
    md = _sampling_md(r, temp)
    kw = dict(needs_top_k=False, needs_top_p_min_p=False)
    out_a, num_a, kv_a = tree_rejection_sample(
        logits, drafts, tree, md, **kw)
    out_b, num_b, kv_b = tree_rejection_sample(
        logits, drafts, tree, md,
        num_draft=jnp.full((r,), tree.num_nodes, jnp.int32), **kw)
    assert (np.asarray(out_a) == np.asarray(out_b)).all()
    assert (np.asarray(num_a) == np.asarray(num_b)).all()
    assert (np.asarray(kv_a) == np.asarray(kv_b)).all()


def test_tree_rejection_pruned_rows_never_accept_past_budget():
    import jax.numpy as jnp

    from tests.spec_decode.test_ngram_spec import _sampling_md
    from vllm_tpu.sample.tree_rejection import tree_rejection_sample
    from vllm_tpu.spec_decode.tree import build_tree

    tree = build_tree("2x2")
    r, w, v = 2, tree.width, 16
    # Greedy rows whose drafts all match the target: an unpruned row
    # accepts a full depth-2 path (3 tokens out); a row pruned to the
    # depth-1 prefix can accept at most depth 1 (2 tokens out).
    logits = np.full((r, w, v), -10.0, np.float32)
    logits[:, :, 5] = 10.0  # target argmax is token 5 everywhere
    drafts = np.full((r, w), 5, np.int32)
    md = _sampling_md(r, 0.0)
    out, num, _ = tree_rejection_sample(
        jnp.asarray(logits), jnp.asarray(drafts), tree, md,
        num_draft=jnp.asarray([tree.num_nodes, 2], jnp.int32),
        needs_top_k=False, needs_top_p_min_p=False,
    )
    num = np.asarray(num)
    assert num[0] == tree.num_levels + 1
    assert num[1] == 2  # depth-1 accept + bonus, never past the prefix
    assert (np.asarray(out)[1, :2] == 5).all()


# ----------------------------------------------------------------------
# Suffix-corpus sharing
# ----------------------------------------------------------------------


class RecordingProposer:
    def __init__(self) -> None:
        self.seqs: list[np.ndarray] = []

    def observe_finished(self, seq) -> None:
        self.seqs.append(np.asarray(seq))


def _server_with_sink(share: SuffixCorpusShare):
    from vllm_tpu.kv_fabric.peer import PeerServer

    server = PeerServer(tier=object()).start()
    server.corpus_sink = lambda header, body: share.ingest(
        SuffixCorpusShare.decode_frame(header, body))
    return server


def _fast_client(url):
    from vllm_tpu.kv_fabric.peer import PeerClient

    return PeerClient(url, timeout_s=2.0, max_retries=0, backoff_s=0.01)


def test_corpus_share_roundtrip_and_dedup():
    rx_prop = RecordingProposer()
    rx = SuffixCorpusShare(rx_prop, async_flush=False)
    server = _server_with_sink(rx)
    try:
        tx = SuffixCorpusShare(
            RecordingProposer(), [server.url],
            client_factory=_fast_client, async_flush=False)
        seq = list(range(20))
        tx.observe(seq)
        tx.observe(seq)  # duplicate: dropped sender-side
        tx.observe([1, 2])  # below min_seq_len: dropped
        assert tx.flush() == 1
        assert tx.shared_out == 1 and tx.dropped_dup == 1
        assert rx.ingested == 1
        assert [s.tolist() for s in rx_prop.seqs] == [seq]
        # Receiver-side dedup: the same sequence arriving again (e.g.
        # bounced via another peer) folds in at most once.
        tx2 = SuffixCorpusShare(
            RecordingProposer(), [server.url],
            client_factory=_fast_client, async_flush=False)
        tx2.observe(seq)
        assert tx2.flush() == 1
        assert rx.ingested == 1 and rx.dropped_dup == 1
        tx.close()
        tx2.close()
    finally:
        server.shutdown()
        rx.close()


def test_corpus_share_truncates_and_bounds_pending():
    tx = SuffixCorpusShare(
        RecordingProposer(), ["127.0.0.1:1"],
        max_seq_len=8, max_pending=2,
        client_factory=_fast_client, async_flush=False)
    long_seq = list(range(100))
    tx.observe(long_seq)
    assert len(tx._pending) == 1 and len(tx._pending[0]) == 8
    assert tx._pending[0].tolist() == long_seq[-8:]
    tx.observe(list(range(10, 30)))
    tx.observe(list(range(40, 60)))  # overflows the pending bound
    assert len(tx._pending) == 2
    assert tx.dropped_overflow == 1
    tx.close()


def test_peer_death_degrades_to_local_only():
    rx = SuffixCorpusShare(RecordingProposer(), async_flush=False)
    server = _server_with_sink(rx)
    tx = SuffixCorpusShare(
        RecordingProposer(), [server.url],
        client_factory=_fast_client, async_flush=False)
    try:
        tx.observe(list(range(20)))
        assert tx.flush() == 1
        # Peer dies mid-share: the next flush counts the failure, drops
        # the client, and the share degrades to local-only (observe
        # becomes a no-op) instead of erroring the serving path.
        server.shutdown()
        tx.observe(list(range(30, 60)))
        assert tx.flush() == 0
        assert tx.peer_failures == 1
        assert tx.local_only
        tx.observe(list(range(60, 90)))
        assert len(tx._pending) == 0
        assert tx.stats()["peers"] == 0
    finally:
        tx.close()
        rx.close()
        server.shutdown()


def test_decode_frame_rejects_length_mismatch():
    blob = np.arange(5, dtype=np.int32).tobytes()
    with pytest.raises(ValueError):
        SuffixCorpusShare.decode_frame({"lens": [3, 3]}, blob)
    out = SuffixCorpusShare.decode_frame({"lens": [2, 3]}, blob)
    assert [s.tolist() for s in out] == [[0, 1], [2, 3, 4]]


def test_corpus_put_without_sink_is_an_error_not_a_crash():
    from vllm_tpu.kv_fabric.peer import PeerServer

    server = PeerServer(tier=object()).start()
    try:
        client = _fast_client(server.url)
        with pytest.raises(ConnectionError):
            client.corpus_put(
                {"lens": [3]}, np.arange(3, dtype=np.int32).tobytes())
        client.close()
    finally:
        server.shutdown()


# ----------------------------------------------------------------------
# Config plumbing
# ----------------------------------------------------------------------


def test_spec_adaptive_requires_spec():
    from vllm_tpu.engine.arg_utils import EngineArgs

    with pytest.raises(ValueError, match="spec-adaptive requires"):
        EngineArgs(
            model="dummy-llama", spec_adaptive=True
        ).create_engine_config()


def test_adaptive_watermarks_validated_at_config_time():
    from vllm_tpu.engine.arg_utils import EngineArgs

    with pytest.raises(ValueError, match="watermark"):
        EngineArgs(
            model="dummy-llama", speculative_method="ngram",
            num_speculative_tokens=3, spec_adaptive=True,
            spec_adaptive_high_watermark=0.5,
            spec_adaptive_low_watermark=0.6,
        ).create_engine_config()


def test_adaptive_knobs_reach_scheduler_config():
    from vllm_tpu.engine.arg_utils import EngineArgs

    cfg = EngineArgs(
        model="dummy-llama", speculative_method="ngram",
        num_speculative_tokens=3, spec_adaptive=True,
        spec_adaptive_ema_half_life_s=5.0,
    ).create_engine_config()
    sc = cfg.scheduler_config
    assert sc.spec_adaptive is True
    assert sc.spec_num_speculative_tokens == 3
    assert sc.spec_adaptive_ema_half_life_s == 5.0


# ----------------------------------------------------------------------
# E2E: adaptation never changes accepted text
# ----------------------------------------------------------------------


@pytest.mark.slow
def test_e2e_adaptive_greedy_identical_to_static(tmp_path):
    from tests.models.utils import tiny_llama_dir
    from vllm_tpu import LLM, SamplingParams

    path = tiny_llama_dir(tmp_path / "ck")
    prompts = [
        {"prompt_token_ids": [5, 6, 7, 5, 6, 7, 5, 6]},
        {"prompt_token_ids": [9, 9, 9, 9, 9, 9]},
        {"prompt_token_ids": [3, 1, 4, 1, 5, 9, 2, 6]},
    ]
    params = SamplingParams(temperature=0.0, max_tokens=16, ignore_eos=True)

    results = {}
    for adaptive in (False, True):
        llm = LLM(
            model=path, dtype="float32", max_model_len=128, block_size=16,
            num_gpu_blocks_override=64, max_num_seqs=8,
            max_num_batched_tokens=128,
            speculative_method="ngram", num_speculative_tokens=3,
            spec_adaptive=adaptive,
        )
        outs = llm.generate(prompts, params)
        results[adaptive] = [o.outputs[0].token_ids for o in outs]
        core = llm.llm_engine.engine_core.engine_core
        assert (core.scheduler.adaptive_spec is not None) == adaptive

    assert results[True] == results[False]
