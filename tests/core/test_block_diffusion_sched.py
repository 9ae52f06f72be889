"""The scheduler's plan under generation by diffusion over blocks, with no
model: a real Scheduler (sync and lag-N), synthetic requests and a stand-in
for the runner that keeps the device's copy of each open block and commits
by the row's rule. What is held: a clean block's store rides with the next
block's first denoising pass in one row of ``2B`` tokens wherever the step
and the model's length have room, pass numbers stay those of one pass a
step, and the lone store pass still runs where there is no room.
"""

from __future__ import annotations

import pytest

from vllm_tpu.config import CacheConfig, SchedulerConfig
from vllm_tpu.core.async_scheduler import AsyncScheduler
from vllm_tpu.core.kv_cache_utils import make_block_hasher
from vllm_tpu.core.sched_output import ModelRunnerOutput
from vllm_tpu.core.scheduler import Scheduler
from vllm_tpu.request import EngineCoreRequest, Request, RequestStatus
from vllm_tpu.sampling_params import SamplingParams

B = 4
DEPTHS = [0, 2, 3]  # 0 = one step at a time, else the lag pipeline's depth
DEPTH_IDS = ["one_step_at_a_time", "lag2", "lag3"]


def make_scheduler(depth, budget=64, max_model_len=128, num_blocks=64, max_seqs=8,
                   caching=True):
    cfg = SchedulerConfig(
        max_num_batched_tokens=budget, max_num_seqs=max_seqs, max_model_len=max_model_len,
        async_scheduling=depth > 0, async_pipeline_depth=depth or 3, block_length=B)
    cache = CacheConfig(block_size=B, enable_prefix_caching=caching)
    cache.num_gpu_blocks = num_blocks
    return (AsyncScheduler if depth else Scheduler)(cfg, cache)


def make_request(rid, prompt, max_tokens=16, rule="low_confidence_static", steps=2):
    core = EngineCoreRequest(
        request_id=rid, prompt_token_ids=list(prompt),
        sampling_params=SamplingParams(
            max_tokens=max_tokens, ignore_eos=True, temperature=0.0, remasking=rule,
            denoising_steps=steps, confidence_threshold=0.5))
    return Request.from_engine_core_request(core, make_block_hasher(B))


def token_at(position):
    return 1000 + position


class Device:
    """What the runner does to a block row, by the scheduler's own words:
    ``bp.tokens`` where the host sent them, else the previous pass's
    output; the static rule takes the rightmost open positions first (so
    commits are out of order, as confidence makes them), the sequential the
    leftmost, the dynamic every open position at once."""

    def __init__(self, scheduler):
        self.scheduler = scheduler
        self.state = {}  # rid -> (tokens, committed) after its last pass
        self.rows = []  # (rid, tokens in the row, BlockPass, first position)
        self.steps = []  # the row widths of each step that ran block rows
        self.chunks = []  # (rid, tokens, first position) of prefill chunks

    def run(self, so):
        results = {}
        for rid, bp in so.block_passes.items():
            request = self.scheduler.requests[rid]
            n = so.num_scheduled_tokens[rid]
            assert n == (2 * B if bp.denoise_next else B)
            start = self.start_of(so, rid)
            self.rows.append((rid, n, bp, start))
            if bp.tokens is not None:
                tokens, committed = list(bp.tokens), list(bp.committed)
            else:
                tokens, committed = self.state[rid]
            if bp.store:
                assert all(committed), (rid, bp)
                tokens, committed = [0] * B, [False] * B
                start += B
            if bp.denoises:
                p = request.sampling_params
                open_ = [j for j in range(B) if not committed[j]]
                take = B // p.denoising_steps
                if p.remasking == "low_confidence_static":
                    picked = open_[::-1][:take]
                elif p.remasking == "sequential":
                    picked = open_[:take]
                else:
                    picked = open_
                for j in picked:
                    tokens[j], committed[j] = token_at(start + j), True
            self.state[rid] = (tokens, committed)
            results[rid] = (list(tokens), list(committed), None)
        self.chunks += [(rid, n, self.start_of(so, rid))
                        for rid, n in so.num_scheduled_tokens.items()
                        if rid not in so.block_passes]
        if so.block_passes:
            self.steps.append({so.num_scheduled_tokens[rid] for rid in so.block_passes})
        rids = list(so.num_scheduled_tokens)
        return ModelRunnerOutput(req_ids=rids, sampled_token_ids=[[] for _ in rids],
                                 block_results=results)

    @staticmethod
    def start_of(so, rid):
        for new in so.scheduled_new_reqs:
            if new.req_id == rid:
                return new.num_computed_tokens
        cached = so.scheduled_cached_reqs
        return cached.num_computed_tokens[cached.req_ids.index(rid)]


def drive(scheduler, depth, steps=200, before_schedule=None):
    """Run the engine's loop: with a lag pipeline up to ``depth`` steps are
    scheduled and dispatched before the oldest one's result is folded in.
    Returns what streamed, by request: (tokens, commit passes)."""
    device = Device(scheduler)
    flight, streamed = [], {}
    for step in range(steps):
        if before_schedule is not None:
            before_schedule(step, scheduler, device)
        so = scheduler.schedule()
        if so.total_num_scheduled_tokens:
            flight.append((so, device.run(so)))
        if not flight:
            if not scheduler.has_unfinished_requests():
                break
            continue
        if len(flight) >= max(depth, 1) or not so.total_num_scheduled_tokens:
            done = scheduler.update_from_output(*flight.pop(0))
            for out in done.outputs:
                got = streamed.setdefault(out.req_id, ([], []))
                got[0].extend(out.new_token_ids)
                got[1].extend(out.commit_passes or [])
    assert not scheduler.has_unfinished_requests()
    return device, streamed


def reference_passes(prompt_len, max_tokens, steps, rule):
    """The pass that commits each generated position, as
    ``benchmark/reference/sdar_moe.py::generate`` numbers passes: one a
    denoising pass, and one more for a block that is complete."""
    start = prompt_len // B * B
    committed = [j < prompt_len - start for j in range(B)]
    pass_of, n_pass = {}, 0
    while start < prompt_len + max_tokens:
        open_ = [j for j in range(B) if not committed[j]]
        take = B // steps
        picked = (open_[::-1][:take] if rule == "low_confidence_static"
                  else open_[:take] if rule == "sequential" else open_)
        for j in picked:
            committed[j], pass_of[start + j] = True, n_pass
        n_pass += 1
        if all(committed):
            n_pass += 1
            start += B
            committed = [False] * B
    return [pass_of[p] for p in range(prompt_len, prompt_len + max_tokens)]


@pytest.mark.parametrize("depth", DEPTHS, ids=DEPTH_IDS)
@pytest.mark.parametrize("rule,steps", [
    ("low_confidence_static", 2), ("low_confidence_static", 1), ("sequential", 4),
    ("sequential", 2), ("low_confidence_dynamic", 4)])
def test_a_block_after_the_first_takes_its_denoising_passes_and_no_more(depth, rule, steps):
    """Five blocks: every store rides with the next block's first pass, in
    a row of 2B tokens, and the numbers under which commits are reported
    are the reference's."""
    scheduler = make_scheduler(depth)
    prompt_len, max_tokens = 8, 20  # five whole blocks
    request = make_request("a", range(prompt_len), max_tokens, rule, steps)
    scheduler.add_request(request)
    device, streamed = drive(scheduler, depth)
    tokens, passes = streamed["a"]
    assert tokens == [token_at(p) for p in range(prompt_len, prompt_len + max_tokens)]
    assert passes == reference_passes(prompt_len, max_tokens, steps, rule)
    rows = [(n, bp) for rid, n, bp, _ in device.rows]
    fused = [bp for n, bp in rows if bp.denoise_next]
    assert all(bp.store and n == 2 * B for n, bp in rows if bp.denoise_next)
    assert not [bp for _, bp in rows if bp.store and not bp.denoise_next]
    # One pass of the dynamic rule commits a whole block here.
    per_block = 1 if rule == "low_confidence_dynamic" else steps
    # The first block opens behind the prefill: its first pass is alone.
    # Of the four blocks after it, each opened in the row that stored the
    # one before; the lag pipeline may have run ahead of the last token.
    assert len(fused) >= 4
    done = [bp for _, bp in rows if bp.commit_seq <= max(passes)]
    assert len(done) == 5 * per_block
    assert [bp.seq for _, bp in rows] == sorted({bp.seq for _, bp in rows})
    for bp in fused:
        assert bp.commit_seq == bp.seq + 1 and bp.denoises


@pytest.mark.parametrize("depth", DEPTHS, ids=DEPTH_IDS)
def test_a_row_of_two_blocks_stores_one(depth):
    """``num_computed_tokens`` is what is STORED: a row of 2B advances it by
    B, and the next pass of the request starts at the second block."""
    scheduler = make_scheduler(depth)
    request = make_request("a", range(8), max_tokens=12)
    scheduler.add_request(request)
    device, streamed = drive(scheduler, depth)
    assert streamed["a"][0] == [token_at(p) for p in range(8, 20)]
    rows = [(bp.denoise_next, start) for _, _, bp, start in device.rows][:6]
    # Block 8..11 twice, then 8..15 in one row (stores 8..11), 12..15 once
    # more, then 12..19 in one row, 16..19 once more.
    assert rows == [(False, 8), (False, 8), (True, 8), (False, 12), (True, 12), (False, 16)]


@pytest.mark.parametrize("depth", DEPTHS, ids=DEPTH_IDS)
@pytest.mark.parametrize("why", ["budget", "max_model_len"])
def test_the_lone_store_pass_runs_where_there_is_no_room_for_two_blocks(depth, why):
    if why == "budget":
        # Six tokens a step hold a block and not two.
        scheduler = make_scheduler(depth, budget=6)
        prompt_len, max_tokens = 4, 12
    else:
        # The last block of the model's length has no block behind it.
        scheduler = make_scheduler(depth, max_model_len=16)
        prompt_len, max_tokens = 8, 8
    request = make_request("a", range(prompt_len), max_tokens)
    scheduler.add_request(request)
    device, streamed = drive(scheduler, depth)
    tokens, passes = streamed["a"]
    assert tokens == [token_at(p) for p in range(prompt_len, prompt_len + max_tokens)]
    assert passes == reference_passes(prompt_len, max_tokens, 2, "low_confidence_static")
    rows = [(n, bp.store, bp.denoise_next, start) for _, n, bp, start in device.rows]
    lone = [r for r in rows if r[1] and not r[2]]
    assert all(n == B for n, *_ in lone)
    if why == "budget":
        assert lone and not [r for r in rows if r[2]]
        assert len(lone) >= 2
    else:
        # Block 8..11 is stored beside 12..15's first pass; 12..15's own
        # store, if the pipeline got that far, has no room for a next.
        assert (2 * B, True, True, 8) in rows
        assert all(start == 12 for *_, start in lone)
        assert all(start + n <= 16 for n, _, _, start in rows)


@pytest.mark.parametrize("depth", DEPTHS, ids=DEPTH_IDS)
def test_a_step_with_room_for_one_more_block_gives_the_lone_store_to_the_last_row(depth):
    """Twenty tokens a step and three requests whose blocks are clean at
    once: two rows of 2B, and B for the third, which stores alone."""
    scheduler = make_scheduler(depth, budget=20)
    for rid in "abc":
        scheduler.add_request(make_request(rid, range(4), max_tokens=8))
    device, streamed = drive(scheduler, depth)
    for rid in "abc":
        assert streamed[rid][0] == [token_at(p) for p in range(4, 12)]
        assert streamed[rid][1] == reference_passes(4, 8, 2, "low_confidence_static")
    kinds = {(bp.store, bp.denoise_next) for _, _, bp, _ in device.rows}
    assert kinds == {(False, False), (True, False), (True, True)}


@pytest.mark.parametrize("depth", DEPTHS, ids=DEPTH_IDS)
def test_a_preemption_with_a_row_of_two_blocks_in_flight_resumes_with_the_same_tokens(depth):
    """The request is preempted right after its row of two blocks is
    scheduled: what that pass committed of the next block still lands in
    the host's view, the new prefill covers the stored block, and the
    block it held reopens with those tokens."""
    scheduler = make_scheduler(depth, caching=False)
    request = make_request("a", range(8), max_tokens=12)
    scheduler.add_request(request)

    def preempt(step, sched, device):
        if request.num_preemptions or not device.rows or not device.rows[-1][2].denoise_next:
            return
        if depth and request.num_inflight_steps == 0:
            return  # landed already: the case of the sync scheduler
        sched.running.remove(request)
        sched._preempt(request)

    device, streamed = drive(scheduler, depth, before_schedule=preempt)
    assert request.num_preemptions == 1
    assert streamed["a"][0] == [token_at(p) for p in range(8, 20)]
    # The prefill after the preemption ends where the open block starts:
    # the stored block is under it, the open one is not; and the block
    # reopens from the host's view, with what the row of two committed.
    assert device.chunks == [("a", 8, 0), ("a", 12, 0)]
    reopened = [bp for _, _, bp, start in device.rows if start == 12 and bp.tokens][0]
    assert not reopened.store and reopened.committed == [False, False, True, True]
    assert reopened.tokens[2:] == [token_at(14), token_at(15)]
    assert request.status == RequestStatus.FINISHED_LENGTH_CAPPED


@pytest.mark.parametrize("depth", DEPTHS, ids=DEPTH_IDS)
def test_the_prefix_cache_holds_stored_blocks_and_never_the_open_one(depth):
    """After a row of two blocks has run, a second request with the same
    tokens hits the prefix up to the block that row STORED; the block it
    opened, whose slots the next pass overwrites, is not in the cache."""
    scheduler = make_scheduler(depth)
    first = make_request("a", range(8), max_tokens=6)  # ends inside block 12..15
    scheduler.add_request(first)
    device, streamed = drive(scheduler, depth)
    tokens = list(range(8)) + streamed["a"][0]
    assert len(tokens) == 14
    # Block 8..11 was stored by the row 8..15; 12..15 never was.
    assert (True, 8) in [(bp.denoise_next, start) for _, _, bp, start in device.rows]
    again = make_request("b", tokens + [7, 7, 7, 7, 7, 7], max_tokens=4)
    scheduler.add_request(again)
    so = scheduler.schedule()
    assert so.scheduled_new_reqs[0].num_computed_tokens == 12
    assert so.num_scheduled_tokens["b"] == 8  # the prompt's 12..19


@pytest.mark.parametrize("depth", DEPTHS, ids=DEPTH_IDS)
def test_rules_share_a_step_with_rows_of_one_block_and_of_two(depth):
    """A dynamic row beside static and sequential ones: steps hold rows of
    B and of 2B together, a dynamic row is never scheduled ahead of its
    result, and each request's commits carry the reference's numbers."""
    scheduler = make_scheduler(depth)
    cases = {"s2": ("low_confidence_static", 2, 9), "q4": ("sequential", 4, 6),
             "d4": ("low_confidence_dynamic", 4, 11), "s1": ("low_confidence_static", 1, 4)}
    requests = {}
    for rid, (rule, steps, prompt_len) in cases.items():
        requests[rid] = make_request(rid, range(prompt_len), 14, rule, steps)
        scheduler.add_request(requests[rid])

    def watch(step, sched, device):
        assert requests["d4"].num_inflight_steps <= 1

    device, streamed = drive(scheduler, depth, before_schedule=watch)
    for rid, (rule, steps, prompt_len) in cases.items():
        assert streamed[rid][0] == [token_at(p) for p in range(prompt_len, prompt_len + 14)]
        assert streamed[rid][1] == reference_passes(prompt_len, 14, steps, rule)
    assert {B, 2 * B} in device.steps
    by_rid = {}
    for rid, n, bp, _ in device.rows:
        by_rid.setdefault(rid, set()).add(n)
    assert all(sizes == {B, 2 * B} for sizes in by_rid.values())
    # One pass a block commits it whole: after its first block every row
    # of that request is a row of two.
    assert [n for rid, n, _, _ in device.rows if rid == "s1"].count(B) == 1
