"""Scheduler behavior tests — protocol of reference tests/v1/core/test_scheduler.py."""

from tests.core.utils import EOS, create_request, create_scheduler, make_runner_output
from vllm_tpu.core.sched_output import ModelRunnerOutput
from vllm_tpu.request import RequestStatus


def test_schedule_new_requests_full_prefill():
    sched = create_scheduler()
    reqs = [create_request(prompt_len=50) for _ in range(3)]
    for r in reqs:
        sched.add_request(r)
    out = sched.schedule()
    assert len(out.scheduled_new_reqs) == 3
    assert out.total_num_scheduled_tokens == 150
    assert all(out.num_scheduled_tokens[r.request_id] == 50 for r in reqs)
    assert len(sched.running) == 3
    # Block allocation covers the prompt.
    for r in reqs:
        assert len(out.scheduled_new_reqs[0].block_ids[0]) >= 50 // 16


def test_chunked_prefill_respects_token_budget():
    sched = create_scheduler(max_num_batched_tokens=64)
    req = create_request(prompt_len=100)
    sched.add_request(req)
    out = sched.schedule()
    assert out.num_scheduled_tokens[req.request_id] == 64
    # Partial prefill: no tokens sampled.
    sched.update_from_output(
        out, ModelRunnerOutput(req_ids=[req.request_id], sampled_token_ids=[[]])
    )
    assert req.num_computed_tokens == 64
    out2 = sched.schedule()
    assert out2.num_scheduled_tokens[req.request_id] == 36
    assert out2.scheduled_cached_reqs.req_ids == [req.request_id]


def test_budget_shared_across_requests():
    sched = create_scheduler(max_num_batched_tokens=100)
    r1 = create_request(prompt_len=80)
    r2 = create_request(prompt_len=60)
    sched.add_request(r1)
    sched.add_request(r2)
    out = sched.schedule()
    assert out.num_scheduled_tokens[r1.request_id] == 80
    assert out.num_scheduled_tokens[r2.request_id] == 20  # chunked
    assert out.total_num_scheduled_tokens == 100


def test_decode_after_prefill_and_eos_stop():
    sched = create_scheduler()
    req = create_request(prompt_len=10, max_tokens=8)
    sched.add_request(req)
    out = sched.schedule()
    eco = sched.update_from_output(out, make_runner_output(out, token_id=100))
    assert eco.outputs[0].new_token_ids == [100]
    assert req.num_tokens == 11

    # Decode step schedules exactly 1 token.
    out2 = sched.schedule()
    assert out2.num_scheduled_tokens[req.request_id] == 1
    # Model emits EOS -> request finishes with "stop".
    eco2 = sched.update_from_output(out2, make_runner_output(out2, token_id=EOS))
    assert eco2.outputs[0].finish_reason == "stop"
    assert not sched.has_unfinished_requests()
    # All blocks returned.
    assert sched.kv_cache_manager.get_num_free_blocks() == 999


def test_max_tokens_length_cap():
    sched = create_scheduler()
    req = create_request(prompt_len=5, max_tokens=2)
    sched.add_request(req)
    out = sched.schedule()
    sched.update_from_output(out, make_runner_output(out, token_id=7))
    out = sched.schedule()
    eco = sched.update_from_output(out, make_runner_output(out, token_id=8))
    assert eco.outputs[0].finish_reason == "length"
    assert req.status == RequestStatus.FINISHED_LENGTH_CAPPED


def test_stop_token_ids_sets_stop_reason():
    sched = create_scheduler()
    req = create_request(prompt_len=5, max_tokens=10, stop_token_ids=[77])
    sched.add_request(req)
    out = sched.schedule()
    eco = sched.update_from_output(out, make_runner_output(out, token_id=77))
    assert eco.outputs[0].finish_reason == "stop"
    assert eco.outputs[0].stop_reason == 77


def test_min_tokens_suppresses_eos():
    sched = create_scheduler()
    req = create_request(prompt_len=5, max_tokens=10, min_tokens=3)
    sched.add_request(req)
    out = sched.schedule()
    eco = sched.update_from_output(out, make_runner_output(out, token_id=EOS))
    assert eco.outputs[0].finish_reason is None  # min_tokens not reached
    assert len(sched.running) == 1


def test_max_num_seqs_limits_admission():
    sched = create_scheduler(max_num_seqs=2)
    reqs = [create_request(prompt_len=10) for _ in range(4)]
    for r in reqs:
        sched.add_request(r)
    out = sched.schedule()
    assert len(out.scheduled_new_reqs) == 2
    assert len(sched.waiting) == 2


def test_preemption_on_kv_exhaustion():
    # 10 usable blocks of 16 tokens = 160 token capacity.
    sched = create_scheduler(num_blocks=11, block_size=16, max_num_batched_tokens=256)
    r1 = create_request(prompt_len=79, max_tokens=50)  # 5 blocks, fills to 80
    r2 = create_request(prompt_len=79, max_tokens=50)
    sched.add_request(r1)
    sched.add_request(r2)
    out = sched.schedule()
    assert len(out.scheduled_new_reqs) == 2
    # Decode until the pool is exhausted; r2 (tail) must get preempted.
    preempted = False
    for _ in range(40):
        out = sched.schedule()
        if r2.status == RequestStatus.PREEMPTED:
            preempted = True
            break
        sched.update_from_output(out, make_runner_output(out, token_id=50))
    assert preempted
    assert r2.num_computed_tokens == 0
    assert len(sched.running) == 1
    # r1 keeps decoding; r2 waits for space.
    assert len(sched.waiting) == 1


def test_preempted_request_resumes_with_token_ids():
    sched = create_scheduler(num_blocks=11, block_size=16, max_num_batched_tokens=256)
    r1 = create_request(prompt_len=79, max_tokens=60)
    r2 = create_request(prompt_len=79, max_tokens=4)
    sched.add_request(r1)
    sched.add_request(r2)
    # prefill both
    out = sched.schedule()
    sched.update_from_output(out, make_runner_output(out, token_id=50))
    # run until r2 finishes (frees space) or r2 preempted
    for _ in range(10):
        out = sched.schedule()
        sched.update_from_output(out, make_runner_output(out, token_id=50))
        if r2.is_finished or r2.status == RequestStatus.PREEMPTED:
            break
    # Keep scheduling; if r2 was preempted it should eventually resume and the
    # resumed record must carry full token ids.
    for _ in range(30):
        out = sched.schedule()
        cached = out.scheduled_cached_reqs
        for i, rid in enumerate(cached.req_ids):
            if cached.resumed_from_preemption[i]:
                assert cached.resumed_req_token_ids[i] is not None
                assert len(cached.resumed_req_token_ids[i]) >= 79
        if not sched.has_unfinished_requests():
            break
        sched.update_from_output(out, make_runner_output(out, token_id=50))


def test_finish_requests_abort():
    sched = create_scheduler()
    req = create_request(prompt_len=10)
    sched.add_request(req)
    out = sched.schedule()
    sched.finish_requests(req.request_id, RequestStatus.FINISHED_ABORTED)
    assert not sched.has_unfinished_requests()
    assert sched.kv_cache_manager.get_num_free_blocks() == 999
    # Next schedule reports it for runner cleanup.
    out2 = sched.schedule()
    assert req.request_id in out2.finished_req_ids


def test_priority_policy_orders_waiting_queue():
    sched = create_scheduler(max_num_seqs=1, policy="priority")
    lo = create_request(prompt_len=8, priority=10)
    hi = create_request(prompt_len=8, priority=0)
    sched.add_request(lo)
    sched.add_request(hi)
    out = sched.schedule()
    assert out.scheduled_new_reqs[0].req_id == hi.request_id


def test_spec_decode_accept_reject_accounting():
    sched = create_scheduler()
    req = create_request(prompt_len=10, max_tokens=20)
    sched.add_request(req)
    out = sched.schedule()
    # Prefill sampled token 100, runner proposes drafts [5, 6].
    sched.update_from_output(
        out,
        ModelRunnerOutput(
            req_ids=[req.request_id],
            sampled_token_ids=[[100]],
            draft_token_ids={req.request_id: [5, 6]},
        ),
    )
    assert req.spec_token_ids == [5, 6]
    out2 = sched.schedule()
    # Verification step covers last real token + 2 drafts.
    assert out2.num_scheduled_tokens[req.request_id] == 3
    assert out2.scheduled_spec_decode_tokens[req.request_id] == [5, 6]
    # Model accepts first draft, rejects second: emits [5, 42].
    sched.update_from_output(
        out2,
        ModelRunnerOutput(
            req_ids=[req.request_id], sampled_token_ids=[[5, 42]]
        ),
    )
    # 1 draft rejected -> computed rolled back by 1: computed = tokens - 1.
    assert req.num_tokens == 13  # 10 prompt + 100, 5, 42
    assert req.num_computed_tokens == req.num_tokens - 1


def test_prefix_cache_hit_on_shared_prefix():
    sched = create_scheduler(block_size=16)
    prompt = list(range(100, 164))  # 4 full blocks
    r1 = create_request(prompt_token_ids=prompt, max_tokens=2)
    sched.add_request(r1)
    out = sched.schedule()
    sched.update_from_output(out, make_runner_output(out, token_id=7))
    out = sched.schedule()
    eco = sched.update_from_output(out, make_runner_output(out, token_id=8))
    assert not sched.has_unfinished_requests()

    # Same prompt again: blocks are cached -> big hit.
    r2 = create_request(prompt_token_ids=prompt, max_tokens=2)
    sched.add_request(r2)
    out2 = sched.schedule()
    # 64 tokens, 4 blocks cached but hit capped at num_tokens-1 -> 48 cached.
    assert r2.num_cached_tokens == 48
    assert out2.num_scheduled_tokens[r2.request_id] == 64 - 48


def test_prefix_cache_disabled():
    sched = create_scheduler(enable_prefix_caching=False)
    prompt = list(range(100, 164))
    r1 = create_request(prompt_token_ids=prompt, max_tokens=2)
    sched.add_request(r1)
    out = sched.schedule()
    sched.update_from_output(out, make_runner_output(out, token_id=7))
    out = sched.schedule()
    sched.update_from_output(out, make_runner_output(out, token_id=8))
    r2 = create_request(prompt_token_ids=prompt, max_tokens=2)
    sched.add_request(r2)
    out2 = sched.schedule()
    assert out2.num_scheduled_tokens[r2.request_id] == 64


def test_spec_all_or_nothing_trim():
    """Tree spec mode: a budget that truncates the draft tree drops the
    drafts entirely (a partial tree is unverifiable) instead of
    scheduling a prefix of them."""
    sched = create_scheduler(max_num_batched_tokens=4)
    sched.config.spec_all_or_nothing = True
    req = create_request(prompt_len=8, max_tokens=16)
    sched.add_request(req)
    out = sched.schedule()  # prefill chunk (4 of 8)
    sched.update_from_output(
        out, ModelRunnerOutput(req_ids=[req.request_id], sampled_token_ids=[[]])
    )
    out = sched.schedule()  # rest of prefill
    sched.update_from_output(out, make_runner_output(out))
    # 6 drafts + 1 input token > 4-token budget -> drafts dropped.
    req.spec_token_ids = [11, 12, 13, 14, 15, 16]
    out = sched.schedule()
    assert req.request_id not in out.scheduled_spec_decode_tokens
    assert out.num_scheduled_tokens[req.request_id] == 1
    sched.update_from_output(out, make_runner_output(out))
    # A budget that fits the whole tree schedules all of it.
    sched.config.max_num_batched_tokens = 64
    req.spec_token_ids = [11, 12, 13, 14, 15, 16]
    out = sched.schedule()
    assert out.scheduled_spec_decode_tokens[req.request_id] == (
        [11, 12, 13, 14, 15, 16]
    )
    assert out.num_scheduled_tokens[req.request_id] == 7
