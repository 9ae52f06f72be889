"""The scorer's arithmetic, on records written down by hand."""

from __future__ import annotations

import pytest

from benchmark.harness import score as S
from benchmark.harness.client import Record, parse_prometheus


def rec(due, chunks, max_tokens, sent=None, scored=True, finish="length", done=True,
        error=None, prompt=10, status=200):
    return Record(idx=0, scored=scored, prompt_tokens=prompt, max_tokens=max_tokens, due=due,
                  sent=due if sent is None else sent, chunks=list(chunks), status=status,
                  finish=finish, error=error, done=done)


def test_nearest_rank_percentile():
    v = [float(i) for i in range(1, 101)]
    assert S.percentile(v, 0.5) == 50 and S.percentile(v, 0.95) == 95
    assert S.percentile(v, 0.0) == 1 and S.percentile(v, 1.0) == 100
    assert S.percentile([], 0.5) is None
    assert S.percentile([3.0], 0.95) == 3.0


def test_a_request_is_timed_from_when_it_was_due_not_from_when_it_was_sent():
    # Due at 1.0, sent 0.4 late, first token 0.1 after the send.
    r = rec(1.0, [1.5, 1.6], 2, sent=1.4)
    out = S.score([r], seconds=10.0, chips=1)
    assert out["metrics"]["ttft_p50_ms"] == pytest.approx(500.0)
    assert out["facts"]["gen_late_p95_ms"] == pytest.approx(400.0)
    assert out["facts"]["client_ttft_from_send_mean_ms"] == pytest.approx(100.0)


def test_tokens_in_one_chunk_have_the_chunks_wait_and_then_zero():
    # 6 tokens in 3 chunks: the gaps before tokens 2..6 are 0.3 and 0.5
    # (the chunks' waits) and three zeros; never five short gaps.
    r = rec(0.0, [0.1, 0.4, 0.9], 6)
    assert sorted(S.token_gaps(r)) == pytest.approx([0.0, 0.0, 0.0, 0.3, 0.5])
    one = rec(0.0, [0.1, 0.2, 0.3], 3)
    assert S.token_gaps(one) == pytest.approx([0.1, 0.1])


def test_a_failed_request_is_slower_than_any_percentile():
    good = [rec(float(i), [i + 0.1, i + 0.2], 2) for i in range(18)]
    bad = [rec(18.0, [], 2, done=False, error="boom"), rec(19.0, [19.5], 2, done=False)]
    out = S.score(good + bad, seconds=30.0, chips=1)
    assert out["attempted"] == 20 and out["failed"] == 2
    assert out["facts"]["tail_is_a_failure"] == {"ttft_p50": False, "ttft_p95": True,
                                                 "itl_p95": True, "itl_p50": False}
    # The number printed is the largest that finished, never a small one.
    assert out["metrics"]["ttft_p95_ms"] == pytest.approx(100.0)
    assert out["metrics"]["ttft_p50_ms"] == pytest.approx(100.0)
    all_bad = S.score(bad, seconds=30.0, chips=1)
    assert all_bad["failed"] == 2 and all_bad["facts"]["tail_is_a_failure"]["ttft_p50"]


def stream(due, first, gap, chunks, max_tokens, **kw):
    """A request whose chunks arrive ``gap`` seconds apart."""
    return rec(due, [first + gap * i for i in range(chunks)], max_tokens, **kw)


@pytest.mark.parametrize("records,p50_ms,p95_ms", [
    # One token a chunk: 18 decode-only steps of 20 ms and two mixed steps of
    # 60 ms in each of 5 requests. The median is the step that runs most
    # often, the percentile the slow one.
    ([rec(0.0, [0.1 + 0.02 * i + 0.04 * ((i > 5) + (i > 15)) for i in range(21)], 21)
      for _ in range(5)], 20.0, 60.0),
    # Two tokens a chunk (a diffusion pass commits two): of a request's 19
    # gaps 9 are a pass and 10 are 0, so the median is 0 and says nothing.
    ([stream(0.0, 0.1, 0.06, 10, 20) for _ in range(5)], 0.0, 60.0),
    # A failed request's missing tokens rank last: 6 gaps of 20 ms, then 7
    # tokens that never came. The median falls among them and prints the
    # largest gap that finished, never a smaller one.
    ([stream(0.0, 0.1, 0.02, 4, 4), stream(0.0, 0.1, 0.02, 4, 4),
      rec(1.0, [1.1], 8, done=False, error="cut")], 20.0, 20.0),
], ids=["one_token_a_chunk", "two_tokens_a_chunk", "a_failed_request_ranks_last"])
def test_the_median_gap_over_records_made_by_hand(records, p50_ms, p95_ms):
    out = S.score(records, seconds=30.0, chips=1)
    assert out["metrics"]["itl_p50_ms"] == pytest.approx(p50_ms, abs=1e-6)
    assert out["metrics"]["itl_p95_ms"] == pytest.approx(p95_ms, abs=1e-6)
    # An end-to-end metric now, no longer a fact for the log alone.
    assert "itl_p50_ms" not in out["facts"]


def test_a_median_that_falls_on_a_failure_says_so():
    bad = [rec(float(i), [i + 0.1], 8, done=False, error="cut") for i in range(3)]
    out = S.score([stream(0.0, 0.1, 0.02, 4, 4)] + bad, seconds=30.0, chips=1)
    assert out["facts"]["tail_is_a_failure"]["itl_p50"] is True
    assert out["metrics"]["itl_p50_ms"] == pytest.approx(20.0)
    assert S.score([], seconds=30.0, chips=1)["metrics"] == {}


@pytest.mark.parametrize("kw", [dict(finish="stop"), dict(status=503), dict(error="shed"),
                                dict(done=False)])
def test_what_counts_as_failed(kw):
    assert S.failed(rec(0.0, [0.1, 0.2], 2, **kw))
    assert not S.failed(rec(0.0, [0.1, 0.2], 2))


def test_only_requests_due_in_the_window_are_scored_but_every_token_in_it_counts():
    ramp = rec(-5.0, [-4.0, -1.0, 1.0, 2.0], 4, scored=False)  # 2 of 4 tokens inside
    inside = rec(1.0, [1.5, 2.5, 3.5, 11.0], 4)  # 3 of 4 inside [0, 10)
    out = S.score([ramp, inside], seconds=10.0, chips=1)
    assert out["attempted"] == 1
    assert out["metrics"]["output_tokens_per_s"] == pytest.approx(5 / 10.0)
    # Of the window's 5 tokens the ramp's request still owed 2 when it opened.
    assert out["facts"]["window_output_tokens"] == pytest.approx(5.0)
    assert out["facts"]["window_output_tokens_owed_by_the_ramp"] == pytest.approx(2.0)
    assert S.score([ramp, inside], seconds=10.0, chips=4)["metrics"][
        "output_tokens_per_s"] == pytest.approx(5 / 40.0)


def test_decode_context_tokens():
    r = rec(0.0, [1.0, 2.0, 3.0, 4.0], 4, prompt=100)
    # Tokens 1..3 are decode tokens with 101, 102, 103 positions of context.
    assert S.decode_context_tokens([r], 0.0, 10.0) == (3, 306)
    assert S.decode_context_tokens([r], 2.5, 3.5) == (1, 102)


def test_parse_prometheus():
    text = "\n".join([
        "# HELP vllm:generation_tokens_total x", "# TYPE vllm:generation_tokens_total counter",
        "vllm:generation_tokens_total 1234.0",
        'vllm:engine_step_duration_seconds_count{phase="dispatch"} 77',
        'vllm:request_queue_time_seconds_bucket{le="+Inf"} 5',
    ])
    m = parse_prometheus(text)
    assert m["vllm:generation_tokens_total"] == 1234.0
    assert m['vllm:engine_step_duration_seconds_count{phase="dispatch"}'] == 77.0
    assert m['vllm:request_queue_time_seconds_bucket{le="+Inf"}'] == 5.0


def test_the_per_layer_ttft_leaves_out_requests_due_around_a_profiler_stall():
    """By their due time, from 1 s before an attempt began to 3 s after its
    stop returned; a failed request that is kept ranks last."""
    from benchmark.harness.manifest import BENCH_DIR, load_module

    reader = load_module(BENCH_DIR / "layer_metrics" / "client_ttft_p50_ms.py")
    recs = [rec(0.5, [0.6], 1), rec(2.0, [2.2], 1), rec(3.5, [3.8], 1),
            rec(9.5, [15.0], 1),   # due 0.5 s before the attempt: stalled, left out
            rec(12.0, [15.1], 1),  # due inside it
            rec(16.9, [17.5], 1),  # due 2.9 s after the stop returned: the backlog
            rec(17.1, [17.5], 1), rec(20.0, [26.0], 1, scored=False)]
    ctx = {"records": recs, "profiled": [(10.0, 14.0)]}
    assert reader.read(ctx) == pytest.approx(200.0)  # of 100, 200, 300, 400
    assert reader.read({**ctx, "profiled": []}) == pytest.approx(400.0)  # of all seven scored
    recs[1].finish = "stop"  # short of its tokens: failed, so it ranks last
    assert reader.read(ctx) == pytest.approx(300.0)
    assert reader.read({"records": recs[3:5], "profiled": [(10.0, 14.0)]}) is None
