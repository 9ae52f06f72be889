"""The traffic generators: the same seed gives the same requests, another
seed gives the same lengths at the same times, request for request, and
other token ids."""

from __future__ import annotations

import pytest

from benchmark.harness import traffic as T
from benchmark.harness.manifest import BENCH_DIR, load_json

from pathlib import Path

VOCAB, POSITIONS = 32768, 32768
# ``chat`` is the mix of a cell. The other two are kept beside the tests:
# they drive the generators no cell uses yet (shared documents, a closed
# loop of clients), which a later cell gets by adding a data file alone.
MIXES = ["chat", "rag-shared", "batch-prefill"]
FILES = {"chat": BENCH_DIR / "traffic" / "chat.json",
         "chat-diffusion": BENCH_DIR / "traffic" / "chat-diffusion.json",
         "long-answers": BENCH_DIR / "traffic" / "long-answers.json",
         "rag-shared": Path(__file__).with_name("mix_shared_docs.json"),
         "batch-prefill": Path(__file__).with_name("mix_closed_clients.json")}
# Every generator file there is: the three mixes of the cells and the two
# kept beside the tests.
ALL_MIXES = list(FILES)


def plan(name, seed, rate=4.0, ramp=10.0, seconds=30.0, positions=POSITIONS, segment=0):
    return T.build_plan(load_json(FILES[name]), rate, ramp, seconds, seed, VOCAB, positions,
                        segment)


def shape(p):
    return [(r.due_s, tuple(r.prompt), r.max_tokens, r.scored, r.group) for r in p.requests]


def work(p):
    """What a request costs and when it comes, without what it says."""
    return [(r.due_s, len(r.prompt), r.max_tokens, r.scored, r.group, r.sampling)
            for r in p.requests]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    assert shape(plan(name, 2**31 + 11)) == shape(plan(name, 2**31 + 11))


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_same_load_other_order(name):
    """Since PR 42 another seed is the same load in the SAME order: the due
    times and the pair (prompt tokens, answer tokens) agree request for
    request, and only the token ids differ. (The name is the accepted
    test's; before, a seed permuted the pairs and the gaps, and which long
    answers fell inside a 51 s window moved every end-to-end metric.)"""
    a, b = plan(name, 1), plan(name, 2)
    assert shape(a) != shape(b)
    assert [r.prompt for r in a.requests] != [r.prompt for r in b.requests]
    assert work(a) == work(b)
    if a.kind == "open":
        assert [r.due_s for r in a.requests] == sorted(r.due_s for r in a.requests)
        n = lambda p: sum(r.scored for r in p.requests)
        assert n(a) == n(b) > 0
    else:
        assert all(r.due_s is None for r in a.requests)


@pytest.mark.parametrize("name", ALL_MIXES)
@pytest.mark.parametrize("seeds", [(1, 2), (7, 2**31 + 11), (2**31 + 5, 2**31 + 6)],
                         ids=["small", "small_large", "neighbours"])
def test_every_seed_sends_one_order_of_work(name, seeds):
    """For any two seeds and every generator file: the same due times and
    the same (len(prompt), max_tokens) in the same places, other token ids;
    the same seed, the same plan."""
    a, b = (plan(name, s, rate=3.2, ramp=12.0, seconds=51.0) for s in seeds)
    assert work(a) == work(b) and len(a.requests) > 30
    differ = sum(x.prompt != y.prompt for x, y in zip(a.requests, b.requests))
    assert differ == len(a.requests), "every prompt's ids are the seed's"
    assert shape(a) == shape(plan(name, seeds[0], rate=3.2, ramp=12.0, seconds=51.0))


@pytest.mark.parametrize("name", ALL_MIXES)
def test_a_second_segment_has_an_order_of_its_own_from_the_same_file(name):
    """The traced segment after the window is segment 1: the file gives it
    another order (it does not replay the window), the same for every seed,
    and its token ids are not the window's either."""
    window, segment = plan(name, 5), plan(name, 5, segment=1)
    assert work(window) != work(segment)
    if name != "rag-shared":  # there an ask due past the end is dropped: the count follows the order
        lengths = lambda p: sorted((len(r.prompt), r.max_tokens, r.scored) for r in p.requests)
        assert lengths(window) == lengths(segment), "the same lengths, otherwise placed"
    assert work(segment) == work(plan(name, 6, segment=1))
    assert {tuple(r.prompt[:16]) for r in window.requests}.isdisjoint(
        tuple(r.prompt[:16]) for r in segment.requests)


def test_the_order_is_the_files_and_another_pool_seed_gives_another():
    """The order comes from ``pool_seed`` and from nothing else in the run:
    a file with another one is another cell's worth of work, in lengths'
    pairing and in order."""
    spec = load_json(FILES["chat"])
    a = T.build_plan(spec, 4.0, 10.0, 30.0, 3, VOCAB, POSITIONS)
    b = T.build_plan({**spec, "pool_seed": spec["pool_seed"] + 1}, 4.0, 10.0, 30.0, 3,
                     VOCAB, POSITIONS)
    assert work(a) != work(b)
    assert sorted(len(r.prompt) for r in a.requests) == sorted(len(r.prompt) for r in b.requests)
    assert sorted(round(r.due_s, 9) for r in a.requests) != sorted(
        round(r.due_s, 9) for r in b.requests)


def test_chat_fills_the_window_at_its_rate():
    p = plan("chat", 5, rate=4.0, ramp=10.0, seconds=30.0)
    scored = [r for r in p.requests if r.scored]
    ramp = [r for r in p.requests if not r.scored]
    assert len(scored) == 120 and len(ramp) == 40
    assert all(0.0 <= r.due_s < 30.0 for r in scored)
    assert all(-10.0 <= r.due_s < 0.0 for r in ramp)
    assert [r.due_s for r in p.requests] == sorted(r.due_s for r in p.requests)
    assert all(16 <= len(r.prompt) <= 4096 and 8 <= r.max_tokens <= 1024 for r in p.requests)
    assert all(r.sampling == {"temperature": 0.7, "top_p": 0.9} for r in p.requests)
    # Every prompt its own: nothing for the prefix cache.
    assert len({tuple(r.prompt[:16]) for r in p.requests}) == len(p.requests)


def test_chat_is_clipped_to_the_models_positions():
    p = plan("chat", 5, positions=4096)
    assert all(len(r.prompt) + r.max_tokens <= 4096 for r in p.requests)
    assert max(len(r.prompt) for r in p.requests) > 3000


def test_shared_documents_are_asked_three_times_within_the_spread():
    p = plan("rag-shared", 9, rate=3.0, ramp=15.0, seconds=40.0)
    by_doc: dict[int, list] = {}
    for r in p.requests:
        by_doc.setdefault(r.group, []).append(r)
    whole = [v for v in by_doc.values() if len(v) == 3]
    assert len(whole) >= 0.6 * len(by_doc)
    for asks in whole:
        n = min(len(r.prompt) for r in asks) - 128
        assert 2048 - 128 <= n and all(r.prompt[:n] == asks[0].prompt[:n] for r in asks)
        assert len({tuple(r.prompt[-32:]) for r in asks}) == 3  # its own question each
        assert max(r.due_s for r in asks) - min(r.due_s for r in asks) < 10.0
        assert all(32 <= r.max_tokens <= 256 and r.sampling == {"temperature": 0.0} for r in asks)
    assert all(r.due_s < 40.0 for r in p.requests)
    assert all(r.scored == (0.0 <= r.due_s < 40.0) for r in p.requests)


def test_closed_loop_has_clients_and_a_pool():
    p = plan("batch-prefill", 3)
    assert p.kind == "closed" and p.clients == 32 and len(p.requests) == 4096
    assert all(r.due_s is None for r in p.requests)
    assert all(512 <= len(r.prompt) <= 3072 and 4 <= r.max_tokens <= 16 for r in p.requests)


def test_quantile_sample_is_the_distribution():
    x = T.quantile_sample({"dist": "lognormal", "median": 400, "sigma": 1.0, "min": 16, "max": 4096}, 1001)
    assert list(x) == sorted(x) and x[500] == 400 and x[0] >= 16 and x[-1] == 4096
    u = T.quantile_sample({"dist": "uniform", "min": 4, "max": 16}, 13)
    assert u[0] == 4 and u[-1] == 16


@pytest.mark.parametrize("name", MIXES)
def test_check_requests_are_greedy_with_logprobs(name):
    spec = load_json(FILES[name])
    reqs, together = T.check_requests(spec, 77, VOCAB, 4096)
    again, _ = T.check_requests(spec, 77, VOCAB, 4096)
    assert [r.prompt for r in reqs] == [r.prompt for r in again]
    assert all(r.sampling == {"temperature": 0.0} and r.logprobs == 5 for r in reqs)
    assert all(len(r.prompt) + r.max_tokens <= 4096 for r in reqs)
    if name == "rag-shared":
        assert not together and reqs[0].prompt[:2000] == reqs[1].prompt[:2000]
        assert reqs[0].prompt != reqs[1].prompt


def test_unknown_generator_is_an_error():
    with pytest.raises(ValueError, match="generator"):
        T.build_plan({"generator": "sessions"}, 1.0, 0.0, 1.0, 0, 10, 10)


def test_a_traced_run_extends_the_plan_with_unscored_arrivals():
    spec = load_json(BENCH_DIR / "traffic" / "chat.json")
    p = T.build_plan(spec, 4.0, 5.0, 20.0, 1, VOCAB, POSITIONS)
    n, scored = len(p.requests), sum(r.scored for r in p.requests)
    more = T.build_plan(spec, 4.0, 0.0, 10.0, 1, VOCAB, POSITIONS, segment=1)
    p = T.extend(p, more, 10.0)
    assert p.score_until == 20.0 and p.end_s == 30.0
    assert len(p.requests) == n + 40 and sum(r.scored for r in p.requests) == scored
    assert all(20.0 <= r.due_s < 30.0 for r in p.requests[n:])


def test_a_warm_up_wave_is_due_at_once_with_the_traffics_sampling():
    wave = [[12, 20, 40], [1, 4600, 40]]
    p = T.wave_plan(wave, {"temperature": 0.7, "top_p": 0.9}, 7, VOCAB)
    again = T.wave_plan(wave, {"temperature": 0.7, "top_p": 0.9}, 7, VOCAB)
    assert shape(p) == shape(again)
    assert [(len(r.prompt), r.max_tokens) for r in p.requests] == [(20, 40)] * 12 + [(4600, 40)]
    assert all(r.due_s == 0.0 < p.score_until and not r.scored for r in p.requests)
    assert all(r.sampling == {"temperature": 0.7, "top_p": 0.9} for r in p.requests)
    assert [r.idx for r in p.requests] == list(range(13))
    assert len({tuple(r.prompt[:16]) for r in p.requests}) == 13
    # A fourth number: that entry is due so many seconds after the others.
    late = T.wave_plan([[10, 20, 120], [1, 506, 40, 0.5]], {"temperature": 0.7}, 7, VOCAB)
    assert [r.due_s for r in late.requests] == [0.0] * 10 + [0.5]
    assert late.end_s == 0.5 < late.score_until and not any(r.scored for r in late.requests)
