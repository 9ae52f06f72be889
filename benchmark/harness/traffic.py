"""Traffic generators. One general generator per arrival pattern, each
driven by a data file under ``benchmark/traffic/``.

Every seed gets the SAME work in the SAME order. The lengths of a phase are
the mid-quantiles of the file's distributions (a stratified sample, so the
sum of prompt and output tokens is the same in every run), paired once by
the file's ``pool_seed``; the gaps between arrivals are the mid-quantiles of
the exponential distribution, scaled to fill the phase exactly; and the
order of the pairs and of the gaps is the file's too (``_draws``: from
``pool_seed`` and the phase's number), whatever the mix. ``--seed`` draws the
token ids (and, in the server, the weights and the sampler's draws) and
nothing else. So two seeds send request for request the same lengths at the
same times: which long prompts and which long answers fall inside the
window is a property of the cell, not of the run. A second stretch of one
run (the traced segment after the window) is a ``segment`` of its own, with
its own phase numbers: another order from the same file, not the window's
replayed.

Times are seconds relative to the opening of the measured window: the ramp
is negative, the window is ``[0, seconds)``.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Request:
    idx: int
    due_s: float | None  # None: closed loop, sent when a client is free
    prompt: list[int]
    max_tokens: int
    sampling: dict
    scored: bool = False
    group: int | None = None  # the shared document, where there is one
    logprobs: int | None = None


@dataclasses.dataclass
class Plan:
    kind: str  # "open" | "closed"
    requests: list[Request]
    clients: int = 0  # closed loop only
    start_s: float = 0.0  # when the first phase of the plan begins
    end_s: float = 0.0  # when arrivals stop
    # A traced run may go on sending after the window, unscored, until the
    # profiler has given a device trace: ``extend`` appends those arrivals.
    score_until: float = 0.0


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng([int(w) % (2**63) for w in words])


def quantile_sample(dist: dict, n: int) -> np.ndarray:
    """``n`` whole numbers at the mid-quantiles of ``dist``, ascending."""
    u = (np.arange(n) + 0.5) / max(n, 1)
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif kind == "loguniform":
        lo, hi = math.log(dist["min"]), math.log(dist["max"])
        x = np.exp(lo + u * (hi - lo))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "fixed":
        x = np.full(n, dist["value"], float)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), dist.get("min", 1), dist.get("max", 2**31)).astype(int)


def _draws(traffic: dict, seed: int, phase: int, generator: int):
    """The two sources of one phase of one generator: ``order``, which
    permutes the pairs of lengths and the gaps and comes from the traffic
    file alone, and ``ids``, which draws the token ids and comes from
    ``--seed``."""
    return _rng(traffic["pool_seed"], phase, generator, 11), _rng(seed, phase, generator)


def arrival_times(n: int, start_s: float, duration_s: float, order) -> np.ndarray:
    """``n`` arrivals in ``[start, start + duration)``: exponential gaps at
    their mid-quantiles, scaled to fill the phase, in the file's order."""
    if n <= 0:
        return np.zeros(0)
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps *= duration_s / gaps.sum()
    gaps = gaps[order.permutation(n)]
    return start_s + np.cumsum(gaps) - gaps / 2


def _pairs(traffic: dict, n: int, positions: int, keys: tuple[str, ...]) -> np.ndarray:
    """``[n, len(keys)]`` lengths, each column a stratified sample, the
    columns paired by the file's own seed (so the pairing is the same for
    every ``--seed``). The first column is clipped so that the row fits the
    model's positions."""
    pool = _rng(traffic["pool_seed"], n)
    cols = [quantile_sample(traffic[k], n)[pool.permutation(n)] for k in keys]
    out = np.stack(cols, axis=1)
    rest = out[:, 1:].sum(axis=1)
    out[:, 0] = np.minimum(out[:, 0], positions - rest)
    if (out[:, 0] < 1).any():
        raise ValueError("a request does not fit the model's positions")
    return out


def _tokens(rng, n: int, vocab: int) -> list[int]:
    return rng.integers(0, vocab, size=int(n)).tolist()


def _phases(ramp_s: float, seconds: float, segment: int) -> list[tuple[float, float, int, bool]]:
    """(start, duration, phase number, whether it is the window): the ramp,
    then the window; each segment of a run has its own two numbers."""
    out = []
    if ramp_s > 0:
        out.append((-ramp_s, ramp_s, 2 * segment, False))
    if seconds > 0:
        out.append((0.0, seconds, 2 * segment + 1, True))
    return out


def open_poisson(traffic, rate, ramp_s, seconds, seed, vocab, positions, segment=0) -> Plan:
    reqs: list[Request] = []
    for start, dur, phase, window in _phases(ramp_s, seconds, segment):
        n = int(round(rate * dur))
        order, ids = _draws(traffic, seed, phase, 1)
        lens = _pairs(traffic, n, positions, ("prompt_len", "output_len"))
        lens = lens[order.permutation(n)]
        due = arrival_times(n, start, dur, order)
        for (p, o), t in zip(lens, due):
            reqs.append(Request(
                idx=len(reqs), due_s=float(t), prompt=_tokens(ids, p, vocab),
                max_tokens=int(o), sampling=dict(traffic["sampling"]),
                scored=window,
            ))
    reqs.sort(key=lambda r: r.due_s)
    return Plan("open", reqs, start_s=-ramp_s, end_s=seconds)


def open_poisson_shared_docs(traffic, rate, ramp_s, seconds, seed, vocab, positions,
                             segment=0) -> Plan:
    """``rate`` counts requests; documents arrive at ``rate / asks_per_doc``."""
    asks = int(traffic["asks_per_doc"])
    spread = float(traffic["ask_spread_s"])
    reqs: list[Request] = []
    docs = 0
    for start, dur, phase, _ in _phases(ramp_s, seconds, segment):
        n = int(round(rate / asks * dur))
        order, ids = _draws(traffic, seed, phase, 2)
        pool = _rng(traffic["pool_seed"], n, 7)
        doc_len = quantile_sample(traffic["doc_len"], n)[order.permutation(n)]
        qo = _pairs(
            {**traffic, "pool_seed": traffic["pool_seed"] + 1}, n * asks,
            2**31, ("question_len", "output_len"),
        )[order.permutation(n * asks)].reshape(n, asks, 2)
        # Ask k of a document falls in the k-th part of the spread, at a
        # place fixed by the file's seed.
        offs = (np.arange(asks)[None, :] + pool.random((n, asks))) / asks * spread
        arrive = arrival_times(n, start, dur, order)
        for d in range(n):
            doc = _tokens(ids, doc_len[d], vocab)
            for k in range(asks):
                due = float(arrive[d] + offs[d, k])
                q, o = (int(x) for x in qo[d, k])
                if due >= seconds or len(doc) + q + o > positions:
                    continue
                reqs.append(Request(
                    idx=0, due_s=due, prompt=doc + _tokens(ids, q, vocab),
                    max_tokens=o, sampling=dict(traffic["sampling"]),
                    scored=0.0 <= due < seconds, group=docs,
                ))
            docs += 1
    reqs.sort(key=lambda r: r.due_s)
    for i, r in enumerate(reqs):
        r.idx = i
    return Plan("open", reqs, start_s=-ramp_s, end_s=seconds)


def closed_clients(traffic, rate, ramp_s, seconds, seed, vocab, positions, segment=0) -> Plan:
    """``rate`` is not used: the clients set the pace. The pool is long
    enough that it does not run out; a request is scored when it is sent
    inside the window."""
    n = int(traffic["pool_requests"])
    order, ids = _draws(traffic, seed, 2 * segment, 3)
    lens = _pairs(traffic, n, positions, ("prompt_len", "output_len"))
    lens = lens[order.permutation(n)]
    reqs = [
        Request(idx=i, due_s=None, prompt=_tokens(ids, p, vocab),
                max_tokens=int(o), sampling=dict(traffic["sampling"]))
        for i, (p, o) in enumerate(lens)
    ]
    return Plan("closed", reqs, clients=int(traffic["clients"]),
                start_s=-ramp_s, end_s=seconds)


GENERATORS = {
    "open_poisson": open_poisson,
    "open_poisson_shared_docs": open_poisson_shared_docs,
    "closed_clients": closed_clients,
}


def build_plan(traffic: dict, rate: float, ramp_s: float, seconds: float,
               seed: int, vocab: int, positions: int, segment: int = 0) -> Plan:
    """The plan of one stretch of a run: ``segment`` 0 is the window with
    its ramp, 1 the traced segment that follows it. The lengths, the gaps
    and their order are the traffic file's, one order a segment; ``seed``
    draws the token ids."""
    try:
        gen = GENERATORS[traffic["generator"]]
    except KeyError:
        raise ValueError(
            f"traffic names the generator {traffic['generator']!r}; "
            f"the harness has {sorted(GENERATORS)}"
        ) from None
    plan = gen(traffic, rate, ramp_s, seconds, seed, vocab, positions, segment)
    plan.score_until = plan.end_s
    return plan


def extend(plan: Plan, more: Plan, by_s: float) -> Plan:
    """``plan`` followed by the window phase of ``more`` (the same mix at
    the same rate, another segment), shifted to begin where ``plan``
    ends; none of the added requests is scored."""
    if plan.kind == "open":
        for r in more.requests:
            if r.due_s is not None and r.due_s >= 0.0:
                r.due_s += plan.end_s
                r.scored = False
                r.idx = len(plan.requests)
                plan.requests.append(r)
    plan.end_s += by_s
    return plan


def wave_plan(wave: list, sampling: dict, seed: int, vocab: int) -> Plan:
    """One wave of a warm-up: ``[[count, prompt tokens, answer tokens], ...]``,
    none scored, token ids from ``seed``. Every request is due at once,
    but for an entry with a fourth number, which is due that many seconds
    later: a prompt that has to arrive while the others are decoding."""
    rng = _rng(seed, 5)
    reqs = [
        Request(idx=0, due_s=float(after[0]) if after else 0.0, prompt=_tokens(rng, p, vocab),
                max_tokens=int(o), sampling=dict(sampling))
        for count, p, o, *after in wave for _ in range(int(count))
    ]
    for i, r in enumerate(reqs):
        r.idx = i
    last = max(r.due_s for r in reqs)
    return Plan("open", reqs, end_s=last, score_until=last + 1.0)


def check_requests(traffic: dict, seed: int, vocab: int, positions: int) -> tuple[list[Request], bool]:
    """The greedy requests with logprobs that decide ``correct``, shaped
    like the cell's traffic, and whether they are sent together (a batch,
    as served) or one after the other (so that the second ask of a
    document finds the first one's prefix in the cache)."""
    spec = traffic["check"]
    rng = _rng(seed, 4)
    greedy = {"temperature": 0.0}
    out = int(spec["output_len"])
    reqs: list[Request] = []
    if "shared_doc_len" in spec:
        doc = _tokens(rng, min(spec["shared_doc_len"], positions - spec["question_len"] - out), vocab)
        for i in range(int(spec["sequences"])):
            reqs.append(Request(
                idx=i, due_s=None, prompt=doc + _tokens(rng, spec["question_len"], vocab),
                max_tokens=out, sampling=greedy, group=0, logprobs=int(spec["logprobs"]),
            ))
        return reqs, False
    lo, hi = spec["prompt_len"]
    n = int(spec["sequences"])
    for i in range(n):
        p = int(round(math.exp(math.log(lo) + (math.log(hi) - math.log(lo)) * i / max(n - 1, 1))))
        p = min(p, positions - out)
        reqs.append(Request(
            idx=i, due_s=None, prompt=_tokens(rng, p, vocab), max_tokens=out,
            sampling=greedy, logprobs=int(spec["logprobs"]),
        ))
    return reqs, True
