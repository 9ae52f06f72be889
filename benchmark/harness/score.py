"""From the client's records to the end-to-end metrics.

The arithmetic of ``vllm_tpu/metrics/goodput.py::percentile`` (nearest
rank), copied so that the program cannot change the yardstick, with two
corrections to ``vllm_tpu/benchmarks/run.py``: a request is timed from the
time it was DUE, not from the time it was sent, and a request that failed
is ranked behind every request that did not.
"""

from __future__ import annotations

import math

INF = float("inf")


def percentile(values: list[float], q: float) -> float | None:
    """Nearest-rank percentile (``q`` in [0, 1]); None on empty input."""
    if not values:
        return None
    ordered = sorted(values)
    idx = max(0, min(len(ordered) - 1, int(round(q * len(ordered))) - 1))
    if q <= 0:
        idx = 0
    return ordered[idx]


def failed(rec) -> bool:
    """Errored, shed, cut by the drain limit, or short of its tokens."""
    return not rec.ok or rec.finish != "length" or len(rec.chunks) > rec.max_tokens + 1


def token_gaps(rec) -> list[float]:
    """The wait before each output token after the first, in seconds, as
    the client saw it. Tokens that came in one chunk share that chunk's
    wait: the first of them has it, the others have 0. So a server that
    emits k tokens at once shows one long gap and k-1 gaps of 0, never k
    short ones."""
    times = rec.chunks
    gaps = [b - a for a, b in zip(times, times[1:])]
    # With ignore_eos a finished request has exactly max_tokens tokens.
    gaps += [0.0] * max(0, rec.max_tokens - len(times))
    return gaps


def tokens_in(rec, lo: float, hi: float) -> float:
    """Output tokens of one request that arrived in ``[lo, hi)``. A chunk
    of a finished request stands for max_tokens / chunks tokens."""
    if not rec.chunks:
        return 0.0
    per = rec.max_tokens / len(rec.chunks) if rec.done and rec.finish == "length" else 1.0
    return per * sum(1 for t in rec.chunks if lo <= t < hi)


def rank_last(values: list[float]) -> list[float]:
    """Failures (inf) rank behind everything, as the largest finite value
    (a JSON number), or as 0 where nothing finished."""
    worst = max((v for v in values if math.isfinite(v)), default=0.0)
    return [v if math.isfinite(v) else worst for v in values]


def score(records: list, seconds: float, chips: int) -> dict:
    """End-to-end metrics over the requests due in ``[0, seconds)``.

    Returns ``metrics`` (name -> value), ``attempted``, ``failed`` and
    ``facts`` for the per-layer readers and the log."""
    scored = [r for r in records if r.scored]
    bad = [r for r in scored if failed(r)]
    ttft = [INF if failed(r) else r.chunks[0] - r.due for r in scored]
    gaps: list[float] = []
    for r in scored:
        if failed(r):
            # Every token that did not arrive waited longer than any that did.
            gaps += token_gaps(r)[: max(0, len(r.chunks) - 1)]
            gaps += [INF] * max(1, r.max_tokens - len(r.chunks))
        else:
            gaps += token_gaps(r)
    window_tokens = sum(tokens_in(r, 0.0, seconds) for r in records)
    any_failed_tail = {
        "ttft_p50": percentile(ttft, 0.50), "ttft_p95": percentile(ttft, 0.95),
        "itl_p95": percentile(gaps, 0.95), "itl_p50": percentile(gaps, 0.50),
    }
    # Sorted once: every percentile below sorts its input again, which is
    # linear on a sorted list.
    ttft_f, gaps_f = rank_last(ttft), sorted(rank_last(gaps))
    metrics = {}
    if scored:
        metrics = {
            "ttft_p50_ms": 1e3 * percentile(ttft_f, 0.50),
            "ttft_p95_ms": 1e3 * percentile(ttft_f, 0.95),
            "itl_p95_ms": 1e3 * percentile(gaps_f, 0.95) if gaps_f else 0.0,
            # The median of the same pooled gaps: the step that runs most
            # often (a decode-only one), where the percentile above is the
            # slowest kind. 0 where a step commits two tokens in a chunk.
            "itl_p50_ms": 1e3 * percentile(gaps_f, 0.50) if gaps_f else 0.0,
            "output_tokens_per_s": window_tokens / seconds / chips,
        }
    late = [r.sent - r.due for r in scored if r.sent == r.sent]
    good = [r for r in scored if not failed(r)]
    facts = {
        "tail_is_a_failure": {k: v == INF for k, v in any_failed_tail.items()},
        "requests_due": len(scored),
        "requests_failed": len(bad),
        "first_errors": [r.error or f"finish={r.finish} done={r.done} status={r.status}"
                         for r in bad[:5]],
        "gen_late_p95_ms": 1e3 * percentile(late, 0.95) if late else None,
        "gen_late_max_ms": 1e3 * max(late) if late else None,
        "client_ttft_mean_ms": 1e3 * sum(r.chunks[0] - r.due for r in good) / len(good) if good else None,
        "client_ttft_from_send_mean_ms": 1e3 * sum(r.chunks[0] - r.sent for r in good) / len(good) if good else None,
        # The pooled gaps at every twentieth from 5% to 95%, for the log: where
        # the median lies between two kinds of step (a flat stretch of the
        # distribution) it swings with their counts, and this line shows it.
        "itl_ms_every_5_per_cent": [round(1e3 * percentile(gaps_f, q / 100.0), 3)
                                    for q in range(5, 100, 5)] if gaps_f else None,
        "prompt_tokens_due": sum(r.prompt_tokens for r in scored),
        "output_tokens_due": sum(r.max_tokens for r in scored),
        "window_output_tokens": window_tokens,
        # Of those, the tokens of requests due BEFORE the window (the ramp's):
        # what set-up still owed when the window opened. A stall in the ramp
        # moves tokens from before the window into it (PERF.md section 2).
        "window_output_tokens_owed_by_the_ramp": sum(
            tokens_in(r, 0.0, seconds) for r in records if not r.scored),
        "multi_token_chunks": sum(1 for r in good if len(r.chunks) < r.max_tokens),
    }
    return {"metrics": metrics, "attempted": len(scored), "failed": len(bad), "facts": facts}


def decode_context_tokens(records: list, lo: float, hi: float) -> tuple[int, int]:
    """(decode tokens, sum of their context lengths) among the chunks that
    arrived in ``[lo, hi)``: token j of a request (j >= 1; the first comes
    from the prefill) attends to prompt + j positions. One chunk is
    counted as one token, so where a chunk carries several the count is a
    lower bound."""
    n = ctx = 0
    for r in records:
        for j, t in enumerate(r.chunks):
            if j >= 1 and lo <= t < hi:
                n += 1
                ctx += r.prompt_tokens + j
    return n, ctx
