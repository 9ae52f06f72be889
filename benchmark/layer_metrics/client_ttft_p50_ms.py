"""Layer: HTTP front end (entrypoints/openai/api_server.py), as the client
sees it. The median, over requests due in the window, of first streamed
token minus due time: the end-to-end ``ttft_p50_ms`` of every run's log,
read here because its runs spread by more than half of the widest bound an
end-to-end metric may have (PERF.md section 2). A traced run's server stalls
for seconds at each ``/stop_profile``, so the requests due from 1 s before a
profiler attempt began to 3 s after its stop returned (the backlog of a
stall is some 25 requests, a second of 512-token steps) are left out, by
their due time and never by how they fared. A failed request ranks last."""

from benchmark.harness import score

BEFORE_S, AFTER_S = 1.0, 3.0


def read(ctx):
    stalls = [(lo - BEFORE_S, hi + AFTER_S) for lo, hi in ctx["profiled"]]
    clear = [r for r in ctx["records"]
             if r.scored and not any(lo <= r.due < hi for lo, hi in stalls)]
    if not clear:
        return None
    ttft = [score.INF if score.failed(r) else r.chunks[0] - r.due for r in clear]
    return 1e3 * score.percentile(score.rank_last(ttft), 0.50)
