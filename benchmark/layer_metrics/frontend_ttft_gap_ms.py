"""Layer: HTTP front end (entrypoints/openai/api_server.py). The client's
mean time to first token, from the SEND (so the generator's lateness and
the wait for a free client are not in it), minus the engine's own mean
over the same window (delta sum / delta count of
``vllm:time_to_first_token_seconds``): what parsing, admission, the hop to
the engine thread and the stream back add."""


def read(ctx):
    d_sum = ctx["delta"]("vllm:time_to_first_token_seconds_sum")
    d_cnt = ctx["delta"]("vllm:time_to_first_token_seconds_count")
    mine = ctx["facts"].get("client_ttft_from_send_mean_ms")
    if not d_cnt or mine is None:
        return None
    return mine - 1e3 * d_sum / d_cnt
