"""Layer: scheduler (core/scheduler.py). Mean time a request waited to be
scheduled: delta sum / delta count of ``vllm:request_queue_time_seconds``
between the scrapes at the window's two ends."""


def read(ctx):
    d_sum = ctx["delta"]("vllm:request_queue_time_seconds_sum")
    d_cnt = ctx["delta"]("vllm:request_queue_time_seconds_count")
    return 1e3 * d_sum / d_cnt if d_cnt else None
