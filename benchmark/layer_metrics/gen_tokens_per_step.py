"""Layer: scheduler (core/scheduler.py). The mean decode batch: delta of
``vllm:generation_tokens`` over the number of engine steps in the window
(delta count of one phase of ``vllm:engine_step_duration_seconds``)."""


def read(ctx):
    steps = ctx["steps"]()
    toks = ctx["delta"]("vllm:generation_tokens_total", "vllm:generation_tokens")
    return toks / steps if steps and toks is not None else None
