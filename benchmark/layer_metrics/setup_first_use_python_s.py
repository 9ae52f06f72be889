"""Layer: runner step (worker/model_runner.py). Of ``setup_first_use_s``,
the seconds Python took tracing the step programs and lowering them to
MLIR: ``vllm:step_first_use_stage_seconds_total`` over the stages ``trace``
and ``lower`` at the scrape that opens the window. Neither XLA's persistent
cache nor a faster compiler removes this part (a serialized executable
would); the rest is ``backend`` (the compile, or the load from the cache)
and ``run``. None where the program has no such series."""


def read(ctx):
    m0 = ctx["m0"]
    keys = [f'vllm:step_first_use_stage_seconds_total{{stage="{s}"}}' for s in ("trace", "lower")]
    return sum(m0[k] for k in keys) if all(k in m0 for k in keys) else None
