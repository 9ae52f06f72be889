"""Layer: runner step (worker/model_runner.py). Host milliseconds a step
spends building the step program's inputs (``_update_states`` and
``_prepare_inputs``, the runner's ``prep_s``): delta sum of
``vllm:engine_step_duration_seconds{phase="prepare"}`` over the engine
steps in the window. The device is idle for all of it unless the previous
step still runs (the lag pipeline)."""


def read(ctx):
    steps = ctx["steps"]()
    total = ctx["delta"]('vllm:engine_step_duration_seconds_sum{phase="prepare"}')
    return 1e3 * total / steps if steps and total is not None else None
