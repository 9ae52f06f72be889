"""Layer: scheduler (core/scheduler.py). Host milliseconds a step spends in
``Scheduler.schedule``: delta sum of
``vllm:engine_step_duration_seconds{phase="schedule"}`` between the scrapes
at the window's two ends, over the engine steps between them. The counter
is the program's own (``EngineCore.step`` times the call), read from the
clean window."""


def read(ctx):
    steps = ctx["steps"]()
    total = ctx["delta"]('vllm:engine_step_duration_seconds_sum{phase="schedule"}')
    return 1e3 * total / steps if steps and total is not None else None
