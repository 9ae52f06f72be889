"""Layer: runner step (worker/model_runner.py). Seconds of the set-up spent
in first uses of a step program (traced, lowered and compiled or loaded
inside the step's dispatch): ``vllm:step_first_use_seconds_total`` at the
scrape that opens the window, so the warm-up's, the check's and the
ramp's. Beside ``compiles_in_window``, their count inside the window."""


def read(ctx):
    return ctx["m0"].get("vllm:step_first_use_seconds_total")
