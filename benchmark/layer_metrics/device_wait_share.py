"""Layer: runner step (worker/model_runner.py). The share of the engine
loop's time spent waiting for the device: delta sum of
``vllm:engine_step_duration_seconds{phase="fetch"}`` (the ``device_get`` of
a step's results, the only place a step waits) over the seconds between the
two scrapes. 100 minus it is the host's share of the loop: scheduling,
preparing, dispatching, updating, delivering and waiting for input. While
it is high the cell is device-bound and a faster device step shows end to
end; as it falls the host sets the pace."""


def read(ctx):
    total = ctx["delta"]('vllm:engine_step_duration_seconds_sum{phase="fetch"}')
    if total is None or not ctx["scrape_dt"]:
        return None
    return 100.0 * total / ctx["scrape_dt"]
