"""Layer: worker (worker/worker.py::load_model). Seconds of the start spent
in ``Worker.load_model``: the configuration, the model's class, the weights
made from the seed (or read) and placed on the device;
``vllm:startup_seconds{phase="load_model"}`` at the scrape that opens the
window. None where the program has no such series."""


def read(ctx):
    return ctx["m0"].get('vllm:startup_seconds{phase="load_model"}')
