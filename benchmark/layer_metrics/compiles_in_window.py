"""Layer: runner step (worker/model_runner.py). Step programs first used
inside the window: delta of ``vllm:step_bucket_compiles``. The warm-up walks
the cell's ladder so that this is 0; what is left is a program the walk did
not visit (``cells/<cell>.json`` says which it leaves out). Every run logs
the number, traced or not, because each such first use stalls the running
requests for seconds."""


def read(ctx):
    return ctx["delta"]("vllm:step_bucket_compiles_total", "vllm:step_bucket_compiles")
