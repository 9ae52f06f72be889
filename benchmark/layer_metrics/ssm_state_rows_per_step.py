"""Layer: KV cache manager (the per-request state slots of a state-space
mixer). Rows whose state a step reads and writes, over the window: delta of
``vllm:linear_attn_state_rows_total`` (rows x layers that hold the mixer:
every layer of ``falcon_h1``) over the layers and the steps. It is the batch
the slots carry: at 25.35 MB of state a row, it sets what a decode step
moves beside the weights. None where the configuration has no state-space
mixer or the program no such counter."""


def read(ctx):
    config = ctx["config"]
    if "mamba_d_state" not in config:
        return None
    rows, steps = ctx["delta"]("vllm:linear_attn_state_rows_total"), ctx["steps"]()
    layers = config["num_hidden_layers"]
    if rows is None or not steps or not layers:
        return None
    return rows / (layers * steps)
