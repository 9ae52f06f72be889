"""Layer: KV cache manager (the per-request state slots of linear
attention). Rows whose state a step reads and writes, over the window:
delta of ``vllm:linear_attn_state_rows_total`` (rows x KDA layers) over
the KDA layers and the steps. It is the batch the slots carry: at 21.7 MB
of state a row, it and not the pages sets what a step moves. None where
the program has no such counter."""


def read(ctx):
    rows, steps = ctx["delta"]("vllm:linear_attn_state_rows_total"), ctx["steps"]()
    layers = len(ctx["config"].get("linear_attn_config", {}).get("kda_layers", ()))
    if rows is None or not steps or not layers:
        return None
    return rows / (layers * steps)
