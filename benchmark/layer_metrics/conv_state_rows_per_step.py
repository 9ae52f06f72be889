"""Layer: KV cache manager (the per-request state slots of a short
convolution). Rows whose tail a step reads and writes, over the window:
delta of ``vllm:linear_attn_state_rows_total`` (rows x the layers that hold
the mixer: the ``conv`` entries among the first ``num_hidden_layers`` of
``layer_types``) over those layers and the steps. It is the batch the slots
carry: at 8 KiB of state a row a layer the slots cost next to nothing, and
this count says how many requests a step really holds. None where the
configuration has no such mixer or the program no such counter."""


def read(ctx):
    config = ctx["config"]
    if "conv_L_cache" not in config:
        return None
    rows, steps = ctx["delta"]("vllm:linear_attn_state_rows_total"), ctx["steps"]()
    layers = config["layer_types"][: config["num_hidden_layers"]].count("conv")
    if rows is None or not steps or not layers:
        return None
    return rows / (layers * steps)
