"""Layer: kernels (layers/moe.py). Of the experts a step could read
(experts x layers), the share that got at least one live token, over the
window: delta of ``vllm:moe_experts_hit_total`` over experts x layers x
steps. At 100 every step reads every expert, and the grouped matmul is
bound by reading them whatever the batch. None where the program has no
such counter."""


def read(ctx):
    hit, steps = ctx["delta"]("vllm:moe_experts_hit_total"), ctx["steps"]()
    config = ctx["config"]
    if hit is None or not steps or "num_experts" not in config:
        return None
    return 100.0 * hit / (config["num_experts"] * config["num_hidden_layers"] * steps)
