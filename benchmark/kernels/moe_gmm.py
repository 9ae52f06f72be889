"""Counting functions of the grouped matmul of a mixture of experts
(``jax.experimental.pallas.ops.tpu.megablox.gmm`` through
``vllm_tpu/layers/moe.py``), three calls a layer: gate, up and down.

An expert that holds a row has its three matrices read once in the layer:
``3 * hidden * moe_intermediate * dtype_bytes`` bytes. A token-expert pair
costs ``2 * hidden * moe_intermediate`` operations in each of the three
matmuls. At a few rows an expert the BYTES set the least time (8 pairs a
token and 128 experts: five rows an expert at 80 live tokens is 6.5
operations a byte, far under the chip's 240); at large groups the
operations do, so the least time is the larger of the two. Both are lower
bounds: the counter holds the experts that got a LIVE token, and the kernel
also reads the experts that only the padding rows of a step route to; the
rows' own reads and writes are left out.
"""

# The names the trace prints for the kernel's calls (instance numbers
# removed), as read on the v5e.
TRACE_KERNELS = ("gmm",)


def expert_bytes(config: dict, dtype_bytes: int = 2) -> int:
    """Bytes of one expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] * dtype_bytes


def pair_ops(config: dict) -> int:
    """Operations of one token-expert pair through gate, up and down."""
    return 6 * config["hidden_size"] * config["moe_intermediate_size"]


def least_seconds(config: dict, experts_hit: float, assignments: float, peaks: dict) -> float:
    """``experts_hit``: experts with a row, counted a layer and a step and
    summed; ``assignments``: token-expert pairs, summed over layers."""
    return max(experts_hit * expert_bytes(config) / peaks["hbm_bytes_per_s"],
               assignments * pair_ops(config) / peaks["bf16_flops_per_s"])


def kernel_seconds(trace: dict) -> float:
    return sum(s for name, s in trace["kernel_seconds"].items()
               if name.split(".")[0] in TRACE_KERNELS)
