"""Counting functions of the short convolution of a state-space mixer
(``vllm_tpu/ops/kda.py::kda_short_conv``, the kernel both slot families
share, which the trace prints as ``kda_conv_kernel``): one call a layer a
step, over ONE stream of ``mamba_d_ssm + 2 * mamba_n_groups *
mamba_d_state`` channels (x, B and C side by side; 5120 here), with a bias.

What any form of it must move: a token's channels in the served dtype in
and its float32 result out, and a LIVE row's tail (``mamba_d_conv - 1``
tokens of channels in the served dtype) in and out. Two operations a tap a
channel, 9 a channel against 6 bytes: far under the chip's 240 operations a
byte, so the BYTES set its least time. (``kda_conv.py`` counts the delta
rule's geometry, three streams of heads x head size, for the same kernel.)"""

TRACE_KERNELS = ("kda_conv_kernel",)
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def channels(config: dict) -> int:
    inner = config.get("mamba_d_ssm") or config["mamba_n_heads"] * config["mamba_d_head"]
    return inner + 2 * config["mamba_n_groups"] * config["mamba_d_state"]


def token_bytes(config: dict) -> int:
    """A token's inputs in the served dtype and its float32 output."""
    return channels(config) * (DTYPE_BYTES[config["torch_dtype"]] + 4)


def tail_bytes(config: dict) -> int:
    """One row's tail of one layer, read and written."""
    return 2 * (config["mamba_d_conv"] - 1) * channels(config) * DTYPE_BYTES[config["torch_dtype"]]


def least_seconds(config: dict, tokens: float, rows: float, peaks: dict) -> float:
    """``tokens``: the step's tokens, ``rows``: its live rows, both summed
    over the layers (``vllm:linear_attn_tokens_total`` of both kinds,
    ``vllm:linear_attn_state_rows_total``)."""
    return (tokens * token_bytes(config) + rows * tail_bytes(config)) / peaks["hbm_bytes_per_s"]


def kernel_seconds(trace: dict) -> float:
    return sum(s for name, s in trace["kernel_seconds"].items()
               if name.split(".")[0] in TRACE_KERNELS)
