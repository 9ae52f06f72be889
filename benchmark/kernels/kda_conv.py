"""Counting functions of the short convolution of a KDA layer
(``vllm_tpu/ops/kda.py::kda_short_conv``), the Pallas kernel the trace
prints as ``kda_conv_kernel``: one call a KDA layer a step.

A depthwise causal convolution of ``short_conv_kernel_size`` taps over the
``3 * heads * head_dim`` channels of q, k and v, continued from and stored
to a request's slot. What any form of it must move: a token's channels in
the served dtype in and its float32 result out, and a LIVE row's tail
(``taps - 1`` tokens of channels in the served dtype) in and out. Two
operations a tap a channel, 8 a channel against 6 bytes: far under the
chip's 240 operations a byte, so the BYTES set its least time."""

TRACE_KERNELS = ("kda_conv_kernel",)
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _channels(config: dict) -> int:
    la = config["linear_attn_config"]
    return 3 * la["num_heads"] * la["head_dim"]


def token_bytes(config: dict) -> int:
    """A token's inputs in the served dtype and its float32 output."""
    return _channels(config) * (DTYPE_BYTES[config["torch_dtype"]] + 4)


def tail_bytes(config: dict) -> int:
    """One row's tail of one layer, read and written."""
    taps = config["linear_attn_config"]["short_conv_kernel_size"]
    return 2 * (taps - 1) * _channels(config) * DTYPE_BYTES[config["torch_dtype"]]


def least_seconds(config: dict, tokens: float, rows: float, peaks: dict) -> float:
    """``tokens``: the step's tokens, ``rows``: its live rows, both summed
    over the KDA layers (``vllm:linear_attn_tokens_total`` of both kinds,
    ``vllm:linear_attn_state_rows_total``)."""
    return (tokens * token_bytes(config) + rows * tail_bytes(config)) / peaks["hbm_bytes_per_s"]


def kernel_seconds(trace: dict) -> float:
    return sum(s for name, s in trace["kernel_seconds"].items()
               if name.split(".")[0] in TRACE_KERNELS)
