"""Counting functions of the gated short convolution of an LFM2 layer
(``vllm_tpu/ops/kda.py::kda_short_conv``, the kernel every slot family
shares, which the trace prints as ``kda_conv_kernel``): one call a
convolution layer a step, over ONE stream of ``hidden_size`` channels, three
taps, no bias, and the tail is all the state there is.

What any form of it must move: a token's channels in the served dtype in and
its float32 result out, and a LIVE row's tail (``conv_L_cache - 1`` tokens
of channels in the served dtype) in and out. Two operations a tap a channel,
6 a channel against 6 bytes: far under the chip's 240 operations a byte, so
the BYTES set its least time. (``kda_conv.py`` and ``ssd_conv.py`` count the
delta rule's and the state-space mixer's geometry for the same kernel.)"""

TRACE_KERNELS = ("kda_conv_kernel",)
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layers(config: dict) -> int:
    """The convolution layers the program runs: of the first
    ``num_hidden_layers`` entries of ``layer_types``."""
    return config["layer_types"][: config["num_hidden_layers"]].count("conv")


def token_bytes(config: dict) -> int:
    """A token's gated input in the served dtype and its float32 output."""
    return config["hidden_size"] * (DTYPE_BYTES[config["torch_dtype"]] + 4)


def tail_bytes(config: dict) -> int:
    """One row's tail of one layer, read and written."""
    return (2 * (config["conv_L_cache"] - 1) * config["hidden_size"]
            * DTYPE_BYTES[config["torch_dtype"]])


def least_seconds(config: dict, tokens: float, rows: float, peaks: dict) -> float:
    """``tokens``: the step's tokens, ``rows``: its live rows, both summed
    over the convolution layers (``vllm:linear_attn_tokens_total`` of both
    kinds, ``vllm:linear_attn_state_rows_total``)."""
    return (tokens * token_bytes(config) + rows * tail_bytes(config)) / peaks["hbm_bytes_per_s"]


def kernel_seconds(trace: dict) -> float:
    return sum(s for name, s in trace["kernel_seconds"].items()
               if name.split(".")[0] in TRACE_KERNELS)
