"""Counting functions of the Mamba-2 rule over state slots
(``vllm_tpu/ops/mamba.py``), the state-space mixer of every layer of
``falcon_h1``.

The one-token form is the Pallas kernel the trace prints as
``ssd_decode_kernel``: a row's state, ``mamba_n_heads * mamba_d_head *
mamba_d_state`` float32 numbers a layer (4 MiB at 32 heads of 128 x 256), is
read and written once, and the token's ``dt x``, decay and output (a number
a head channel each) and its ``B`` and ``C`` (a number a group a state
channel) beside it. Some 5 operations a state number against 8 bytes: far
under the chip's 240 operations a byte, so the BYTES set its least time.
The counts take the configuration, not the kernel's arguments: they read the
same work whatever implements it (a form that moved the state as the three
passes of a gather, a scan and a scatter would read a third).

The chunked form (rows of more than one token) is XLA in this program and
has no share here; ``tools/probe_ssd.py`` times either form by itself."""

TRACE_KERNELS = ("ssd_decode_kernel",)


def state_bytes(config: dict) -> int:
    """One row's state of one layer, read and written."""
    return 2 * config["mamba_n_heads"] * config["mamba_d_head"] * config["mamba_d_state"] * 4


def token_bytes(config: dict) -> int:
    """A token's operands of one layer in float32: ``dt x``, the decay on
    the head's channels and the output (3 numbers a head channel), ``B``
    and ``C`` (2 a group a state channel)."""
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    return (3 * inner + 2 * config["mamba_n_groups"] * config["mamba_d_state"]) * 4


def least_seconds(config: dict, decode_rows: float, peaks: dict) -> float:
    """``decode_rows``: rows of one token summed over the layers
    (``vllm:linear_attn_tokens_total{kind="decode"}``)."""
    return decode_rows * (state_bytes(config) + token_bytes(config)) / peaks["hbm_bytes_per_s"]


def kernel_seconds(trace: dict) -> float:
    return sum(s for name, s in trace["kernel_seconds"].items()
               if name.split(".")[0] in TRACE_KERNELS)
