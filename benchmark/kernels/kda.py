"""Counting functions of the delta rule with a decay a key channel
(``vllm_tpu/ops/kda.py``), the mixer of three layers in four.

The one-token form is the Pallas kernel the trace prints as
``kda_decode_kernel``: a row's state, ``heads * head_dim * head_dim``
float32 numbers a layer (2 MiB at 32 heads of 128), is read and written
once, and the token's ``q``, ``k``, ``v``, decays, ``beta`` and output
beside it; some 7 operations a state number, far under the chip's 240 a
byte, so the BYTES set its least time.

The chunked form takes a row of several tokens 64 at a time: a chunk
reads and writes the state once (a 64th of the one-token form's bytes a
token) and pays operations instead: a token's part of the pairwise
``k k^T`` and ``q k^T`` (2 x 2 x 64 x d), of the solve and its two
right-hand sides (2 x 64 x 2d), and of the four state matmuls (4 x 2 x d
x d), a head. ``least_seconds`` takes the larger of all bytes over the
bandwidth and the chunked operations over the bf16 peak (the program
computes them in float32 passes, which cost more: a lower bound). Since
PR 35 it is a Pallas kernel of its own, which the trace prints as
``kda_chunk_kernel``: ``kda_roofline`` reads the one-token kernel and is
given the decode rows alone (``TRACE_KERNELS`` below), ``kda_chunk_roofline``
reads the chunk kernel's time against the chunk tokens through the same
``least_seconds``; ``tools/probe_kda.py`` times either form by itself.
"""

# The names the trace prints for the kernel's calls (instance numbers
# removed), as read on the v5e.
TRACE_KERNELS = ("kda_decode_kernel",)
CHUNK = 64


def _geometry(config: dict) -> tuple[int, int]:
    la = config["linear_attn_config"]
    return la["num_heads"], la["head_dim"]


def state_bytes(config: dict) -> int:
    """One row's state of one layer, read and written."""
    heads, d = _geometry(config)
    return 2 * heads * d * d * 4


def token_bytes(config: dict) -> int:
    """A token's q, k, v, decays and output (float32) and its beta."""
    heads, d = _geometry(config)
    return heads * (5 * d + 1) * 4


def chunk_token_ops(config: dict) -> int:
    heads, d = _geometry(config)
    return heads * (4 * CHUNK * d + 4 * CHUNK * d + 8 * d * d)


def least_seconds(config: dict, decode_rows: float, chunk_tokens: float, peaks: dict) -> float:
    """``decode_rows``: rows of one token, ``chunk_tokens``: tokens of the
    other rows, both summed over the KDA layers
    (``vllm:linear_attn_tokens_total{kind}``)."""
    moved = (decode_rows * (state_bytes(config) + token_bytes(config))
             + chunk_tokens * (state_bytes(config) / CHUNK + token_bytes(config)))
    return max(moved / peaks["hbm_bytes_per_s"],
               chunk_tokens * chunk_token_ops(config) / peaks["bf16_flops_per_s"])


def kernel_seconds(trace: dict) -> float:
    return sum(s for name, s in trace["kernel_seconds"].items()
               if name.split(".")[0] in TRACE_KERNELS)
