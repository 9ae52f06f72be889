"""Layer: scheduler (core/scheduler.py). Positions committed a pass under
generation by diffusion over blocks: delta of
``vllm:diffusion_committed_tokens_total`` over the delta of
``vllm:diffusion_passes_total``, denoising and store passes together (a
store pass commits nothing and is the price of a block). A block of four
masks under two denoising steps is 4 tokens in 2 passes (the
clean block's store rides with the next block's first denoising pass, and
is counted there, once) and in 3 where the step has no room for that; a
request's first block opens with its prompt's last ``P mod 4`` tokens and
commits fewer.
None where the program has no such counters."""

PASSES = "vllm:diffusion_passes_total"


def read(ctx):
    committed = ctx["delta"]("vllm:diffusion_committed_tokens_total")
    passes = [ctx["delta"](f'{PASSES}{{kind="{k}"}}') for k in ("denoise", "store")]
    if committed is None or None in passes or not sum(passes):
        return None
    return committed / sum(passes)
