"""Layer: scheduler (core/scheduler.py, counted where the runner builds the
step). Of the engine steps of the window, the share that were MIXED: a step
that carries a prompt's chunk and runs the ragged attention kernel for every
row, against the decode-only step that runs the decode kernel. Delta of
``vllm:engine_steps_total{kind="mixed"}`` over the summed deltas of both
kinds. It says which step each percentile of the gaps is: ``itl_p95_ms`` is
a mixed step wherever this reads over 5, ``itl_p50_ms`` a decode-only one
wherever it reads under 50. None where the program has no such counter, or
counted no step in the window (it read nothing in ``sdar30b.chat``, rows of
several tokens, which is why the entry lists its cells)."""


def read(ctx):
    mixed = ctx["delta"]('vllm:engine_steps_total{kind="mixed"}')
    decode = ctx["delta"]('vllm:engine_steps_total{kind="decode"}')
    if mixed is None or decode is None or not mixed + decode:
        return None
    return 100.0 * mixed / (mixed + decode)
