"""Layer: HTTP front end (engine/output_processor.py and what feeds it).
Host milliseconds a step spends after its tokens are on the host: the
scheduler folding them in (phase ``update``) and the front end
detokenising and delivering them to the streams (phase ``output``), both
delta sums of ``vllm:engine_step_duration_seconds`` over the engine steps
in the window."""


def read(ctx):
    steps = ctx["steps"]()
    parts = [ctx["delta"](f'vllm:engine_step_duration_seconds_sum{{phase="{p}"}}')
             for p in ("update", "output")]
    if not steps or None in parts:
        return None
    return 1e3 * sum(parts) / steps
