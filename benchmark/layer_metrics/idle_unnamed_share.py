"""Layer: device. Of the seconds of the idle gaps the trace reduction lists
(the ten longest), the share whose label is not one of the program's own
spans (``vllm_tpu.<layer>.<phase>``, written on the profiler's clock while a
profile runs): a gap nobody can say the cause of. 100 for a program that
writes no such spans."""


def read(ctx):
    gaps = (ctx.get("trace") or {}).get("idle_gaps") or []
    total = sum(seconds for _, seconds in gaps)
    if total <= 0:
        return None
    unnamed = sum(seconds for label, seconds in gaps if "vllm_tpu." not in label)
    return 100.0 * unnamed / total
