"""Layer: device. 1 minus the union of the intervals in which an operation
ran on the device, over the traced slices (each from its first to its last
device event, added up; several slices spread over the window), averaged
over the chips used. Source: the device trace."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
