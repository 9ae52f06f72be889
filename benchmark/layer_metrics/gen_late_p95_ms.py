"""Layer: load generator (benchmark). How late the generator sent: the 95th
percentile, over requests due in the window, of actual send minus due time.
A starved generator must not be read as a fast server. Source: the client's
records."""


def read(ctx):
    return ctx["facts"].get("gen_late_p95_ms")
