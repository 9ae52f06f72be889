"""Layer: runner step (worker/model_runner.py). Seconds between the two
scrapes over the engine steps between them: the mean time from one step to
the next, device work and host work together."""


def read(ctx):
    steps = ctx["steps"]()
    return 1e3 * ctx["scrape_dt"] / steps if steps else None
