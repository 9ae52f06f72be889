"""Layer: engine start (entrypoints/cli, engine/engine_core.py). Seconds
from the creation of the server's process to a bound socket that answers,
by the program's own clock: the sum of ``vllm:startup_seconds`` over its
phases (import, init_device, load_model, profile, kv_cache, precompile,
engine, frontend; each phase's seconds are its own, so they add up), at the
scrape that opens the window. With ``setup_first_use_s`` it leaves of
``setup_s`` what the benchmark's own protocol takes at a steady step. None
where the program has no such family."""

FAMILY = "vllm:startup_seconds{"


def read(ctx):
    phases = [v for k, v in ctx["m0"].items() if k.startswith(FAMILY)]
    return sum(phases) if phases else None
