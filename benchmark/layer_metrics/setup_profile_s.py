"""Layer: worker (profile_step_memory, resize_kv_cache). Seconds of the
start spent sizing and allocating the KV cache and precompiling: the
provisional runner with the memory profile (an ahead-of-time compile of the
largest step program), ``determine_num_kv_blocks`` with
``resize_kv_cache``, and ``compile_or_warm_up_model`` (0 where the flag is
off): ``vllm:startup_seconds`` over those three phases at the scrape that
opens the window. None where the program has no such series."""


def read(ctx):
    m0 = ctx["m0"]
    keys = [f'vllm:startup_seconds{{phase="{p}"}}' for p in ("profile", "kv_cache", "precompile")]
    return sum(m0[k] for k in keys) if all(k in m0 for k in keys) else None
