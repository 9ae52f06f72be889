"""Layer: runner step (worker/model_runner.py). Of the programs the server
asked JAX's persistent compilation cache for up to the scrape that opens
the window, the share it had: ``vllm:compile_cache_total``, hits over hits
and misses (the whole process: the memory profile's program and the small
jits beside the step programs). Near 100 in a warm run and near 0 in the
run that compiles, so it tells the two apart, and shows a tree whose
"warm" run is not. None where the program has no such family, or asked the
cache for nothing."""


def read(ctx):
    m0 = ctx["m0"]
    hit = m0.get('vllm:compile_cache_total{result="hit"}')
    miss = m0.get('vllm:compile_cache_total{result="miss"}')
    if hit is None or miss is None or hit + miss == 0:
        return None
    return 100.0 * hit / (hit + miss)
