"""Smoke run of the serving path on a TPU: the quickest proof that the
system still starts on the chip and that what it computes there is right.

ONE PROCESS: the OpenAI server (``run_server``, in-process engine) runs
inside this script's own process, on the main thread; the HTTP client that
drives it is a thread of the same process. No child process is started, so
nothing else can want the chip while this process holds it.

``python chip_smoke.py`` (one chip) serves a Llama-3-8B-width model through
the OpenAI-compatible server with default engine settings, checks every
response, checks each Pallas kernel of the path against the repository's
XLA reference at the same widths, and prints the device facts and the
runner's counters. ``python chip_smoke.py --chips 4`` runs ONLY the
tensor-parallel phase: the same model under ``tensor_parallel_size=4`` and
under ``tensor_parallel_size=1`` through ``LLM.generate``, logprobs
compared. Any failed phase ends the process with a non-zero exit code and
no result line; so does a JAX that finds no TPU (``JAX_PLATFORMS=cpu``
included). The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Depth is cut from the published 32 layers to 16: 32 bf16 layers are 16 GB
of weights and the chip has 16 GB. No width is narrowed, nothing is
quantized; weights are ``load_format="dummy"`` from a fixed seed.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import faulthandler
import gc
import http.client
import json
import math
import os
import random
import signal
import socket
import sys
import threading
import time

import jax
import jax.numpy as jnp
import jaxlib
import numpy as np

from vllm_tpu.utils.compile_cache import enable_compilation_cache

OUT_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "chiprun_out", "chip_smoke"
)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Model widths and request sizes. The defaults are the smoke; a
    rehearsal on the CPU backend shrinks them from a scratch script."""

    hidden: int = 4096
    intermediate: int = 14336
    layers: int = 16  # published: 32 (cut: 32 bf16 layers do not fit 16 GB)
    heads: int = 32
    kv_heads: int = 8
    vocab: int = 128256
    max_position: int = 8192
    dtype: str = "bfloat16"
    seed: int = 0
    long_prompt: int = 1500
    long_out: int = 32
    batch_prompt: int = 128
    batch_out: int = 64
    batch: int = 8
    parity_rows: int = 128
    parity_tokens: int = 512
    parity_context: int = 1500  # decode rows; the XLA reference gathers
    parity_mixed_context: int = 640  # [T, context, KH, D] f32 per token
    tp_kv_blocks: int = 512
    # Extra engine arguments for the server. Empty in the smoke: default
    # engine settings, what the README's serve command gives.
    server_engine_kw: dict = dataclasses.field(default_factory=dict)
    # Pallas interpret mode for the kernel parity checks (CPU rehearsal).
    allow_interpret: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ----------------------------------------------------------------------
# JAX monitoring: compile seconds and persistent-cache hits.
# ----------------------------------------------------------------------

_compile = {"seconds": 0.0, "programs": 0, "cache_hits": 0, "cache_misses": 0}


def _watch_compiles() -> None:
    from jax import monitoring

    def on_duration(event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _compile["seconds"] += secs
            _compile["programs"] += 1

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            _compile["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _compile["cache_misses"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def device_facts() -> dict:
    devs = jax.devices()
    say(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}")
    say(f"jax.devices() = {devs}")
    for d in devs:
        say(f"{d} memory_stats() = {d.memory_stats()}")
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def write_model_dir(sz: Sizes) -> str:
    """A config-only checkpoint directory; nothing is downloaded and
    there is no tokenizer."""
    from transformers import LlamaConfig

    cfg = LlamaConfig(
        hidden_size=sz.hidden, intermediate_size=sz.intermediate,
        num_hidden_layers=sz.layers, num_attention_heads=sz.heads,
        num_key_value_heads=sz.kv_heads, vocab_size=sz.vocab,
        max_position_embeddings=sz.max_position, rope_theta=500000.0,
        rms_norm_eps=1e-5, tie_word_embeddings=False,
        torch_dtype=sz.dtype,
    )
    cfg.architectures = ["LlamaForCausalLM"]
    path = os.path.join(OUT_DIR, f"llama3_8b_width_{sz.layers}l")
    os.makedirs(path, exist_ok=True)
    cfg.save_pretrained(path)
    return path


def runner_of(engine):
    """The in-process engine's ModelRunner (see the verify skill)."""
    return engine.engine_core.engine_core.executor.worker.runner


def bytes_on(tree, device) -> int:
    return sum(
        s.data.nbytes
        for leaf in jax.tree_util.tree_leaves(tree)
        for s in leaf.addressable_shards
        if s.device == device
    )


# ----------------------------------------------------------------------
# Kernel parity at the model's widths, on the device path.
# ----------------------------------------------------------------------

# bf16 keeps 8 significant bits (eps = 2**-8 = 3.9e-3). The kernels and
# the XLA reference both accumulate scores and outputs in f32 but round q,
# k, v and the result to bf16 and walk the context in different block
# orders, so outputs of magnitude <= 1 may differ by a few bf16 ulps:
# 2e-2 absolute and relative, the tolerance of the repository's own bf16
# kernel test (tests/models/test_decode_attention.py).
ATTN_TOL = 2e-2


def _attention_case(sz: Sizes, rng, q_lens, kv_lens, decode_only: bool):
    from vllm_tpu.ops.attention import AttentionMetadata, kv_cache_shape

    page = 16
    r = len(q_lens)
    t = int(sum(q_lens))
    pages_per_seq = -(-max(kv_lens) // page)
    num_blocks = 1 + sum(-(-kv // page) for kv in kv_lens)
    q = jnp.asarray(
        rng.standard_normal((t, sz.heads, sz.head_dim)), jnp.bfloat16
    )
    kv = jnp.asarray(
        rng.standard_normal(
            kv_cache_shape(2, num_blocks, page, sz.kv_heads, sz.head_dim)
        ),
        jnp.bfloat16,
    )
    tables = np.zeros((r, pages_per_seq), np.int32)
    nxt = 1
    for i, kv_len in enumerate(kv_lens):
        n = -(-kv_len // page)
        tables[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    starts = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
    # A request's query tokens are the last q_len positions of its context.
    positions = np.concatenate(
        [np.arange(kv - ql, kv) for ql, kv in zip(q_lens, kv_lens)]
    ).astype(np.int32)
    md = AttentionMetadata(
        positions=jnp.asarray(positions),
        token_req_idx=jnp.asarray(np.repeat(np.arange(r), q_lens), jnp.int32),
        slot_mapping=None, logits_indices=None,
        block_tables=jnp.asarray(tables),
        seq_lens=jnp.asarray(kv_lens, jnp.int32),
        query_start_loc=jnp.asarray(starts),
        num_seqs=jnp.asarray([r], jnp.int32),
        decode_only=decode_only,
    )
    return q, kv, md


def _attention_parity(sz: Sizes, name: str, q, kv, md) -> None:
    from vllm_tpu.ops.attention import (
        dispatch_ragged_attention,
        ref_ragged_paged_attention,
    )

    scale = sz.head_dim ** -0.5
    layer = jnp.int32(1)
    got = jax.jit(
        lambda q, kv, md: dispatch_ragged_attention(
            q, kv, layer, md, scale, allow_interpret=sz.allow_interpret
        )
    )(q, kv, md)
    want = jax.jit(
        lambda q, kv, md: ref_ragged_paged_attention(q, kv, layer, md, scale)
    )(q, kv, md)
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(np.isfinite(got).all(), f"{name}: non-finite kernel output")
    err = float(np.max(np.abs(got - want) - ATTN_TOL * np.abs(want)))
    say(
        f"parity {name}: q {q.shape} kv {kv.shape}, max |kernel - ref| = "
        f"{float(np.max(np.abs(got - want))):.3e} "
        f"(tolerance {ATTN_TOL} abs + {ATTN_TOL} rel)"
    )
    check(err <= ATTN_TOL, f"{name}: kernel differs from the XLA reference")


def _sampler_parity(sz: Sizes, rng) -> None:
    """Greedy rows through both variants of the fused sampler kernel must
    return exactly ``argmax(logits)``: integer output, tolerance 0 (the
    top-k/top-p/penalty machinery runs, with neutral values)."""
    from vllm_tpu.sample.sampler import SamplingMetadata, dispatch_sample

    r, v = sz.parity_rows, sz.vocab
    logits = jnp.asarray(rng.standard_normal((r, v)) * 4.0, jnp.float32)
    want = np.asarray(jnp.argmax(logits, axis=-1))
    for penalties in (False, True):
        md = SamplingMetadata(
            temperature=jnp.zeros((r,), jnp.float32),
            top_k=jnp.full((r,), 50, jnp.int32),
            top_p=jnp.full((r,), 0.9, jnp.float32),
            min_p=jnp.zeros((r,), jnp.float32),
            presence_penalty=jnp.zeros((r,), jnp.float32),
            frequency_penalty=jnp.zeros((r,), jnp.float32),
            repetition_penalty=jnp.ones((r,), jnp.float32),
            prng_keys=jnp.asarray(
                rng.integers(0, 2**32, (r, 2), dtype=np.uint32)
            ),
            output_token_counts=(
                jnp.zeros((r, v), jnp.int32) if penalties
                else jnp.zeros((0, 0), jnp.int32)
            ),
            prompt_token_mask=(
                jnp.zeros((r, v), bool) if penalties
                else jnp.zeros((0, 0), bool)
            ),
        )
        got, _ = jax.jit(
            lambda lg, md: dispatch_sample(
                lg, md, needs_penalties=penalties, needs_gumbel=True,
                allow_interpret=sz.allow_interpret,
            )
        )(logits, md)
        name = "sampler kernel" + (" (penalties)" if penalties else "")
        wrong = int(np.sum(np.asarray(got) != want))
        say(f"parity {name}: logits {logits.shape}, rows != argmax: {wrong} "
            "(tolerance 0)")
        check(wrong == 0, f"{name}: greedy rows differ from argmax")


def kernel_parity(sz: Sizes) -> None:
    from vllm_tpu.ops import attention
    from vllm_tpu.sample.sampler import sampler_kernel_eligible

    check(attention._on_tpu() or sz.allow_interpret,
          "attention dispatch would not take the kernels")
    check(
        sampler_kernel_eligible(
            sz.vocab, needs_gumbel=True, allow_interpret=sz.allow_interpret
        )[0],
        "sampler dispatch would not take the kernel",
    )
    rng = np.random.default_rng(sz.seed)
    r = sz.parity_rows
    kv_lens = [int(x) for x in rng.integers(1, sz.parity_context, r)]
    q, kv, md = _attention_case(sz, rng, [1] * r, kv_lens, decode_only=True)
    _attention_parity(sz, "decode kernel", q, kv, md)
    # Mixed batch: two prefill chunks (one from position 0, one resumed)
    # and single-token decodes filling the rest of the token bucket.
    half = sz.parity_tokens // 2
    n_dec = sz.parity_tokens - half - half // 2
    q_lens = [half, half // 2] + [1] * n_dec
    kv_lens = [half, sz.parity_mixed_context] + [
        int(x) for x in rng.integers(1, sz.parity_mixed_context, n_dec)
    ]
    q, kv, md = _attention_case(sz, rng, q_lens, kv_lens, decode_only=False)
    _attention_parity(sz, "ragged kernel", q, kv, md)
    _sampler_parity(sz, rng)
    del q, kv, md
    gc.collect()


# ----------------------------------------------------------------------
# One chip: the OpenAI server, driven over HTTP.
# ----------------------------------------------------------------------


class Client:
    def __init__(self, port: int, model: str, sz: Sizes) -> None:
        self.port, self.model, self.sz = port, model, sz
        self.rng = random.Random(sz.seed)

    def prompt(self, n: int) -> list[int]:
        return [self.rng.randrange(self.sz.vocab) for _ in range(n)]

    def _post(self, body: dict, timeout: float = 900.0):
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=timeout)
        conn.request(
            "POST", "/v1/completions",
            body=json.dumps({"model": self.model, "ignore_eos": True, **body}),
            headers={"Content-Type": "application/json"},
        )
        return conn, conn.getresponse()

    def complete(self, **body) -> dict:
        conn, resp = self._post(body)
        data = resp.read()
        conn.close()
        check(resp.status == 200, f"HTTP {resp.status}: {data[:300]!r}")
        out = json.loads(data)
        check(
            out["usage"]["completion_tokens"] == body["max_tokens"],
            f"asked {body['max_tokens']} tokens, got {out['usage']}",
        )
        check(out["usage"]["prompt_tokens"] == len(body["prompt"]),
              f"prompt token count: {out['usage']}")
        return out

    def stream(self, **body) -> int:
        """Server-sent events; returns the number of data events. Without
        a tokenizer an event carries no text, so the token-level checks
        are made on the non-streamed repeat of the same prompts."""
        conn, resp = self._post({**body, "stream": True})
        check(resp.status == 200, f"HTTP {resp.status} on stream")
        check("text/event-stream" in resp.getheader("Content-Type", ""),
              "stream is not an event stream")
        events, done, finish = 0, False, None
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            if line == "data: [DONE]":
                done = True
                break
            chunk = json.loads(line[len("data: "):])
            check("error" not in chunk, f"stream error: {chunk}")
            events += 1
            finish = chunk["choices"][0]["finish_reason"] or finish
        conn.close()
        check(done, "stream ended without [DONE]")
        check(finish == "length", f"stream finish_reason {finish!r}")
        check(1 <= events <= body["max_tokens"] + 1,
              f"{events} events for {body['max_tokens']} tokens")
        return events

    def wait_ready(self, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while True:
            try:
                conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                                  timeout=5)
                conn.request("GET", "/health")
                if conn.getresponse().status == 200:
                    return
            except OSError:
                pass
            check(time.monotonic() < deadline, "server did not come up")
            time.sleep(0.5)


def _tokens(out: dict) -> list[str]:
    return out["choices"][0]["logprobs"]["tokens"]


def _check_logprobs(out: dict, k: int) -> None:
    lp = out["choices"][0]["logprobs"]
    check(len(lp["tokens"]) == out["usage"]["completion_tokens"],
          "logprob entries != tokens")
    for sampled, top in zip(lp["token_logprobs"], lp["top_logprobs"]):
        vals = list(top.values())
        check(len(vals) >= k, f"asked {k} logprobs, got {len(vals)}")
        check(all(math.isfinite(x) and x <= 1e-6 for x in [sampled, *vals]),
              f"logprobs not finite or positive: {sampled} {vals}")


def drive_requests(client: Client, timings: dict) -> None:
    sz = client.sz
    t0 = time.monotonic()
    client.complete(prompt=client.prompt(sz.long_prompt),
                    max_tokens=sz.long_out, temperature=0.0)
    timings["long prefill"] = time.monotonic() - t0
    say(f"long prefill: {sz.long_prompt} in / {sz.long_out} out ok")

    prompts = [client.prompt(sz.batch_prompt) for _ in range(sz.batch - 1)]
    prompts.append(prompts[0])  # two requests share one prompt
    t0 = time.monotonic()
    with concurrent.futures.ThreadPoolExecutor(sz.batch) as pool:
        events = list(pool.map(
            lambda p: client.stream(prompt=p, max_tokens=sz.batch_out,
                                    temperature=0.0),
            prompts,
        ))
        timings["streamed batch"] = time.monotonic() - t0
        say(f"{sz.batch} concurrent streamed requests ({sz.batch_prompt} in /"
            f" {sz.batch_out} out) ok, events per stream {events}")
        t0 = time.monotonic()
        outs = list(pool.map(
            lambda p: client.complete(prompt=p, max_tokens=sz.batch_out,
                                      temperature=0.0, logprobs=1),
            prompts,
        ))
        timings["token-checked batch"] = time.monotonic() - t0
    for out in outs:
        _check_logprobs(out, 1)
    check(_tokens(outs[0]) == _tokens(outs[-1]),
          "two greedy requests with one prompt gave different tokens: "
          f"{_tokens(outs[0])} vs {_tokens(outs[-1])}")
    check(all(t.isdigit() for out in outs for t in _tokens(out)),
          "expected token ids as strings (no tokenizer)")
    check(len({tuple(_tokens(out)) for out in outs}) == sz.batch - 1,
          "different prompts gave identical token sequences")
    say(f"{sz.batch} concurrent greedy requests ok; the two that share a "
        f"prompt are token-identical ({_tokens(outs[0])[:6]} ...)")

    t0 = time.monotonic()
    client.complete(prompt=client.prompt(sz.batch_prompt),
                    max_tokens=sz.batch_out, temperature=0.8, top_p=0.9,
                    top_k=50, seed=1)
    client.complete(prompt=client.prompt(sz.batch_prompt),
                    max_tokens=sz.batch_out, temperature=0.8, seed=2,
                    repetition_penalty=1.1, presence_penalty=0.5)
    timings["sampled requests"] = time.monotonic() - t0
    say("sampled request (temperature/top_p/top_k/seed) and penalties "
        "request ok")
    t0 = time.monotonic()
    out = client.complete(prompt=client.prompt(sz.batch_prompt),
                          max_tokens=sz.batch_out, temperature=0.0,
                          logprobs=5)
    _check_logprobs(out, 5)
    timings["logprobs request"] = time.monotonic() - t0
    say("logprobs=5 request ok, all finite")


def serve_one_chip(sz: Sizes, model_dir: str) -> None:
    from vllm_tpu.engine.arg_utils import AsyncEngineArgs
    from vllm_tpu.engine.async_llm import AsyncLLM
    from vllm_tpu.entrypoints.openai.api_server import run_server

    # run_server builds its engine itself; keep a handle on it so the
    # runner's counters can be read after it has drained.
    engines: list[AsyncLLM] = []
    make_engine = AsyncLLM.from_engine_args

    def keep(engine_args):
        engines.append(make_engine(engine_args))
        return engines[-1]

    AsyncLLM.from_engine_args = keep

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    client = Client(port, model_dir, sz)
    failure: list[BaseException] = []
    timings: dict[str, float] = {}

    def drive() -> None:
        try:
            client.wait_ready(timeout=900.0)
            timings["engine start"] = time.monotonic() - t_start
            drive_requests(client, timings)
        except BaseException as exc:  # noqa: BLE001 - re-raised on the main thread
            failure.append(exc)
        finally:
            os.kill(os.getpid(), signal.SIGTERM)  # run_server drains, returns

    t_start = time.monotonic()
    compile_before = dict(_compile)
    threading.Thread(target=drive, name="smoke-client", daemon=True).start()
    # Default engine settings: what the README's serve command gives.
    run_server(
        AsyncEngineArgs(model=model_dir, load_format="dummy", seed=sz.seed,
                        **sz.server_engine_kw),
        host="127.0.0.1", port=port,
    )
    AsyncLLM.from_engine_args = make_engine
    if failure:
        raise failure[0]

    wall = time.monotonic() - t_start
    compiled = _compile["seconds"] - compile_before["seconds"]
    say("seconds: " + ", ".join(f"{k} {v:.1f}" for k, v in timings.items()))
    say(f"seconds in XLA/Mosaic compilation (or cache load) "
        f"{compiled:.1f} of {wall:.1f} wall; "
        f"serving requests {sum(timings.values()) - timings['engine start']:.1f}")
    runner = runner_of(engines[0])
    dev = jax.devices()[0]
    say(f"weights on chip: {bytes_on(runner.params, dev)} bytes; "
        f"KV cache on chip: {bytes_on(runner.kv_cache, dev)} bytes "
        f"{runner.kv_cache.shape} {runner.kv_cache.dtype}")
    counters = {
        name: getattr(runner, name)
        for name in ("step_launches", "decode_only_launches",
                     "sampler_kernel_launches", "sampler_fallback_rows",
                     "bucket_compiles", "bucket_hits")
    }
    say(f"runner counters: {counters}")
    say(f"{dev} memory_stats() after serving = {dev.memory_stats()}")
    for name in ("step_launches", "decode_only_launches",
                 "sampler_kernel_launches"):
        check(counters[name] > 0, f"runner counter {name} did not move")
    check(counters["sampler_fallback_rows"] == 0,
          "sampled rows fell back to the XLA epilogue")


# ----------------------------------------------------------------------
# Four chips: tensor parallelism against one chip, through LLM.generate.
# ----------------------------------------------------------------------

# Logprobs of the first generated token (same prompt, same weights): the
# tp=4 step sums each row-parallel matmul's four bf16 partial products in
# an all-reduce where tp=1 accumulates one f32 dot, and 16 layers compound
# the rounding; 0.1 nat absolute on logprobs of magnitude ~10.
TP_LOGPROB_TOL = 0.1


def _generate(sz: Sizes, model_dir: str, tp: int, prompts) -> tuple:
    from vllm_tpu import LLM, SamplingParams

    say(f"--- tensor_parallel_size={tp} ---")
    llm = LLM(model=model_dir, load_format="dummy", seed=sz.seed,
              tensor_parallel_size=tp, num_gpu_blocks_override=sz.tp_kv_blocks,
              max_model_len=2048)
    runner = runner_of(llm.llm_engine)
    texts: dict[bool, str] = {}
    step_fn = runner._step_fn

    def spy(*args, **kw):
        """Keep the compiled text of the first decode-only and of the
        first mixed step (the step the runner really launches)."""
        kind = bool(kw.get("decode_only"))
        if kind not in texts:
            texts[kind] = step_fn.lower(*args, **kw).compile().as_text()
        return step_fn(*args, **kw)

    runner._step_fn = spy
    outs = llm.generate(
        [{"prompt_token_ids": p} for p in prompts],
        SamplingParams(temperature=0.0, max_tokens=8, logprobs=5,
                       ignore_eos=True),
    )
    first = [
        {tid: lp.logprob for tid, lp in o.outputs[0].logprobs[0].items()}
        for o in outs
    ]
    devs = jax.devices()[:tp]
    weights = [bytes_on(runner.params, d) for d in devs]
    kv = [bytes_on(runner.kv_cache, d) for d in devs]
    say(f"weight bytes per device: {weights}")
    say(f"KV bytes per device: {kv} {runner.kv_cache.shape}")
    for d in jax.devices():
        say(f"{d} memory_stats() = {d.memory_stats()}")
    llm.shutdown()
    del llm, runner, outs
    gc.collect()
    return first, weights, kv, texts


def tensor_parallel_phase(sz: Sizes, model_dir: str) -> None:
    check(len(jax.devices()) >= 4, f"need 4 devices, have {jax.devices()}")
    rng = random.Random(sz.seed)
    prompts = [[rng.randrange(sz.vocab) for _ in range(sz.batch_prompt)]
               for _ in range(sz.batch)]
    lp4, w4, kv4, texts = _generate(sz, model_dir, 4, prompts)
    lp1, w1, kv1, _ = _generate(sz, model_dir, 1, prompts)

    for name, per_dev, whole in (("weights", w4, w1[0]), ("KV", kv4, kv1[0])):
        for b in per_dev:
            # Norm vectors are replicated; everything else is sharded.
            check(0.24 * whole <= b <= 0.27 * whole,
                  f"{name}: a device holds {b} bytes of {whole}, not a quarter")
    worst = 0.0
    for a, b in zip(lp4, lp1):
        common = set(a) & set(b)
        check(len(common) >= 3, f"top-5 token sets barely overlap: {a} {b}")
        check(all(math.isfinite(x) for x in [*a.values(), *b.values()]),
              "non-finite logprob")
        worst = max(worst, max(abs(a[t] - b[t]) for t in common))
    say(f"first-token logprobs tp=4 vs tp=1 over {len(lp4)} prompts: "
        f"max |diff| = {worst:.4f} nat (tolerance {TP_LOGPROB_TOL})")
    check(worst <= TP_LOGPROB_TOL, "tp=4 logprobs differ from tp=1")

    for kind, text in sorted(texts.items()):
        name = "decode-only step" if kind else "mixed step"
        has_kernel = "tpu_custom_call" in text
        has_reduce = "all-reduce" in text
        say(f"compiled tp=4 {name}: tpu_custom_call {has_kernel}, "
            f"all-reduce {has_reduce}")
        check(has_kernel and has_reduce, f"tp=4 {name} lacks kernel or all-reduce")
    check(set(texts) == {True, False}, "did not see both step kinds")


def run(sz: Sizes, chips: int) -> dict:
    _watch_compiles()
    enable_compilation_cache()
    say(f"compilation cache: {jax.config.jax_compilation_cache_dir}")
    device = device_facts()
    say(f"model: Llama-3-8B widths (hidden {sz.hidden}, intermediate "
        f"{sz.intermediate}, {sz.heads} q / {sz.kv_heads} kv heads, head_dim "
        f"{sz.head_dim}, vocab {sz.vocab}, {sz.dtype}), depth cut 32 -> "
        f"{sz.layers} layers, dummy weights from seed {sz.seed}")
    model_dir = write_model_dir(sz)
    if chips == 4:
        tensor_parallel_phase(sz, model_dir)
    else:
        kernel_parity(sz)
        serve_one_chip(sz, model_dir)
    say(f"compile cache: {_compile['cache_hits']} hits, "
        f"{_compile['cache_misses']} misses, {_compile['programs']} programs, "
        f"{_compile['seconds']:.1f} s")
    return device


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; jax found {platform!r}")
    # Fail loudly, with every thread's stack, before a 1200 s limit.
    faulthandler.dump_traceback_later(
        1150 if args.chips == 1 else 3000, exit=True
    )
    device = run(Sizes(), args.chips)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
