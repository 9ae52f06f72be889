"""Generation by diffusion over blocks (``models/generation.py``), held on
the CPU at a tiny size against the benchmark's plain reference
(``benchmark/reference/sdar_moe.py``): the model's forward pass, the engine
end to end (offline and over the OpenAI server), the ragged kernel's mask
over blocks, the sampler's probability of the drawn token, and what the
mechanism refuses."""

from __future__ import annotations

import asyncio
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.manifest import BENCH_DIR, load_module
from tests.benchmark.tiny_sdar_moe import TINY_SDAR, model_dir
from vllm_tpu.ops.attention import AttentionMetadata, kv_cache_shape, packed_kv_layout

REF = load_module(BENCH_DIR / "reference" / "sdar_moe.py")
SEED = 3
B = TINY_SDAR["block_length"]
# float32 program against float32 reference on the CPU: the order of
# float32 sums only (the program pages and batches), on logits of size 1-3.
TOL_F32 = 5e-4
# bfloat16 program (bf16 activations and matmuls) against the float32
# reference over the same bf16-held weights, on logits of size 1-3: the
# median, and a far looser bound on the worst number, because where two
# experts' router weights are nearly equal bf16 picks the other one and
# that row moves by the difference of two experts' outputs.
TOL_BF16 = 0.05
TOL_BF16_WORST = 1.5


def close(got, want, dtype):
    diff = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    if dtype == jnp.float32:
        return float(diff.max()) < TOL_F32
    return float(np.median(diff)) < TOL_BF16 and float(diff.max()) < TOL_BF16_WORST


@pytest.fixture(scope="module")
def weights():
    return REF.seeded_weights(TINY_SDAR, SEED, jnp.float32)


# ---------------------------------------------------------------------------
# (a) SDARMoeForCausalLM.apply against block_forward
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def model_and_params(request):
    from transformers import PretrainedConfig

    from vllm_tpu.models.registry import get_model_class

    dtype = jnp.dtype(request.param)
    cfg = PretrainedConfig.from_dict(TINY_SDAR)
    model = get_model_class(cfg)(cfg, dtype=dtype)
    assert model.generation == "block_diffusion"
    return model, model.init_dummy_params(jax.random.PRNGKey(SEED)), dtype


def run_apply(model, params, dtype, ids):
    """One sequence through ``apply`` with the mask over blocks, on the XLA
    path: logits [T, V] and the paged cache it wrote."""
    t, page = len(ids), 16
    pages = -(-t // page)
    pos = np.arange(t, dtype=np.int32)
    table = np.arange(1, pages + 1, dtype=np.int32)
    md = AttentionMetadata(
        positions=jnp.asarray(pos),
        slot_mapping=jnp.asarray(table[pos // page] * page + pos % page),
        block_tables=jnp.asarray(table[None, :]),
        seq_lens=jnp.asarray([t], jnp.int32),
        query_start_loc=jnp.asarray([0, t], jnp.int32),
        token_req_idx=jnp.zeros(t, jnp.int32),
        logits_indices=jnp.asarray([t - 1], jnp.int32),
        num_seqs=jnp.asarray([1], jnp.int32), block_len=B,
    )
    kv = jnp.zeros(kv_cache_shape(model.num_layers, pages + 1, page,
                                  model.num_kv_heads, model.head_dim), dtype)
    hidden, kv = model.apply(params, kv, jnp.asarray(ids, jnp.int32), md)
    return model.compute_logits(params, hidden), kv


# Every shape of the open block (the 16 masks of 4 positions) behind two
# clean blocks.
MASKS = list(itertools.product([True, False], repeat=B))


@pytest.mark.parametrize("mask", MASKS, ids=lambda m: "".join("c" if c else "m" for c in m))
def test_apply_matches_block_forward_for_every_shape_of_the_block(model_and_params, mask):
    model, params, dtype = model_and_params
    rng = np.random.default_rng(17)
    tokens = rng.integers(0, 240, 3 * B).tolist()
    committed = [True] * (2 * B) + list(mask)
    weights = REF.seeded_weights(TINY_SDAR, SEED, dtype)
    want, want_k, want_v = REF.block_forward(TINY_SDAR, weights, tokens, committed, return_kv=True)
    ids = np.where(committed, tokens, TINY_SDAR["mask_token_id"])
    got, kv = run_apply(model, params, dtype, ids)
    assert close(got, want, dtype)
    # The K/V written: page 1 of every layer holds the 12 positions.
    page = np.asarray(kv[:, 1, : 3 * B], np.float32)
    d = model.head_dim
    if packed_kv_layout(d):  # a head of 16: K beside V on the lane axis
        got_k, got_v = page[..., :d], page[..., d:]
    else:
        got_k, got_v = page[:, :, 0::2], page[:, :, 1::2]
    assert close(got_k, want_k, dtype) and close(got_v, want_v, dtype)


# ---------------------------------------------------------------------------
# (b) the engine end to end against generate()
# ---------------------------------------------------------------------------


def make_llm(tmp_path, **kw):
    from vllm_tpu import LLM

    args = dict(model=model_dir(tmp_path), dtype="float32", load_format="dummy", seed=SEED,
                max_model_len=128, block_size=16, num_gpu_blocks_override=64,
                max_num_seqs=8, max_num_batched_tokens=64)
    return LLM(**{**args, **kw})


@pytest.fixture(scope="module", params=[True, False], ids=["lag_pipeline", "one_step_at_a_time"])
def llm(request, tmp_path_factory):
    # 22 tokens a step: a prompt's chunks would end inside a block (22, 44)
    # were they not cut back to a multiple of 4.
    engine = make_llm(tmp_path_factory.mktemp("sdar"), async_scheduling=request.param,
                      max_num_batched_tokens=22)
    yield engine
    engine.shutdown()


def params_for(rule="sequential", steps=4, **kw):
    from vllm_tpu import SamplingParams

    return SamplingParams(temperature=0.0, ignore_eos=True, remasking=rule,
                          denoising_steps=steps, confidence_threshold=0.02,
                          **{"max_tokens": 10, **kw})


def prompt_of(n, seed=0):
    return np.random.default_rng(seed + n).integers(0, 240, n).tolist()


def check_against_generate(weights, prompt, out, rule, steps, max_tokens=10):
    want = REF.generate(TINY_SDAR, weights, prompt, max_tokens, rule, steps, 0.02)
    assert list(out.token_ids) == want["tokens"]
    assert out.commit_passes == want["commit_passes"]
    if out.logprobs:
        for tok, top, row in zip(out.token_ids, out.logprobs, want["logits"]):
            logp = np.asarray(jax.nn.log_softmax(jnp.asarray(row)))
            for t, lp in top.items():
                assert abs(float(logp[t]) - lp.logprob) < TOL_F32
            assert tok in top


RULES = [("sequential", 4), ("sequential", 2), ("low_confidence_static", 2),
         ("low_confidence_static", 1), ("low_confidence_dynamic", 4)]


@pytest.mark.parametrize("rule,steps", RULES)
def test_engine_generates_as_the_reference_does(llm, weights, rule, steps):
    """Prompts with P mod 4 in 0..3, shorter than a block, and longer than
    the 22 tokens a step holds (chunked prefill)."""
    prompts = [prompt_of(n) for n in (8, 9, 10, 11, 1, 3, 50)]
    outs = llm.generate([{"prompt_token_ids": p} for p in prompts],
                        params_for(rule, steps, logprobs=3))
    for p, o in zip(prompts, outs):
        check_against_generate(weights, p, o.outputs[0], rule, steps)


@pytest.mark.parametrize("max_tokens", [1, 2, 5, 7])
def test_max_tokens_cuts_inside_a_block(llm, weights, max_tokens):
    p = prompt_of(9)
    out = llm.generate([{"prompt_token_ids": p}],
                       params_for("low_confidence_static", 2, max_tokens=max_tokens))[0].outputs[0]
    assert out.finish_reason == "length" and len(out.token_ids) == max_tokens
    check_against_generate(weights, p, out, "low_confidence_static", 2, max_tokens)


def test_a_stop_token_inside_a_block_ends_the_answer_there(llm, weights):
    p = prompt_of(10)
    want = REF.generate(TINY_SDAR, weights, p, 10, "sequential", 2, 0.02)["tokens"]
    cut = next(i for i in range(1, 10) if (len(p) + i) % B and want[i] not in want[:i])
    out = llm.generate([{"prompt_token_ids": p}],
                       params_for("sequential", 2, stop_token_ids=[want[cut]]))[0].outputs[0]
    assert list(out.token_ids) == want[: cut + 1]
    assert out.finish_reason == "stop" and out.stop_reason == want[cut]


def test_requests_under_different_rules_share_a_step(llm, weights):
    prompts = [prompt_of(n, 7) for n in (6, 13, 21, 9, 30)]
    ps = [params_for(r, s) for r, s in RULES]
    runner = llm.llm_engine.engine_core.engine_core.executor.worker.runner
    programs = runner.bucket_compiles
    llm.generate([{"prompt_token_ids": prompt_of(5, 7)}], ps[0])
    outs = llm.generate([{"prompt_token_ids": p} for p in prompts], ps)
    for p, o, (rule, steps) in zip(prompts, outs, RULES):
        check_against_generate(weights, p, o.outputs[0], rule, steps)
    # Rule, count and threshold are data of one step program: five rules
    # in a batch met no program that one rule alone had not.
    assert runner.bucket_compiles - programs <= 3
    assert runner.diffusion_passes_by_kind["store"] > 0
    assert runner.diffusion_committed_tokens > 0 and runner.moe_experts_hit > 0


def test_sampled_block_passes_are_counted_where_the_sampler_ran(tmp_path):
    """A sampling block pass goes to the fused kernel (here in interpret
    mode) with the drawn token's probability and the count of usable
    positions, and counts as a kernel launch; with the kernel turned off
    every position of its sampling rows is a fallback row. Both draw the
    same tokens: at this width the two paths agree bit for bit."""
    from vllm_tpu import SamplingParams

    prompts = [{"prompt_token_ids": prompt_of(n, 5)} for n in (6, 13, 9)]
    sp = SamplingParams(temperature=0.8, top_p=0.9, seed=11, ignore_eos=True, max_tokens=10,
                        remasking="low_confidence_static", denoising_steps=2)
    tokens = {}
    for enable in (True, False):
        engine = make_llm(tmp_path / str(enable), enable_sampler_kernel=enable)
        try:
            runner = engine.llm_engine.engine_core.engine_core.executor.worker.runner
            outs = engine.generate(prompts, sp)
            tokens[enable] = [list(o.outputs[0].token_ids) for o in outs]
            denoise = runner.diffusion_passes_by_kind["denoise"]
            assert denoise > 0 and runner.diffusion_committed_tokens >= 30
            if enable:
                assert runner.sampler_kernel_launches > 0
                assert runner.sampler_fallback_rows == 0
            else:
                assert runner.sampler_kernel_launches == 0
                assert runner.sampler_fallback_rows == denoise * B
        finally:
            engine.shutdown()
    assert tokens[True] == tokens[False]
    assert len({tuple(t) for t in tokens[True]}) == 3


def test_the_second_ask_of_a_prompt_hits_the_prefix_cache(llm, weights):
    p = prompt_of(41, 3)
    first = llm.generate([{"prompt_token_ids": p}], params_for("low_confidence_static", 2))[0]
    again = llm.generate([{"prompt_token_ids": p}], params_for("low_confidence_static", 2))[0]
    assert list(first.outputs[0].token_ids) == list(again.outputs[0].token_ids)
    # Two pages of 16 are stored and clean; the third holds the open block.
    assert again.num_cached_tokens == 32
    check_against_generate(weights, p, again.outputs[0], "low_confidence_static", 2)


@pytest.mark.parametrize("async_scheduling", [True, False])
def test_preemption_in_the_middle_of_a_block_resumes_with_the_same_tokens(
        tmp_path, weights, async_scheduling):
    """Nine pages for four requests that each grow to three: the scheduler
    preempts, and a resumed request prefills its tokens so far and reopens
    the block it held."""
    llm = make_llm(tmp_path, async_scheduling=async_scheduling, num_gpu_blocks_override=9,
                   enable_prefix_caching=False)
    prompts = [prompt_of(n, 5) for n in (14, 15, 17, 13)]
    outs = llm.generate([{"prompt_token_ids": p} for p in prompts],
                        params_for("low_confidence_static", 2, max_tokens=30))
    sched = llm.llm_engine.engine_core.engine_core.scheduler
    assert sched.make_stats().num_preempted_reqs > 0
    llm.shutdown()
    for p, o in zip(prompts, outs):
        want = REF.generate(TINY_SDAR, weights, p, 30, "low_confidence_static", 2, 0.02)
        assert list(o.outputs[0].token_ids) == want["tokens"]


def runner_of(engine):
    return engine.llm_engine.engine_core.engine_core.executor.worker.runner


def block_counts(runner):
    return (runner.diffusion_passes_by_kind["denoise"], runner.diffusion_passes_by_kind["store"],
            runner.diffusion_stores_fused, runner.diffusion_committed_tokens)


@pytest.fixture(scope="module")
def roomy(tmp_path_factory):
    """One step at a time and 64 tokens a step: every clean block has room
    for the next behind it, and the counters are exact."""
    engine = make_llm(tmp_path_factory.mktemp("roomy"), async_scheduling=False)
    yield engine
    engine.shutdown()


@pytest.mark.parametrize("rule,steps", [("sequential", 4), ("sequential", 2), ("sequential", 1),
                                        ("low_confidence_static", 2), ("low_confidence_static", 4)])
def test_a_block_after_the_first_costs_its_denoising_passes_and_no_store(roomy, weights, rule, steps):
    """Five whole blocks: each store but the last block's (the request ends
    with its last token) rides with the next block's first pass, so the
    model runs ``denoising_steps`` passes a block and the counters say so;
    tokens and pass numbers are the reference's all the same."""
    before = block_counts(runner_of(roomy))
    p = prompt_of(8, 21)
    out = roomy.generate([{"prompt_token_ids": p}],
                         params_for(rule, steps, max_tokens=20, logprobs=2))[0].outputs[0]
    check_against_generate(weights, p, out, rule, steps, 20)
    denoise, store, fused, committed = np.subtract(block_counts(runner_of(roomy)), before)
    assert (denoise, store, fused, committed) == (5 * steps, 0, 4, 20)
    # What benchmark/layer_metrics/diffusion_tokens_per_pass.py reads.
    assert committed / (denoise + store) == 4 / steps


@pytest.mark.parametrize("why,kw", [
    ("step", dict(max_num_batched_tokens=6, async_scheduling=False)),
    ("max_model_len", dict(max_model_len=24, async_scheduling=True))])
def test_the_lone_store_pass_runs_where_two_blocks_do_not_fit(tmp_path, weights, why, kw):
    """Six tokens a step hold a block and not two: every store runs alone,
    as it did. At the model's length the last block has none behind it:
    the lag pipeline schedules its store before the last token lands, and
    that store runs alone while the others ride."""
    engine = make_llm(tmp_path, **kw)
    try:
        p = prompt_of(9, 4)
        n = 15 if why == "max_model_len" else 11  # 9 + 15: the model's length
        rule, steps = "low_confidence_static", 2
        out = engine.generate([{"prompt_token_ids": p}], params_for(rule, steps, max_tokens=n))[0]
        check_against_generate(weights, p, out.outputs[0], rule, steps, n)
        assert out.outputs[0].finish_reason == "length"
        denoise, store, fused, committed = block_counts(runner_of(engine))
        assert committed == n
        if why == "step":
            assert (denoise, store, fused) == (6, 2, 0)
        else:
            assert (denoise, store, fused) == (8, 1, 3)
    finally:
        engine.shutdown()


@pytest.mark.parametrize("async_scheduling", [True, False])
def test_preemptions_among_rows_of_two_blocks_resume_with_the_same_tokens(
        tmp_path, weights, async_scheduling):
    """Ten pages for five requests under three rules that each grow to
    three: requests are preempted with rows of two blocks scheduled and in
    flight, and every answer is the reference's."""
    engine = make_llm(tmp_path, async_scheduling=async_scheduling, num_gpu_blocks_override=10,
                      enable_prefix_caching=False)
    try:
        prompts = [prompt_of(n, 6) for n in (13, 16, 14, 15, 12)]
        rules = [("low_confidence_static", 2), ("sequential", 2), ("low_confidence_dynamic", 4),
                 ("low_confidence_static", 1), ("low_confidence_static", 2)]
        outs = engine.generate([{"prompt_token_ids": p} for p in prompts],
                               [params_for(r, s, max_tokens=30) for r, s in rules])
        sched = engine.llm_engine.engine_core.engine_core.scheduler
        assert sched.make_stats().num_preempted_reqs > 0
        denoise, store, fused, committed = block_counts(runner_of(engine))
        assert fused > 20 and committed >= 150
    finally:
        engine.shutdown()
    for p, o, (rule, steps) in zip(prompts, outs, rules):
        want = REF.generate(TINY_SDAR, weights, p, 30, rule, steps, 0.02)
        assert list(o.outputs[0].token_ids) == want["tokens"]


@pytest.mark.parametrize("answer,cached", [(14, 16), (16, 16), (17, 32)])
def test_a_second_ask_hits_the_prefix_up_to_the_last_stored_block(roomy, weights, answer, cached):
    """A prompt of one page and an answer that ends in the second page's
    last block (14 tokens: the block is open; 16: it is clean, and the
    request ended before its store) or one token into the third page (17:
    the row that opened that block stored the one before). A row of two
    blocks puts only its first block in the cache, so the second page is
    hit only once its last block was stored."""
    p = prompt_of(16, 40 + answer)
    rule, steps = "low_confidence_static", 2
    first = roomy.generate([{"prompt_token_ids": p}],
                           params_for(rule, steps, max_tokens=answer))[0].outputs[0]
    check_against_generate(weights, p, first, rule, steps, answer)
    longer = p + list(first.token_ids) + prompt_of(9, 2)
    again = roomy.generate([{"prompt_token_ids": longer}], params_for(rule, steps))[0]
    assert again.num_cached_tokens == cached
    check_against_generate(weights, longer, again.outputs[0], rule, steps)


def test_a_dynamic_row_beside_static_ones_shares_steps_of_both_row_widths(roomy, weights):
    """Five requests under the three rules in one batch, 64 tokens a step:
    steps hold rows of one block and of two together (the scheduler's
    outputs say so), every store rides, and each answer, tokens and pass
    numbers, is the reference's."""
    sched = roomy.llm_engine.engine_core.engine_core.scheduler
    widths, schedule = [], sched.schedule

    def watched():
        so = schedule()
        if so.block_passes:
            widths.append({so.num_scheduled_tokens[rid] for rid in so.block_passes})
        return so

    sched.schedule = watched
    before = block_counts(runner_of(roomy))
    try:
        prompts = [prompt_of(n, 13) for n in (7, 12, 5, 10, 9)]
        outs = roomy.generate([{"prompt_token_ids": p} for p in prompts],
                              [params_for(r, s, max_tokens=17) for r, s in RULES])
    finally:
        del sched.schedule
    for p, o, (rule, steps) in zip(prompts, outs, RULES):
        check_against_generate(weights, p, o.outputs[0], rule, steps, 17)
    assert {B, 2 * B} in widths and {2 * B} in widths
    denoise, store, fused, committed = np.subtract(block_counts(runner_of(roomy)), before)
    # A pass may commit positions past an answer's last token.
    assert store == 0 and fused >= 5 * 4 and committed >= 5 * 17


@pytest.mark.parametrize("rule,steps", [("low_confidence_static", 2), ("low_confidence_dynamic", 4)])
def test_the_commit_order_the_engine_reports_replays_through_block_forward(llm, weights, rule, steps):
    """For a confidence rule the ORDER of commits is part of the answer:
    every pass the engine reports is rebuilt from the tokens alone (what
    was committed before it, clean; the rest, masks) and must commit just
    the positions the engine says, with the same tokens."""
    p = prompt_of(11, 9)  # 11 + 13: the answer ends with its block
    out = llm.generate([{"prompt_token_ids": p}], params_for(rule, steps, max_tokens=13))[0].outputs[0]
    seq = p + list(out.token_ids)
    pass_of = [-1] * len(p) + list(out.commit_passes)
    n = B // steps
    for k in sorted(set(out.commit_passes)):
        at = [i for i, q in enumerate(pass_of) if q == k]
        start = at[0] // B * B
        end = min(start + B, len(seq))
        known = [q < k for q in pass_of[:end]] + [False] * (start + B - end)
        logits = np.asarray(REF.block_forward(
            TINY_SDAR, weights, seq[:end] + [0] * (start + B - end), known))[start:]
        prob = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
        drawn = logits.argmax(-1)
        pick = REF.commit(rule, n, 0.02, known[start:], prob[np.arange(B), drawn])
        assert [start + int(j) for j in np.flatnonzero(pick)] == at
        assert [int(drawn[i - start]) for i in at] == [seq[i] for i in at]


def test_the_openai_server_takes_the_three_request_parameters(tmp_path, weights):
    from aiohttp.test_utils import TestClient, TestServer

    from vllm_tpu.engine.arg_utils import AsyncEngineArgs
    from vllm_tpu.engine.async_llm import AsyncLLM
    from vllm_tpu.entrypoints.openai.api_server import build_app

    engine = AsyncLLM.from_engine_args(AsyncEngineArgs(
        model=model_dir(tmp_path), dtype="float32", load_format="dummy", seed=SEED,
        max_model_len=128, block_size=16, num_gpu_blocks_override=64, max_num_seqs=8,
        max_num_batched_tokens=64))
    p = prompt_of(10, 1)

    async def go():
        async with TestClient(TestServer(build_app(engine, "tiny"))) as client:
            body = {"model": "tiny", "prompt": p, "max_tokens": 9, "temperature": 0.0,
                    "ignore_eos": True, "logprobs": 2, "remasking": "low_confidence_static",
                    "denoising_steps": 2}
            ok = await (await client.post("/v1/completions", json=body)).json()
            default = await (await client.post("/v1/completions", json={
                k: v for k, v in body.items() if k not in ("remasking", "denoising_steps")})).json()
            bad = await client.post("/v1/completions", json={**body, "remasking": "random"})
            return ok, default, bad.status

    try:
        ok, default, bad = asyncio.run(go())
    finally:
        engine.shutdown()
    want = REF.generate(TINY_SDAR, weights, p, 9, "low_confidence_static", 2)
    assert [int(t) for t in ok["choices"][0]["logprobs"]["tokens"]] == want["tokens"]
    # The model's own defaults (config.json): sequential, 4 steps.
    want = REF.generate(TINY_SDAR, weights, p, 9, "sequential", 4)
    assert [int(t) for t in default["choices"][0]["logprobs"]["tokens"]] == want["tokens"]
    assert bad == 400


def test_a_pinned_ladder_leaves_one_step_program_a_page_bucket(tmp_path, weights):
    """``token_buckets`` / ``request_buckets`` as the server's flags give
    them (``sdar30b.chat`` pins its step to one shape): a lone request, a
    chunked prompt and a batch of rows all run (64, 8, pages)."""
    engine = make_llm(tmp_path, token_buckets="64", request_buckets="8")
    try:
        runner = engine.llm_engine.engine_core.engine_core.executor.worker.runner
        assert runner.token_buckets == [64] and runner.request_buckets == [8]
        prompts = [prompt_of(n, 11) for n in (3, 9, 70, 14)]
        outs = engine.generate([{"prompt_token_ids": p} for p in prompts],
                               params_for("low_confidence_static", 2))
        for p, o in zip(prompts, outs):
            check_against_generate(weights, p, o.outputs[0], "low_confidence_static", 2)
        assert {key[:2] for key in runner._seen_buckets} == {(64, 8)}
    finally:
        engine.shutdown()


@pytest.mark.parametrize("kw", [dict(token_buckets="16,32"), dict(request_buckets="4"),
                                dict(token_buckets="64,16")])
def test_a_ladder_that_cannot_hold_a_full_step_is_refused(tmp_path, kw):
    with pytest.raises(ValueError, match="must be ascending and reach"):
        engine_config(tmp_path, **kw)


# ---------------------------------------------------------------------------
# (c) the ragged kernel's mask over blocks, interpret mode
# ---------------------------------------------------------------------------


BLOCK_CASES = [
    ([4, 4, 4], [20, 44, 8]),  # block rows against their stored prefix
    ([16, 24], [16, 24]),  # prefill under the mask
    ([4, 12, 4, 8], [36, 12, 12, 32]),  # block rows beside prefill chunks
]


@pytest.mark.parametrize("q_lens,kv_lens", BLOCK_CASES)
@pytest.mark.parametrize("d", [64, 128])
def test_ragged_kernel_with_block_size_4_matches_the_xla_path(q_lens, kv_lens, d):
    from dataclasses import replace

    from tests.models.test_ragged_paged_attention import _random_case, _run_kernel
    from vllm_tpu.ops.attention import ref_ragged_paged_attention

    q, kv, md = _random_case(np.random.default_rng(5), len(q_lens), q_lens, kv_lens,
                             2, 4, d, 8, num_blocks=64)
    scale = d ** -0.5
    got = _run_kernel(q, kv, 0, md, scale, block_len=B)
    want = ref_ragged_paged_attention(q, kv, jnp.int32(0), md, scale, block_len=B)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
    # And the mask is not the causal one: the first query of a block sees
    # the three positions to its right.
    causal = ref_ragged_paged_attention(q, kv, jnp.int32(0), replace(md, block_len=1), scale)
    assert float(jnp.max(jnp.abs(want[0] - causal[0]))) > 1e-3


def test_block_size_1_leaves_the_ragged_kernel_as_it_was():
    from tests.models.test_ragged_paged_attention import CASES, _random_case, _run_kernel
    from vllm_tpu.ops.rpa_kernel import ragged_paged_attention

    q_lens, kv_lens = CASES[2]
    q, kv, md = _random_case(np.random.default_rng(3), len(q_lens), q_lens, kv_lens,
                             2, 4, 128, 8, num_blocks=64)
    np.testing.assert_array_equal(np.asarray(_run_kernel(q, kv, 0, md, 0.1)),
                                  np.asarray(_run_kernel(q, kv, 0, md, 0.1, block_len=1)))
    args = (q, kv, jnp.zeros(1, jnp.int32), md.seq_lens, md.block_tables,
            md.query_start_loc, md.num_seqs)
    kw = dict(sm_scale=0.1, interpret=True, num_kv_pages_per_block=2, num_queries_per_block=8)
    text = [jax.jit(lambda *a, k=k: ragged_paged_attention(*a, **kw, **k)).lower(*args).as_text()
            for k in ({}, {"block_len": 1})]
    assert text[0] == text[1]


# ---------------------------------------------------------------------------
# (d) the sampler's probability of the token it drew
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("needs_penalties", [False, True])
@pytest.mark.parametrize("needs_top_k", [False, True])
@pytest.mark.parametrize("needs_top_p_min_p", [False, True])
def test_sampler_returns_the_probability_under_the_distribution_it_drew_from(
        needs_penalties, needs_top_k, needs_top_p_min_p):
    from tests.sample.test_sampler_kernel import V_ODD, _mixed_batch
    from vllm_tpu.sample.sampler import (_mask_top_k, _mask_top_p_min_p, apply_penalties,
                                         dispatch_sample, sample)

    rng = np.random.default_rng(23 + needs_penalties * 4 + needs_top_k * 2 + needs_top_p_min_p)
    logits, md = _mixed_batch(rng, V_ODD, needs_penalties)
    flags = dict(needs_penalties=needs_penalties, needs_top_k=needs_top_k,
                 needs_top_p_min_p=needs_top_p_min_p, needs_gumbel=True)
    plain, _ = sample(logits, md, **flags)
    drawn, _, prob = dispatch_sample(logits, md, **flags, return_prob=True)
    np.testing.assert_array_equal(np.asarray(drawn), np.asarray(plain))
    # The oracle: softmax over the sorted, truncated, scaled logits.
    x = apply_penalties(logits, md) if needs_penalties else logits
    t = np.asarray(md.temperature)
    scaled = x / jnp.where(md.temperature == 0.0, 1.0, md.temperature)[:, None]
    if needs_top_k:
        scaled = _mask_top_k(scaled, md.top_k)
    if needs_top_p_min_p:
        scaled = _mask_top_p_min_p(scaled, md.top_p, md.min_p)
    for r in range(logits.shape[0]):
        row = np.asarray(x[r] if t[r] == 0.0 else scaled[r], np.float64)
        order = np.sort(row)[::-1]
        z = np.exp(order - order[0])
        want = np.exp(row[int(drawn[r])] - order[0]) / z.sum()
        assert abs(float(prob[r]) - want) < 1e-5 * max(want, 1e-3), (r, float(prob[r]), want)
        assert 0.0 < float(prob[r]) <= 1.0


def test_an_all_greedy_batch_returns_the_softmax_of_its_argmax():
    from tests.sample.test_sampler_kernel import V_ODD, _make_md
    from vllm_tpu.sample.sampler import dispatch_sample

    logits = jnp.asarray(np.random.default_rng(2).standard_normal((4, V_ODD)) * 3, jnp.float32)
    md = _make_md(4, V_ODD, temperature=[0.0] * 4)
    drawn, _, prob = dispatch_sample(logits, md, needs_gumbel=False, return_prob=True)
    want = jnp.max(jax.nn.softmax(logits, axis=-1), axis=-1)
    np.testing.assert_allclose(np.asarray(prob), np.asarray(want), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(drawn), np.asarray(jnp.argmax(logits, -1)))


@pytest.mark.parametrize("enable_sampler_kernel", [True, False], ids=["kernel", "xla"])
def test_the_block_pass_commits_what_sampling_every_position_commits(enable_sampler_kernel):
    """The epilogue tells the sampler how many positions the live rows
    hold (the kernel, here in interpret mode, returns zeros past them, and
    with them the drawn tokens' probabilities). What it commits is what
    the XLA epilogue over every position and the rules, worked out here
    row by row, commit. (PR 30 also tried the usable positions FIRST, a
    permutation: 0.3 ms a step on the chip, left out.)"""
    import types

    from tests.sample.test_sampler_kernel import _make_md
    from vllm_tpu.models.generation import REMASKING_RULES
    from vllm_tpu.sample.sampler import sample
    from vllm_tpu.worker.model_runner import ModelRunner

    r, t, d, v = 8, 40, 16, 256
    rng = np.random.default_rng(11)
    head = jnp.asarray(rng.standard_normal((d, v)), jnp.float32)
    hidden = jnp.asarray(rng.standard_normal((t, d)) * 2, jnp.float32)
    runner = types.SimpleNamespace(
        model=types.SimpleNamespace(compute_logits=lambda params, h: h @ params),
        tp_size=1, _nan_check=False, _guard_numerics=True)
    md = _make_md(r, v, temperature=[0.7, 0.0, 1.0, 0.8, 0.9, 0.7, 1.1, 0.6],
                  top_p=[0.9, 1.0, 0.8, 0.9, 1.0, 0.9, 0.95, 0.9])
    # Rows: a prefill chunk, denoising rows at every stage of a block, store
    # passes, a padding row; two commit rules.
    kind = np.asarray([0, 1, 1, 2, 1, 1, 2, 0], np.int32)
    rule = np.asarray([REMASKING_RULES.index(n) for n in (
        ["low_confidence_static", "sequential"] * 4)], np.int32)
    n_pass = np.asarray([2, 1, 2, 2, 2, 1, 2, 2], np.int32)
    blk_com = np.asarray([[0, 0, 0, 0], [0, 0, 0, 0], [1, 0, 0, 1], [1, 1, 1, 1],
                          [0, 1, 0, 0], [1, 1, 0, 1], [1, 1, 1, 1], [0, 0, 0, 0]], bool)
    blk_tok = rng.integers(0, v, (r, B)).astype(np.int32)
    flat_idx = (np.arange(r)[:, None] * B + np.arange(B)[None, :]).astype(np.int32)
    blk = tuple(jnp.asarray(a) for a in (
        kind, rule, n_pass, blk_com.astype(np.int32), np.full(r, 0.9, np.float32)))
    out = ModelRunner._block_epilogue(
        runner, head, None, None, hidden, md, blk, jnp.asarray(flat_idx),
        jnp.asarray(blk_tok), jnp.asarray(blk_com), None, block_len=B, r_pad=r,
        needs_top_k=False, needs_top_p_min_p=True, needs_gumbel=True,
        num_logprobs=2, enable_sampler_kernel=enable_sampler_kernel,
        num_live=jnp.asarray(7, jnp.int32))
    (result, state), lp = out[2], out[3]
    result, state = np.asarray(result), np.asarray(state)

    # Every position of every row, padding included, on the XLA path.
    keys = np.repeat(np.asarray(md.prng_keys), B, axis=0)
    keys[:, 1] += np.tile(np.arange(B, dtype=keys.dtype), r)
    per_pos = _make_md(r * B, v, temperature=np.repeat(md.temperature, B),
                       top_p=np.repeat(md.top_p, B), seeds=keys)
    logits = hidden[flat_idx.reshape(-1)] @ head
    drawn, logprobs, prob = (np.asarray(a) for a in sample(
        logits, per_pos, needs_top_k=False, return_prob=True))
    drawn, prob = drawn.reshape(r, B), prob.reshape(r, B)
    usable = 0
    for i in range(r):
        want_tok, want_com = blk_tok[i].copy(), blk_com[i].copy()
        if kind[i] == 1:
            open_ = np.flatnonzero(~blk_com[i])
            usable += len(open_)
            if rule[i] == REMASKING_RULES.index("sequential"):
                picked = open_[:n_pass[i]]
            else:  # the surest first, the leftmost among equals
                picked = open_[np.argsort(-prob[i, open_], kind="stable")[:n_pass[i]]]
            want_tok[picked], want_com[picked] = drawn[i, picked], True
            assert result[i, 2 * B] == len(picked) > 0
            # The logprobs of a position this pass committed are its own.
            for j in picked:
                p = i * B + j
                assert float(lp[2][i, j]) == logprobs[p, drawn[i, j]]
                np.testing.assert_array_equal(
                    np.asarray(lp[1][i, j]), np.argsort(-logprobs[p], kind="stable")[:2])
        else:
            assert result[i, 2 * B] == 0
        np.testing.assert_array_equal(result[i, :B], want_tok)
        np.testing.assert_array_equal(result[i, B:2 * B] > 0, want_com)
        np.testing.assert_array_equal(state[i, :B], want_tok)
        np.testing.assert_array_equal(state[i, B:] > 0, want_com & (kind[i] != 2))
    assert usable == 4 + 2 + 3 + 1  # of the 28 positions of the 7 live rows
    assert not np.asarray(out[9]).any()  # no row flagged by the numeric guard


@pytest.mark.parametrize("num_logprobs", [0, 5])
@pytest.mark.parametrize("rule", ["sequential", "low_confidence_static", "low_confidence_dynamic"])
@pytest.mark.parametrize("live", [8, 9, 16, 17, 32])
def test_the_head_runs_over_the_least_rung_that_holds_the_live_rows(live, rule, num_logprobs):
    """A request bucket of 32 rows of a block of 4: the head, the sampler
    and the logprobs run over the first 8, 16 or 32 rows' positions,
    whichever holds the live rows (``head_rungs``), and what a live row
    gets is bit for bit what the head over all 128 positions gives it; a
    pad row commits nothing. That the head stopped at the rung: every
    position past it holds NaNs, and the program counts none."""
    import types

    from tests.sample.test_sampler_kernel import _make_md
    from vllm_tpu.models.generation import REMASKING_RULES
    from vllm_tpu.worker.model_runner import ModelRunner, head_rungs

    r, d, v = 32, 16, 256
    assert head_rungs(r) == [8, 16, 32]
    rows = next(n for n in head_rungs(r) if live <= n)
    rng = np.random.default_rng(100 * live + num_logprobs)
    head = jnp.asarray(rng.standard_normal((d, v)), jnp.float32)
    hidden = rng.standard_normal((r * B, d)).astype(np.float32) * 2
    runner = types.SimpleNamespace(
        model=types.SimpleNamespace(compute_logits=lambda params, h: h @ params),
        tp_size=1, _nan_check=True, _guard_numerics=True)
    md = _make_md(r, v, temperature=rng.choice([0.0, 0.7, 1.0], r), top_p=rng.choice([0.8, 1.0], r))
    # Live rows of every kind (a prefill chunk, a denoising pass, a lone
    # store, a row of two blocks) at every stage of a block; pad rows past.
    kind = np.where(np.arange(r) < live, rng.integers(0, 4, r), 0).astype(np.int32)
    blk_com = (rng.random((r, B)) < 0.4) & (kind != 3)[:, None]
    blk_tok = rng.integers(0, v, (r, B)).astype(np.int32)
    blk = tuple(jnp.asarray(a) for a in (
        kind, np.full(r, REMASKING_RULES.index(rule), np.int32), rng.integers(1, 3, r).astype(np.int32),
        blk_com.astype(np.int32), np.full(r, 0.5, np.float32)))
    flat_idx = jnp.arange(r * B, dtype=jnp.int32).reshape(r, B)

    def epilogue(hidden, num_live):
        out = ModelRunner._block_epilogue(
            runner, head, None, None, jnp.asarray(hidden), md, blk, flat_idx, jnp.asarray(blk_tok),
            jnp.asarray(blk_com), None, block_len=B, r_pad=r, needs_top_k=False,
            needs_top_p_min_p=True, needs_gumbel=True, num_logprobs=num_logprobs,
            enable_sampler_kernel=True, num_live=jnp.asarray(num_live, jnp.int32))
        return jax.tree.map(np.asarray, (out[2], out[3], out[6], out[9]))

    poisoned = hidden.copy()
    poisoned[rows * B:] = np.nan
    (got, state), lp, nans, bad = epilogue(poisoned, live)
    (want, want_state), want_lp, _, _ = epilogue(hidden, r)
    assert nans == 0 and not bad.any()
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(state, want_state)
    assert (lp is None) == (num_logprobs == 0)
    for a, w in zip(lp or (), want_lp or ()):
        np.testing.assert_array_equal(a[:live], w[:live])
    # The rows past the live ones keep what they came with.
    np.testing.assert_array_equal(got[live:, :B], blk_tok[live:])
    np.testing.assert_array_equal(got[live:, B:2 * B] > 0, blk_com[live:])
    assert not got[live:, 2 * B].any() and got[:live, 2 * B].sum() > 0


def test_past_the_kernels_bound_the_epilogue_pads_to_noise_tiles_and_still_cuts_the_nucleus():
    """151936 tokens would pad to 262144 lanes as a power of two, so past
    131072 lanes the epilogue computes at whole noise tiles, 155648, the
    fused kernel (since PR 30) and the XLA path alike; the XLA path sums in
    one pass XLA may fuse, and the nucleus is still the smallest set of
    largest weights that reaches top_p."""
    from vllm_tpu.ops import sampler_kernel as sk
    from vllm_tpu.sample.sampler import _mask_top_p_min_p, _sum_fn

    assert sk.compute_width(151936) == 155648 and sk.padded_vocab(151936) == 262144
    assert sk.compute_width(32768) == 32768 and sk.compute_width(1000) == 1024
    assert _sum_fn(32768) is None and _sum_fn(151936) is sk.plain_sum
    v = 140001
    logits = jnp.asarray(np.random.default_rng(3).standard_normal((2, v)) * 2, jnp.float32)
    got = np.asarray(_mask_top_p_min_p(logits, jnp.asarray([0.9, 0.5]), jnp.zeros(2)))
    for r, top_p in enumerate((0.9, 0.5)):
        x = np.asarray(logits[r], np.float64)
        p = np.exp(x - x.max())
        p /= p.sum()
        order = np.argsort(-p)
        kept = got[r] > -1e29
        n = int(kept.sum())
        assert kept[order[:n]].all()  # upward-closed
        assert p[order[:n]].sum() >= top_p - 1e-4 > p[order[: n - 1]].sum() - 2e-4


def test_the_grouped_matmul_fetches_an_experts_matrix_in_few_pieces():
    from vllm_tpu.layers.moe import _gmm_tiling

    assert _gmm_tiling(2048, 768) == (128, 2048, 768)  # gate, up: 3 MiB a visit
    assert _gmm_tiling(768, 2048) == (128, 768, 2048)  # down
    assert _gmm_tiling(14336, 4096) == (128, 2048, 1024)
    assert _gmm_tiling(64, 32) == (128, 128, 128)  # the tiny presets of the tests
    # Up to 4096 deep a block holds the depth whole and 8 MiB (the repo's
    # kernel, PR 54); the depth tile is then the order of the sums alone.
    assert _gmm_tiling(2048, 1792) == (128, 2048, 1792)  # lfm2: 7.3 MB
    assert _gmm_tiling(2304, 1024) == (128, 1152, 1024)  # kimi-linear: 4.7 MB
    assert _gmm_tiling(4096, 2048) == (128, 2048, 1024)  # mimo: 16 MiB in two
    assert _gmm_tiling(2048, 4096) == (128, 2048, 2048)


# ---------------------------------------------------------------------------
# (e) what the mechanism refuses, by configuration
# ---------------------------------------------------------------------------


def engine_config(tmp_path, **kw):
    from vllm_tpu.engine.arg_utils import EngineArgs

    return EngineArgs(**{**dict(model=model_dir(tmp_path), dtype="float32", load_format="dummy",
                                max_model_len=128), **kw}).create_engine_config().finalize()


@pytest.mark.parametrize("kw,names", [
    (dict(speculative_method="ngram", num_speculative_tokens=2), "speculative decoding"),
    (dict(max_model_len=126), "multiple of the block length"),
])
def test_a_configuration_that_does_not_compose_is_refused_by_mechanism(tmp_path, kw, names):
    with pytest.raises(ValueError, match="block_diffusion") as e:
        engine_config(tmp_path, **kw).resolve_generation()
    assert names in str(e.value) and "SDAR" not in str(e.value)


def test_the_mechanism_is_read_from_the_class_and_the_config(tmp_path):
    cfg = engine_config(tmp_path).resolve_generation()
    assert cfg.model_config.generation == dict(
        kind="block_diffusion", block_length=4, mask_token_id=250, remasking="sequential",
        denoising_steps=4, confidence_threshold=0.9)
    assert cfg.scheduler_config.block_length == 4
    assert not cfg.scheduler_config.enable_decode_attention


@pytest.mark.parametrize("kw,match", [
    (dict(structured_outputs="regex"), "structured output"),
    (dict(presence_penalty=0.5), "penalties"),
    (dict(denoising_steps=3), "must divide"),
])
def test_a_request_that_does_not_compose_is_refused_by_mechanism(tmp_path, kw, match):
    from vllm_tpu import SamplingParams
    from vllm_tpu.engine.input_processor import InputProcessor
    from vllm_tpu.sampling_params import StructuredOutputParams

    if "structured_outputs" in kw:
        kw = dict(structured_outputs=StructuredOutputParams(regex="a+"))
    proc = InputProcessor(engine_config(tmp_path))
    with pytest.raises(ValueError, match=match):
        proc.process("r", {"prompt_token_ids": [1, 2, 3]}, SamplingParams(**kw))
    filled = proc.process("r", {"prompt_token_ids": [1, 2, 3]}, SamplingParams()).sampling_params
    assert (filled.remasking, filled.denoising_steps, filled.confidence_threshold) == (
        "sequential", 4, 0.9)


@pytest.mark.parametrize("kw", [dict(remasking="random"), dict(denoising_steps=0),
                                dict(confidence_threshold=1.5)])
def test_the_request_parameters_are_validated_like_the_others(kw):
    from vllm_tpu import SamplingParams

    with pytest.raises(ValueError):
        SamplingParams(**kw)


def test_an_autoregressive_model_refuses_the_diffusion_parameters(tmp_path):
    from tests.models.utils import tiny_llama_dir
    from vllm_tpu import SamplingParams
    from vllm_tpu.engine.arg_utils import EngineArgs
    from vllm_tpu.engine.input_processor import InputProcessor

    cfg = EngineArgs(model=tiny_llama_dir(tmp_path), dtype="float32",
                     max_model_len=128).create_engine_config().finalize().resolve_generation()
    assert cfg.model_config.generation == {} and cfg.scheduler_config.block_length == 0
    with pytest.raises(ValueError, match="block_diffusion"):
        InputProcessor(cfg).process("r", {"prompt_token_ids": [1, 2]},
                                    SamplingParams(remasking="sequential"))
