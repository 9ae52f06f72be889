"""Strict Prometheus text-exposition grammar check of the full /metrics
render, plus the engine-step phase metric family and the monotonic
resilience-counter refresh. Stub-only, tier-1 fast."""

from __future__ import annotations

import re

import pytest

from vllm_tpu.core.sched_output import SchedulerStats
from vllm_tpu.metrics.prometheus import PrometheusRegistry
from vllm_tpu.metrics.stats import IterationStats

HELP_RE = re.compile(r"^# HELP (vllm:[a-z0-9_]+) (\S.*)$")
TYPE_RE = re.compile(r"^# TYPE (vllm:[a-z0-9_]+) (counter|gauge|histogram)$")
VALUE = r"-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?"
SAMPLE_RE = re.compile(
    r"^(vllm:[a-z0-9_]+)"
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"\\\n]*"'
    r'(?:,[a-zA-Z_][a-zA-Z0-9_]*="[^"\\\n]*")*\})?'
    rf" ({VALUE})$"
)


class _StubEngine:
    def __init__(self):
        self.restarts = {"0": 1.0, "1": 3.0}

    def resilience_status(self):
        return {
            "engine_dead": False,
            "engines": {
                eid: {"up": True, "restarts": n}
                for eid, n in self.restarts.items()
            },
            "requests_replayed_total": 5,
            "requests_failed_on_crash_total": 2,
        }


def _populated_registry() -> PrometheusRegistry:
    reg = PrometheusRegistry(_StubEngine())
    stats = SchedulerStats(
        num_running_reqs=2, num_waiting_reqs=1, kv_cache_usage=0.25,
        queue_times=[0.01, 0.3], spec_accept_lengths=[2],
        bucket_compiles=1, bucket_hits=9, pipeline_stall_s=0.1,
        step_schedule_times=[0.0002, 0.0009],
        step_dispatch_times=[0.004],
        step_prepare_times=[0.001, 0.002, 0.003],
        step_finalize_times=[0.0001],
        step_fetch_times=[0.012, 0.013],
        step_update_times=[0.0003],
        steps_by_kind={"decode": 9, "mixed": 1},
        step_tokens_by_kind={"decode": 18, "mixed": 10},
        attn_context_tokens_by_kind={"decode": 180, "mixed": 10},
        attn_ragged_pairs={"live": 26, "computed": 100},
        attn_decode_tiles={"run": 40, "least": 29.25},
        step_first_use_s=7.5,
        batch_num_tokens=96, batch_num_reqs=3, batch_occupancy=0.75,
        step_interval_s=0.006,
    )
    it = IterationStats(
        num_generation_tokens=12, num_prompt_tokens=7,
        ttfts=[0.05], inter_token_latencies=[0.01, 0.02],
        e2e_latencies=[0.4], finished_reasons=["stop", "length"],
        output_s=0.0005,
    )
    reg.record(stats, it)
    return reg


def _labels_without_le(labels: str | None) -> str:
    if not labels:
        return ""
    parts = [p for p in labels[1:-1].split(",") if not p.startswith("le=")]
    return ",".join(parts)


def test_full_render_line_grammar():
    """Every line of the full /metrics render is either a HELP, the TYPE
    paired right after it, or a well-formed sample of the current family;
    histogram families satisfy the +Inf/_sum/_count invariants per label
    set with cumulative bucket counts."""
    text = _populated_registry().render()
    assert text.endswith("\n")

    current: str | None = None  # family name from the last HELP
    typed: dict[str, str] = {}
    samples: dict[str, list] = {}
    prev_line_was_help = False
    for line in text.splitlines():
        m = HELP_RE.match(line)
        if m:
            name = m.group(1)
            assert name not in typed, f"duplicate family {name}"
            current = name
            prev_line_was_help = True
            continue
        m = TYPE_RE.match(line)
        if m:
            assert prev_line_was_help, f"TYPE without HELP: {line}"
            assert m.group(1) == current, f"TYPE name mismatch: {line}"
            typed[current] = m.group(2)
            prev_line_was_help = False
            continue
        prev_line_was_help = False
        assert not line.startswith("#"), f"unknown comment line: {line!r}"
        m = SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        name, labels, value = m.group(1), m.group(2), m.group(3)
        assert current in typed, f"sample before TYPE: {line!r}"
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if typed[current] == "histogram" and name.endswith(suffix):
                base = name[: -len(suffix)]
                break
        assert base == current, (
            f"sample {name} outside its family block ({current})")
        if typed[current] == "histogram":
            assert name != current, (
                f"bare histogram sample {line!r}: histograms expose only "
                f"_bucket/_sum/_count series")
        samples.setdefault(current, []).append((name, labels, float(value)))

    assert typed, "no metric families rendered"
    # Every family carries its declared TYPE; histogram invariants hold
    # per label set.
    for family, typ in typed.items():
        if typ != "histogram":
            continue
        by_labelset: dict[str, dict] = {}
        for name, labels, value in samples.get(family, []):
            key = _labels_without_le(labels)
            d = by_labelset.setdefault(
                key, {"buckets": [], "sum": None, "count": None})
            if name.endswith("_bucket"):
                le = re.search(r'le="([^"]+)"', labels or "")
                assert le, f"bucket without le: {name}{labels}"
                d["buckets"].append((le.group(1), value))
            elif name.endswith("_sum"):
                assert d["sum"] is None, f"duplicate _sum for {family}"
                d["sum"] = value
            elif name.endswith("_count"):
                assert d["count"] is None, f"duplicate _count for {family}"
                d["count"] = value
        assert by_labelset, f"histogram {family} rendered no samples"
        for key, d in by_labelset.items():
            les = [b[0] for b in d["buckets"]]
            assert les[-1] == "+Inf", f"{family}{{{key}}}: no +Inf bucket"
            counts = [b[1] for b in d["buckets"]]
            assert counts == sorted(counts), (
                f"{family}{{{key}}}: bucket counts not cumulative")
            assert d["sum"] is not None, f"{family}{{{key}}}: missing _sum"
            assert d["count"] is not None, (
                f"{family}{{{key}}}: missing _count")
            assert d["count"] == counts[-1], (
                f"{family}{{{key}}}: +Inf bucket != _count")


def test_step_phase_family_renders_per_phase():
    text = _populated_registry().render()
    assert (
        'vllm:engine_step_duration_seconds_count{phase="schedule"} 2'
        in text
    )
    assert (
        'vllm:engine_step_duration_seconds_count{phase="dispatch"} 1'
        in text
    )
    assert (
        'vllm:engine_step_duration_seconds_count{phase="finalize"} 1'
        in text
    )
    assert "vllm:engine_batch_tokens 96" in text
    assert "vllm:engine_batch_requests 3" in text
    assert "vllm:engine_batch_occupancy 0.75" in text
    assert "vllm:engine_step_interval_seconds 0.006" in text


@pytest.mark.parametrize("phase,count,total", [
    ("prepare", 3, 0.006), ("fetch", 2, 0.025), ("update", 1, 0.0003),
    ("output", 1, 0.0005),
])
def test_step_phase_family_has_the_host_and_device_wait_phases(
        phase, count, total):
    """prepare nests inside dispatch, fetch inside finalize; update and
    output (the frontend's, from IterationStats) follow them. A reader
    divides a phase's _sum by a step count, so both must be there."""
    reg = _populated_registry()
    text = reg.render()
    name = "vllm:engine_step_duration_seconds"
    assert f'{name}_count{{phase="{phase}"}} {count}' in text
    [line] = [l for l in text.splitlines()
              if l.startswith(f'{name}_sum{{phase="{phase}"}}')]
    assert float(line.rsplit(" ", 1)[1]) == pytest.approx(total)
    help_line = next(l for l in text.splitlines()
                     if l.startswith(f"# HELP {name} "))
    assert phase in help_line and "inside" in help_line
    # A record without iteration stats (or an older one without the
    # field's value) adds no output sample.
    reg.record(SchedulerStats(), IterationStats())
    assert f'{name}_count{{phase="output"}} 1' in reg.render()


@pytest.mark.parametrize("family,decode,mixed", [
    ("vllm:engine_steps_total", 9, 1),
    ("vllm:engine_step_tokens_total", 18, 10),
    ("vllm:attn_context_tokens_total", 180, 10),
])
def test_step_kind_counters_are_cumulative_and_never_decrease(
        family, decode, mixed):
    reg = _populated_registry()
    text = reg.render()
    assert f'{family}{{kind="decode"}} {float(decode)}' in text
    assert f'{family}{{kind="mixed"}} {float(mixed)}' in text
    assert f"# TYPE {family} counter" in text
    # The runner's counters are cumulative: the registry adopts the larger
    # value, and a snapshot from a respawned engine (zeros) lowers nothing.
    field = {"vllm:engine_steps_total": "steps_by_kind",
             "vllm:engine_step_tokens_total": "step_tokens_by_kind",
             "vllm:attn_context_tokens_total":
                 "attn_context_tokens_by_kind"}[family]
    reg.record(SchedulerStats(**{field: {"decode": decode + 5, "mixed": 0}}))
    reg.record(SchedulerStats())
    text = reg.render()
    assert f'{family}{{kind="decode"}} {float(decode + 5)}' in text
    assert f'{family}{{kind="mixed"}} {float(mixed)}' in text


def test_ragged_pairs_are_cumulative_by_kind():
    reg = _populated_registry()
    text = reg.render()
    assert "# TYPE vllm:attn_ragged_pairs_total counter" in text
    assert 'vllm:attn_ragged_pairs_total{kind="live"} 26.0' in text
    assert 'vllm:attn_ragged_pairs_total{kind="computed"} 100.0' in text
    reg.record(SchedulerStats(attn_ragged_pairs={"live": 30, "computed": 90}))
    text = reg.render()  # a respawned engine's lower count lowers nothing
    assert 'vllm:attn_ragged_pairs_total{kind="live"} 30.0' in text
    assert 'vllm:attn_ragged_pairs_total{kind="computed"} 100.0' in text


def test_decode_tiles_are_cumulative_by_kind():
    reg = _populated_registry()
    text = reg.render()
    assert "# TYPE vllm:attn_decode_tiles_total counter" in text
    assert 'vllm:attn_decode_tiles_total{kind="run"} 40.0' in text
    assert 'vllm:attn_decode_tiles_total{kind="least"} 29.25' in text
    reg.record(SchedulerStats(attn_decode_tiles={"run": 52, "least": 20.0}))
    text = reg.render()  # a respawned engine's lower count lowers nothing
    assert 'vllm:attn_decode_tiles_total{kind="run"} 52.0' in text
    assert 'vllm:attn_decode_tiles_total{kind="least"} 29.25' in text


def test_first_use_seconds_ratchet_beside_their_count():
    reg = _populated_registry()
    assert "vllm:step_first_use_seconds_total 7.5" in reg.render()
    reg.record(SchedulerStats(step_first_use_s=3.0))  # a respawned engine
    assert "vllm:step_first_use_seconds_total 7.5" in reg.render()
    reg.record(SchedulerStats(step_first_use_s=9.25))
    assert "vllm:step_first_use_seconds_total 9.25" in reg.render()


def test_runner_counts_decode_only_against_mixed_on_a_hand_made_batch(
        tmp_path):
    """Two prompts of 3 and 7 tokens, 8 tokens each, greedy: ONE mixed step
    (the prefill of both, 10 tokens, each row attending to its prompt) and
    then decode-only steps of 2 rows whose contexts grow by one a step.
    The counters ride SchedulerStats to /metrics, cumulative."""
    from tests.models.utils import tiny_llama_dir
    from vllm_tpu import LLM, SamplingParams

    llm = LLM(model=tiny_llama_dir(tmp_path), dtype="float32",
              max_model_len=128, block_size=16, max_num_batched_tokens=128,
              max_num_seqs=4, num_gpu_blocks_override=64,
              # One step at a time: the lag pipeline would dispatch two
              # steps past the last token, real work that is counted too.
              async_scheduling=False)
    engine = llm.llm_engine
    reg = PrometheusRegistry()
    params = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)

    def run_batch(tag):
        engine.add_request(f"{tag}-a", {"prompt_token_ids": [11, 24, 9]},
                           params)
        engine.add_request(
            f"{tag}-b", {"prompt_token_ids": [1, 2, 3, 4, 5, 6, 7]}, params)
        while engine.has_unfinished_requests():
            outputs = engine.engine_core.get_output()
            processed = engine.output_processor.process_outputs(
                outputs.outputs)
            reg.record(outputs.scheduler_stats, processed.iteration_stats)

    run_batch("one")
    runner = engine.engine_core.engine_core.executor.worker.runner
    # The first tokens come from the prefill; 7 more decode steps each.
    decode_ctx = sum(3 + j for j in range(1, 8)) + sum(
        7 + j for j in range(1, 8))
    assert runner.steps_by_kind == {"decode": 7, "mixed": 1}
    assert runner.step_tokens_by_kind == {"decode": 14, "mixed": 10}
    assert runner.attn_context_tokens_by_kind == {
        "decode": decode_ctx, "mixed": 10}
    assert runner.decode_only_launches == 7
    assert runner.timing["steps"] == 8 and runner.timing["prep_s"] > 0
    assert runner.first_use_s > 0 and runner.bucket_compiles == 2
    text = reg.render()
    assert (f'vllm:attn_context_tokens_total{{kind="decode"}} '
            f'{float(decode_ctx)}') in text
    assert 'vllm:engine_steps_total{kind="mixed"} 1.0' in text
    assert f"vllm:step_first_use_seconds_total {runner.first_use_s}" in text
    for phase in ("prepare", "fetch", "update", "output"):
        assert ('vllm:engine_step_duration_seconds_count'
                f'{{phase="{phase}"}} 8') in text, phase
    # A model that does not generate by blocks has no head over a rung of
    # the request bucket: the families are there, without a sample.
    assert runner.diffusion_head_positions == {"live": 0, "computed": 0}
    assert "# TYPE vllm:diffusion_head_positions_total counter" in text
    assert "vllm:diffusion_head_positions_total{" not in text
    assert "vllm:diffusion_head_steps_total{" not in text
    # Cumulative: a second batch adds to every counter, and uses no new
    # step program.
    run_batch("two")
    assert runner.steps_by_kind == {"decode": 14, "mixed": 2}
    assert runner.attn_context_tokens_by_kind["decode"] == 2 * decode_ctx
    assert runner.bucket_compiles == 2
    assert f'kind="decode"}} {float(2 * decode_ctx)}' in reg.render()


def test_resilience_counters_never_decrease():
    """A render racing an engine respawn (snapshot counters briefly reset
    to zero) must not show a counter decrease — scrapers read that as a
    process restart and corrupt rate() windows."""
    engine = _StubEngine()
    reg = PrometheusRegistry(engine)
    text = reg.render()
    assert 'vllm:engine_restarts_total{engine_id="1"} 3.0' in text
    assert "vllm:requests_replayed_total 5.0" in text

    # Snapshot resets (fresh supervisor state after a respawn).
    engine.restarts = {"0": 0.0, "1": 0.0}
    text = reg.render()
    assert 'vllm:engine_restarts_total{engine_id="0"} 1.0' in text
    assert 'vllm:engine_restarts_total{engine_id="1"} 3.0' in text

    # And the ratchet still follows genuine increases.
    engine.restarts = {"0": 2.0, "1": 4.0}
    text = reg.render()
    assert 'vllm:engine_restarts_total{engine_id="0"} 2.0' in text
    assert 'vllm:engine_restarts_total{engine_id="1"} 4.0' in text


def test_diffusion_and_moe_counters_are_cumulative_and_never_decrease():
    """The five counters generation by diffusion over blocks and the
    mixture of experts add (the fifth is a kind of an existing family):
    counted by the runner, ratcheted by the registry."""
    reg = PrometheusRegistry()
    reg.record(SchedulerStats(
        diffusion_passes_by_kind={"denoise": 20, "store": 10},
        diffusion_committed_tokens=40, moe_assignments=4480,
        moe_experts_hit=896, moe_pad_pairs_skipped=20832,
        attn_context_tokens_by_kind={"block": 16000}))
    reg.record(SchedulerStats())  # a respawned engine lowers nothing
    reg.record(SchedulerStats(diffusion_passes_by_kind={"denoise": 22},
                              moe_experts_hit=900))
    text = reg.render()
    for sample in ('vllm:diffusion_passes_total{kind="denoise"} 22.0',
                   'vllm:diffusion_passes_total{kind="store"} 10.0',
                   "vllm:diffusion_committed_tokens_total 40.0",
                   "vllm:moe_assignments_total 4480.0",
                   "vllm:moe_experts_hit_total 900.0",
                   "vllm:moe_pad_pairs_skipped_total 20832.0",
                   'vllm:attn_context_tokens_total{kind="block"} 16000.0'):
        assert sample in text, sample
    for family in ("vllm:diffusion_passes_total",
                   "vllm:diffusion_committed_tokens_total",
                   "vllm:moe_assignments_total", "vllm:moe_experts_hit_total",
                   "vllm:moe_pad_pairs_skipped_total"):
        assert f"# TYPE {family} counter" in text


def test_a_store_that_rode_with_a_denoising_pass_is_counted_apart():
    """``vllm:diffusion_stores_fused_total``: a pass that stores a clean
    block AND denoises the next is one pass of the model, counted once
    under ``kind="denoise"``; ``kind="store"`` is the lone stores that are
    left, so fused / (fused + store) is how often the two rode together,
    and committed / (denoise + store) is tokens a pass of the model."""
    reg = PrometheusRegistry()
    reg.record(SchedulerStats(
        diffusion_passes_by_kind={"denoise": 40, "store": 1},
        diffusion_stores_fused=19, diffusion_committed_tokens=80))
    reg.record(SchedulerStats())  # a respawned engine lowers nothing
    text = reg.render()
    for sample in ("vllm:diffusion_stores_fused_total 19.0",
                   'vllm:diffusion_passes_total{kind="denoise"} 40.0',
                   'vllm:diffusion_passes_total{kind="store"} 1.0',
                   "# TYPE vllm:diffusion_stores_fused_total counter"):
        assert sample in text, sample
    help_of = {line.split()[2]: line for line in text.splitlines()
               if line.startswith("# HELP vllm:diffusion_")}
    assert "counts here, once" in help_of["vllm:diffusion_passes_total"]
    assert "rode with" in help_of["vllm:diffusion_stores_fused_total"]


@pytest.mark.parametrize("lone_stores", [0, 3])
def test_the_accepted_reader_of_tokens_a_pass_reads_a_run_whose_stores_all_rode(lone_stores):
    """``benchmark/layer_metrics/diffusion_tokens_per_pass.py`` (accepted,
    not this repository's to edit here) divides by denoise + store and
    returns None where either sample is missing: ``kind="store"`` has a
    sample at 0 once any pass is counted, and none before (a model that
    does not generate by diffusion exposes the family without samples)."""
    from benchmark.harness.manifest import BENCH_DIR, load_module

    def scrape(reg):
        out = {}
        for line in reg.render().splitlines():
            if line and not line.startswith("#"):
                key, _, value = line.rpartition(" ")
                out[key] = float(value)
        return out

    reg = PrometheusRegistry()
    reg.record(SchedulerStats(diffusion_passes_by_kind={"denoise": 0, "store": 0}))
    assert not [k for k in scrape(reg) if k.startswith("vllm:diffusion_passes_total")]
    reg.record(SchedulerStats(diffusion_passes_by_kind={"denoise": 10, "store": 0},
                              diffusion_committed_tokens=20))
    m0 = scrape(reg)
    assert m0['vllm:diffusion_passes_total{kind="store"}'] == 0.0
    reg.record(SchedulerStats(
        diffusion_passes_by_kind={"denoise": 50, "store": lone_stores},
        diffusion_stores_fused=20 - lone_stores, diffusion_committed_tokens=100))
    m1 = scrape(reg)
    reader = load_module(BENCH_DIR / "layer_metrics" / "diffusion_tokens_per_pass.py")
    got = reader.read({"delta": lambda n: m1[n] - m0[n] if n in m0 and n in m1 else None})
    assert got == 80 / (40 + lone_stores)


@pytest.mark.parametrize("step_tokens", [64, 7], ids=["room_for_two_blocks", "room_for_one"])
def test_runner_counts_passes_commits_and_experts_on_a_hand_made_batch(
        tmp_path, step_tokens):
    """One prompt of 9 tokens, 7 more under the static rule at two steps,
    one step at a time: the prefill of 8, then the first block (1 prompt
    token, 3 masks: passes of 2 and 1, then its store), then a block of
    four masks (2 + 2), after whose last token the request ends. Where a
    step holds two blocks the store rides with the second block's first
    pass, one row of 8 that counts once, as ``denoise``; where it holds 7
    tokens the prefill is two chunks of 4 and the store runs alone."""
    from tests.benchmark.tiny_sdar_moe import TINY_SDAR, model_dir
    from vllm_tpu import LLM, SamplingParams

    llm = LLM(model=model_dir(tmp_path), dtype="float32", load_format="dummy",
              max_model_len=128, block_size=16,
              max_num_batched_tokens=step_tokens,
              max_num_seqs=4, num_gpu_blocks_override=64,
              async_scheduling=False)
    out = llm.generate(
        [{"prompt_token_ids": list(range(1, 10))}],
        SamplingParams(temperature=0.0, max_tokens=7, ignore_eos=True,
                       remasking="low_confidence_static",
                       denoising_steps=2))[0].outputs[0]
    assert out.commit_passes[-1] == 4 and len(out.token_ids) == 7
    runner = llm.llm_engine.engine_core.engine_core.executor.worker.runner
    room = step_tokens >= 8
    assert runner.diffusion_passes_by_kind == {
        "denoise": 4, "store": 0 if room else 1}
    assert runner.diffusion_stores_fused == (1 if room else 0)
    assert runner.diffusion_committed_tokens == 7
    # The context a block row reads, once a row: 12 for each pass of the
    # first block, 16 for each of the second, and 16 for the row of both.
    assert runner.attn_context_tokens_by_kind["block"] == (
        2 * 12 + 16 + 16 if room else 3 * 12 + 2 * 16)
    layers, top = TINY_SDAR["num_hidden_layers"], TINY_SDAR["num_experts_per_tok"]
    tokens = 8 + 5 * 4
    assert runner.moe_assignments == tokens * top * layers
    # Each step hits at least its 2 experts a layer and at most all 8.
    steps = 5 if room else 7
    assert runner.step_launches == steps
    assert steps * 2 * layers <= runner.moe_experts_hit <= steps * 8 * layers
    llm.shutdown()


def test_runner_counts_the_heads_positions_by_the_rung_a_step_took(tmp_path):
    """A request bucket of 8 rows of a block of 4, one step at a time: the
    head of a step runs over 2, 4 or 8 rows' positions, the least that
    holds the live rows (``head_rungs``, the rule of the step program's
    switch). One request in the first step, three in the second, six in
    the third; the counters ride SchedulerStats to /metrics."""
    from tests.benchmark.tiny_sdar_moe import model_dir
    from vllm_tpu import LLM, SamplingParams
    from vllm_tpu.worker.model_runner import head_rungs

    assert head_rungs(8) == [2, 4, 8] and head_rungs(128) == [32, 64, 128]
    assert head_rungs(2) == [1, 2] and head_rungs(1) == [1]
    llm = LLM(model=model_dir(tmp_path), dtype="float32", load_format="dummy",
              max_model_len=128, block_size=16, max_num_batched_tokens=64,
              token_buckets="64", request_buckets="8", max_num_seqs=8,
              num_gpu_blocks_override=64, async_scheduling=False)
    engine = llm.llm_engine
    runner = engine.engine_core.engine_core.executor.worker.runner
    reg = PrometheusRegistry()
    params = SamplingParams(temperature=0.0, max_tokens=16, ignore_eos=True)
    want, live = {"live": 0, "computed": 0}, 0
    for step, (new, rows, rung) in enumerate(
            [(1, 2, "quarter"), (2, 4, "half"), (3, 8, "whole")]):
        for i in range(new):
            engine.add_request(f"{step}-{i}", {"prompt_token_ids": list(range(1, 8))}, params)
        outputs = engine.engine_core.get_output()
        reg.record(outputs.scheduler_stats,
                   engine.output_processor.process_outputs(outputs.outputs).iteration_stats)
        live += new
        want = {"live": want["live"] + live * 4, "computed": want["computed"] + rows * 4}
        assert runner.diffusion_head_positions == want, (step, rung)
        assert runner.diffusion_head_steps_by_rung[rung] == 1
    assert want == {"live": (1 + 3 + 6) * 4, "computed": (2 + 4 + 8) * 4}
    text = reg.render()
    for sample in ('vllm:diffusion_head_positions_total{kind="live"} 40.0',
                   'vllm:diffusion_head_positions_total{kind="computed"} 56.0',
                   'vllm:diffusion_head_steps_total{rung="quarter"} 1.0',
                   'vllm:diffusion_head_steps_total{rung="half"} 1.0',
                   'vllm:diffusion_head_steps_total{rung="whole"} 1.0'):
        assert sample in text, sample
    reg.record(SchedulerStats())  # a respawned engine lowers nothing
    assert 'vllm:diffusion_head_positions_total{kind="computed"} 56.0' in reg.render()
    while engine.has_unfinished_requests():
        engine.step()
    # To the end: every step on one rung, the whole bucket while six rows
    # run, less again once the first requests have ended.
    by_rung = runner.diffusion_head_steps_by_rung
    assert sum(by_rung.values()) == runner.step_launches and by_rung["whole"] > 1
    assert runner.diffusion_head_positions["computed"] == 4 * (
        2 * by_rung["quarter"] + 4 * by_rung["half"] + 8 * by_rung["whole"])
    assert runner.diffusion_head_positions["live"] < runner.diffusion_head_positions["computed"]
    llm.shutdown()


def test_runner_counts_the_pad_tokens_pairs_apart_from_the_live_ones(tmp_path):
    """A step of 140 live tokens in the one token bucket of 512, as
    ``sdar30b.chat`` pins it: the 372 pad tokens' pairs are counted as
    skipped, a layer with experts each, and the live tokens' pairs are
    what they were."""
    from tests.benchmark.tiny_sdar_moe import TINY_SDAR, model_dir
    from vllm_tpu import LLM, SamplingParams

    llm = LLM(model=model_dir(tmp_path), dtype="float32", load_format="dummy",
              max_model_len=256, block_size=16, max_num_batched_tokens=512,
              token_buckets="512", max_num_seqs=4, num_gpu_blocks_override=64,
              async_scheduling=False)
    runner = llm.llm_engine.engine_core.engine_core.executor.worker.runner
    layers, top = TINY_SDAR["num_hidden_layers"], TINY_SDAR["num_experts_per_tok"]
    llm.llm_engine.add_request(
        "a", {"prompt_token_ids": list(range(1, 141))},
        SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True))
    llm.llm_engine.step()
    assert runner.step_launches == 1
    assert runner.step_tokens_by_kind == {"decode": 0, "mixed": 140}
    assert runner.moe_assignments == 140 * top * layers
    assert runner.moe_pad_pairs_skipped == 372 * top * layers
    while llm.llm_engine.has_unfinished_requests():
        llm.llm_engine.step()
    # Every step of the request ran the one shape.
    tokens = sum(runner.step_tokens_by_kind.values())
    assert tokens > 140 and runner.moe_assignments == tokens * top * layers
    assert runner.moe_pad_pairs_skipped == (
        (runner.step_launches * 512 - tokens) * top * layers)
    llm.shutdown()
