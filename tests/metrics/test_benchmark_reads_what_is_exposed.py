"""Every ``vllm:`` series a reader of the benchmark names is on ``/metrics``.

The per-layer readers (``benchmark/layer_metrics/*.py``, and the kernel
counts they cite) find their series by name in two scrapes; a series that
was renamed or removed makes the reader return ``None`` and the ledger
print ``null``, on the chip, after the PR. This reads the readers' sources
(it never edits them) and holds the registry to them on the CPU: each name,
and each ``phase`` / ``kind`` label value beside it, must come out of
``PrometheusRegistry`` as a SAMPLE (a labelled family with no sample is a
key the scrape does not have).
"""

from __future__ import annotations

import re
from pathlib import Path

import pytest

from vllm_tpu.core.sched_output import STEP_PHASES, SchedulerStats
from vllm_tpu.metrics.prometheus import PrometheusRegistry
from vllm_tpu.metrics.stats import IterationStats

BENCHMARK = Path(__file__).resolve().parents[2] / "benchmark"
NAME_RE = re.compile(r"vllm:[a-z0-9_]+")
# ``name{kind="block"}`` as written, in code or in a docstring.
LABELLED_RE = re.compile(r'(vllm:[a-z0-9_]+)\{(\w+)="(\w+)"\}')
# ``f'...{{phase="{p}"}}'`` with ``for p in ("update", "output")`` beside it.
FSTRING_LABEL_RE = re.compile(r'\{\{(\w+)="\{(\w+)\}"\}\}')


def _readers() -> list[Path]:
    files = sorted((BENCHMARK / "layer_metrics").glob("*.py"))
    files.append(BENCHMARK / "kernels" / "block_attn.py")
    return [f for f in files if NAME_RE.search(f.read_text())]


def _family(name: str) -> str:
    """``x_sum`` / ``x_count`` / ``x_bucket`` are samples of ``x``, and a
    reader asks for a counter as ``x_total`` or ``x``: one family."""
    name = re.sub(r"_(sum|count|bucket)$", "", name)
    return re.sub(r"_total$", "", name)


@pytest.fixture(scope="module")
def exposed() -> dict[str, set[tuple[str, str]]]:
    """family -> the (label, value) pairs its samples carry, out of a
    registry that has seen one step of every kind."""
    phases = {f"step_{p}_times": [0.001] for p in STEP_PHASES}
    reg = PrometheusRegistry()
    reg.record(
        SchedulerStats(
            queue_times=[0.01], bucket_compiles=1, num_preempted_reqs=1,
            prefix_cache_queries=8, prefix_cache_hits=4,
            step_first_use_s=7.5, sampler_fallback_rows=3,
            steps_by_kind={"decode": 9, "mixed": 1},
            step_tokens_by_kind={"decode": 9, "mixed": 40},
            attn_context_tokens_by_kind={"decode": 180, "mixed": 10,
                                         "block": 11, "latent": 12,
                                         "window": 13},
            # KV groups over one pool, window layers beside full ones.
            kv_group_pages_in_use=[3, 1], kv_pages_allocated=[5, 5],
            kv_window_pages_freed=4, kv_page_steps={"held": 4, "pool": 10},
            window_attn_rows=6, window_attn_pairs=9,
            # Groups of unlike page shape, a sink inside the kernels (PR 45).
            kv_group_page_tokens=[32, 16],
            kv_token_bytes=[{"published": 7680.0, "held": 9216.0},
                            {"published": 15360.0, "held": 18432.0}],
            attn_sink_rows=2,
            linear_attn_state_rows=4,
            linear_attn_tokens_by_kind={"chunk": 3, "decode": 2},
            attn_ragged_pairs={"live": 26, "computed": 100},
            diffusion_passes_by_kind={"denoise": 2, "store": 1},
            diffusion_committed_tokens=3, moe_assignments=5,
            moe_experts_hit=7,
            # The start and compiling (PR 39): once, and when they moved.
            step_first_use_stage_s={"trace": 3.0, "lower": 1.5,
                                    "backend": 2.5, "run": 0.5},
            compile_seconds={"trace": 4.0, "lower": 2.0, "backend": 9.0},
            compile_cache={"hit": 5, "miss": 0},
            startup={"pid": 1, "created": 0.0, "phases": dict.fromkeys(
                ("import", "init_device", "load_model", "profile",
                 "kv_cache", "precompile", "engine", "frontend"), 1.0)},
            **phases),
        IterationStats(num_generation_tokens=12, num_prompt_tokens=7,
                       ttfts=[0.05], output_s=0.0005))
    out: dict[str, set[tuple[str, str]]] = {}
    for line in reg.render().splitlines():
        if not line or line.startswith("#"):
            continue
        key = line.rpartition(" ")[0]
        name, _, labels = key.partition("{")
        out.setdefault(_family(name), set()).update(
            re.findall(r'(\w+)="([^"]*)"', labels))
    return out


def test_the_readers_are_found():
    # An empty list would pass the cases below by having none.
    assert _readers(), f"no reader under {BENCHMARK} names a vllm: series"


@pytest.mark.parametrize("reader", _readers(), ids=lambda p: p.stem)
def test_reader_names_only_series_that_are_exposed(reader, exposed):
    source = reader.read_text()
    families = {_family(n) for n in NAME_RE.findall(source)}
    missing = sorted(f for f in families if f not in exposed)
    assert not missing, f"{reader.name} reads {missing}: not on /metrics"

    for name, label, value in LABELLED_RE.findall(source):
        assert (label, value) in exposed[_family(name)], (
            f'{reader.name} reads {name}{{{label}="{value}"}}: no such sample')
    for label, var in FSTRING_LABEL_RE.findall(source):
        values = re.search(rf"for {var} in \(([^)]*)\)", source)
        assert values, f"{reader.name}: cannot tell what {var} runs over"
        for value in re.findall(r'"(\w+)"', values.group(1)):
            assert any((label, value) in exposed[f] for f in families), (
                f'{reader.name} reads {label}="{value}": no such sample')
