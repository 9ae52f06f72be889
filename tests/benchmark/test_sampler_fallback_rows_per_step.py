"""The reader of ``vllm:sampler_fallback_rows_total``: rows a step between
the window's two scrapes, and None from a program without the counter."""

from __future__ import annotations

import pytest

from benchmark.harness.manifest import Cell, load_manifest

NAME = "sampler_fallback_rows_per_step"
ROWS = "vllm:sampler_fallback_rows_total"
COUNT = 'vllm:engine_step_duration_seconds_count{phase="dispatch"}'


def ctx_of(m0: dict, m1: dict) -> dict:
    def delta(*names):
        return next((m1[n] - m0[n] for n in names if n in m0 and n in m1), None)

    return {"m0": m0, "m1": m1, "delta": delta, "steps": lambda: delta(COUNT)}


@pytest.mark.parametrize("cell", ["mistral7b.chat", "sdar30b.chat"])
def test_every_cell_lists_it_under_the_kernels(cell):
    entry = next(m for m in Cell(load_manifest(), cell).per_layer if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "rows", "better": "lower", "source": "program_counter",
                     "layer": "kernels", "moves": "itl_p95_ms"}


@pytest.mark.parametrize("m0,m1,want", [
    # 192,000 positions fell back over 930 steps of block passes.
    ({ROWS: 1000.0, COUNT: 70.0}, {ROWS: 193000.0, COUNT: 1000.0}, 192000.0 / 930.0),
    # The kernel took every sampling step.
    ({ROWS: 1000.0, COUNT: 70.0}, {ROWS: 1000.0, COUNT: 3070.0}, 0.0),
    # A program without the counter: nothing to read, nothing raised.
    ({COUNT: 70.0}, {COUNT: 1000.0}, None),
    # No step between the scrapes.
    ({ROWS: 5.0, COUNT: 70.0}, {ROWS: 5.0, COUNT: 70.0}, None),
], ids=["fallback", "kernel", "no_counter", "no_steps"])
def test_rows_a_step_between_the_scrapes(m0, m1, want):
    got = Cell(load_manifest(), "sdar30b.chat").layer_metric(NAME).read(ctx_of(m0, m1))
    assert got == (pytest.approx(want) if want is not None else None)
