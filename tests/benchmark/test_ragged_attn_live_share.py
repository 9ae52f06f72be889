"""The reader of ``vllm:attn_ragged_pairs_total{kind}``: live over computed
pairs between the window's two scrapes, in per cent, and None from a
program without the counter or that ran the ragged kernel in no step."""

from __future__ import annotations

import pytest

from benchmark.harness.manifest import Cell, load_manifest

NAME = "ragged_attn_live_share"
LIVE = 'vllm:attn_ragged_pairs_total{kind="live"}'
COMPUTED = 'vllm:attn_ragged_pairs_total{kind="computed"}'


def ctx_of(m0: dict, m1: dict) -> dict:
    def delta(*names):
        return next((m1[n] - m0[n] for n in names if n in m0 and n in m1), None)

    return {"m0": m0, "m1": m1, "delta": delta}


@pytest.mark.parametrize("cell", ["mistral7b.chat", "sdar30b.chat"])
def test_every_cell_lists_it_under_the_kernels(cell):
    entry = next(m for m in Cell(load_manifest(), cell).per_layer if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
                     "layer": "kernels", "moves": "itl_p95_ms"}


@pytest.mark.parametrize("m0,m1,want", [
    # A recorded pair of scrapes: 1.3e9 of 2.0e9 computed pairs were live.
    ({LIVE: 4.0e8, COMPUTED: 9.0e8}, {LIVE: 1.7e9, COMPUTED: 2.9e9}, 65.0),
    # Every computed pair live.
    ({LIVE: 0.0, COMPUTED: 0.0}, {LIVE: 5.0e6, COMPUTED: 5.0e6}, 100.0),
    # A program without the counter (the parent): nothing read, nothing raised.
    ({"vllm:x": 1.0}, {"vllm:x": 2.0}, None),
    # Decode-only steps between the scrapes: the kernel ran in none.
    ({LIVE: 7.0, COMPUTED: 9.0}, {LIVE: 7.0, COMPUTED: 9.0}, None),
], ids=["recorded", "all_live", "no_counter", "no_ragged_step"])
@pytest.mark.parametrize("cell", ["mistral7b.chat", "sdar30b.chat"])
def test_live_share_between_the_scrapes(cell, m0, m1, want):
    got = Cell(load_manifest(), cell).layer_metric(NAME).read(ctx_of(m0, m1))
    assert got == (pytest.approx(want) if want is not None else None)
