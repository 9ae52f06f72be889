"""Adding to the benchmark is adding files and appending entries: a copy of
the tree with a SYNTHETIC configuration, cell and per-layer metric more
than it has (the counts are read off the manifest, never written down: as
literals they failed from PR 43 to PR 46, whenever a real entry was
appended), each made of copies of accepted files and appended at the
end of its list, passes the form checks of ``test_manifest.py`` and the two
accepted tests that once held an entry to a place, with none of them touched.

This is what a ``model_config`` PR does, which may add files and entries and
may edit no file that is there. Before PR 42 two accepted tests held an
entry to the last place of ``per_layer`` and every such PR failed them."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

from benchmark.harness import manifest as M

from . import test_manifest as forms

CONFIG, CELL, METRIC = "synthetic-7b", "synthetic7b.chat", "synthetic_steps_per_s"
LISTS = ("configs", "workloads", "per_layer")
BEFORE = M.load_manifest()  # the manifest as the tree has it, before the append


def counts_of(manifest: dict) -> list[int]:
    return [len(manifest[k]) for k in LISTS]
READER = '''"""Steps a second of the window (a synthetic reader for the tests)."""


def read(ctx):
    steps = ctx["steps"]()
    return steps / ctx["scrape_dt"] if steps else None
'''


def append_to(root: Path) -> None:
    """Write the synthetic files under ``root`` (a copy of the benchmark)
    and append their entries to its ``BENCHMARK.json``."""
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    bench = root / "benchmark"
    shutil.copy(bench / "configs" / "mistral-7b-v0.3.json", bench / "configs" / f"{CONFIG}.json")
    # A cell that states its own ladder: pinned, and no wave beyond it.
    numbers = json.loads((bench / "cells" / "sdar30b.chat.json").read_text())
    numbers["warmup_waves"] = numbers["warmup_waves"][:6]
    (bench / "cells" / f"{CELL}.json").write_text(json.dumps(numbers, indent=1))
    (bench / "layer_metrics" / f"{METRIC}.py").write_text(READER)
    first = manifest["configs"][0]
    manifest["configs"].append({**first, "name": CONFIG, "file": f"benchmark/configs/{CONFIG}.json"})
    manifest["workloads"].append({"name": CELL, "config": CONFIG, "traffic": "chat", "chips": 1,
                                  "why": "a copy of accepted files under new names, for the tests"})
    manifest["per_layer"].append({"name": METRIC, "unit": "steps/s", "better": "higher",
                                  "source": "program_counter", "layer": "runner step",
                                  "moves": "output_tokens_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1) + "\n")


@pytest.fixture(scope="module")
def tree(tmp_path_factory) -> Path:
    """A copy of what the benchmark is made of, with the additions."""
    root = tmp_path_factory.mktemp("appended")
    shutil.copytree(M.BENCH_DIR, root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(M.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    append_to(root)
    return root


def test_the_form_checks_pass_over_appended_entries(tree, monkeypatch):
    monkeypatch.setattr(M, "ROOT", tree)
    monkeypatch.setattr(M, "BENCH_DIR", tree / "benchmark")
    manifest = M.load_manifest()
    assert counts_of(manifest) == [n + 1 for n in counts_of(BEFORE)]
    for k in LISTS:  # appended: what was there is there, in its order
        assert manifest[k][:-1] == BEFORE[k]
    forms.check_accepted_entries_are_a_prefix(manifest)
    forms.check_top_level_keys_and_limits(manifest)
    forms.check_names_and_units_use_only_the_allowed_characters(manifest)
    for cfg in manifest["configs"]:
        forms.check_a_configuration_states_its_source_its_cuts_and_what_it_stands_for(manifest, cfg)
    for w in manifest["workloads"]:
        forms.check_every_file_a_cell_names_exists_and_loads(manifest, w["name"])
        forms.check_the_warm_up_walks_the_cells_own_ladder(manifest, w["name"])
    # The new cell reports the new metric, and its six waves walk its own
    # twelve programs (a mixed and a decode-only one a page bucket).
    cell = M.Cell(manifest, CELL)
    assert METRIC in {m["name"] for m in cell.per_layer}
    assert cell.layer_metric(METRIC).read({"steps": lambda: 100.0, "scrape_dt": 50.0}) == 2.0
    _, reached = forms.check_the_warm_up_walks_the_cells_own_ladder(manifest, CELL)
    assert len(reached) == 12 and len(cell.numbers["warmup_waves"]) == 6


@pytest.mark.parametrize("what", ["per_layer", "workloads", "configs"])
def test_an_entry_put_before_an_accepted_one_is_refused(what):
    """The other half of the rule: appending is free, a place inside the
    accepted prefix is not (the driver reads it as a change to what was
    there, and refused PR 39 for it)."""
    manifest = M.load_manifest()
    entries = list(manifest[what])
    manifest[what] = entries[:1] + [{**entries[0], "name": "put-in-between"}] + entries[1:]
    with pytest.raises(AssertionError, match="accepted entry"):
        forms.check_accepted_entries_are_a_prefix(manifest)
    manifest[what] = entries[1:]
    with pytest.raises(AssertionError, match="accepted entry"):
        forms.check_accepted_entries_are_a_prefix(manifest)


def test_the_two_tests_that_once_held_a_place_pass_over_the_appended_tree(tree, monkeypatch):
    """``test_kda_chunk_roofline.py`` held its entry to the LAST place and
    ``test_setup_timeline_metrics.py`` said what may stand after place 28:
    both as they are now, over the manifest with the three entries more."""
    from . import test_kda_chunk_roofline as chunk
    from . import test_setup_timeline_metrics as timeline

    monkeypatch.setattr(M, "ROOT", tree)
    monkeypatch.setattr(M, "BENCH_DIR", tree / "benchmark")
    manifest = M.load_manifest()
    assert len(manifest["per_layer"]) == len(BEFORE["per_layer"]) + 1
    assert manifest["per_layer"][-1]["name"] == METRIC
    monkeypatch.setattr(chunk, "MANIFEST", manifest)
    monkeypatch.setattr(timeline, "MANIFEST", manifest)
    monkeypatch.setattr(timeline, "CELLS", [w["name"] for w in manifest["workloads"]])
    chunk.test_the_entry_lists_the_one_cell_under_the_kernels()
    timeline.test_what_the_benchmark_had_is_as_it_was()
    for name in timeline.READERS:
        timeline.test_an_entry_moves_setup_s_in_every_cell(name)
