"""The five readers that split ``setup_s`` (PR 39), each on a scrape written
down by hand: the number on a program that has the series, None on the
parent's scrape (which has none of them).

Their entries in ``BENCHMARK.json`` came with PR 42, appended after
``kda_conv_roofline`` in the order of ``READERS``; their form is held here,
their place by ``test_manifest.py``'s accepted prefix."""

from __future__ import annotations

import pytest

from benchmark.harness.manifest import Cell, load_manifest

MANIFEST = load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
STARTUP = "vllm:startup_seconds"
STAGE = "vllm:step_first_use_stage_seconds_total"
CACHE = "vllm:compile_cache_total"

# A warm start of 41 s, first uses of 20 s of which Python took 14.
PHASES = {"import": 9.5, "init_device": 0.25, "load_model": 14.0, "profile": 8.0,
          "kv_cache": 1.5, "precompile": 0.0, "engine": 0.75, "frontend": 7.0}
M0 = {
    **{f'{STARTUP}{{phase="{p}"}}': s for p, s in PHASES.items()},
    f'{STAGE}{{stage="trace"}}': 9.0, f'{STAGE}{{stage="lower"}}': 5.0,
    f'{STAGE}{{stage="backend"}}': 4.0, f'{STAGE}{{stage="run"}}': 2.0,
    "vllm:step_first_use_seconds_total": 20.0,
    f'{CACHE}{{result="hit"}}': 57.0, f'{CACHE}{{result="miss"}}': 3.0,
}
# What the parent exposes of the set-up: the one sum.
PARENT_M0 = {"vllm:step_first_use_seconds_total": 20.0,
             "vllm:step_bucket_compiles": 8.0}

READERS = {
    "setup_ready_s": (41.0, "engine start"),
    "setup_load_model_s": (14.0, "worker"),
    "setup_profile_s": (9.5, "worker"),
    "setup_first_use_python_s": (14.0, "runner step"),
    "setup_compile_cache_hit_share": (95.0, "runner step"),
}


def read(name: str, m0: dict):
    return Cell(MANIFEST, "mistral7b.chat").layer_metric(name).read({"m0": m0})


@pytest.mark.parametrize("name", READERS)
def test_the_reader_on_a_scrape_that_has_its_series(name):
    assert read(name, M0) == pytest.approx(READERS[name][0])


@pytest.mark.parametrize("name", READERS)
def test_the_reader_finds_nothing_on_the_parents_scrape_and_does_not_raise(name):
    assert read(name, PARENT_M0) is None
    assert read(name, {}) is None


@pytest.mark.parametrize("name", READERS)
def test_an_entry_moves_setup_s_in_every_cell(name):
    entries = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entries == [{
        "name": name, "unit": "%" if name.endswith("_share") else "s",
        "better": "higher" if name.endswith("_share") else "lower",
        "source": "program_counter", "layer": READERS[name][1], "moves": "setup_s"}]
    # No list of cells: every cell reports setup_s, so every cell reports it.
    for cell in CELLS:
        assert len(entries) == sum(m["name"] == name for m in Cell(MANIFEST, cell).per_layer)


def test_what_the_benchmark_had_is_as_it_was():
    """The five stand in the order of ``READERS``, after every entry that
    was accepted before them; what a later PR appends after them is free
    (``test_manifest.py`` holds the whole accepted prefix)."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    places = [names.index(n) for n in READERS]
    assert places == sorted(places) and places[0] > names.index("kda_conv_roofline") > names.index(
        "setup_first_use_s")


@pytest.mark.parametrize("m0,want", [
    # A warm run that asked for 60 programs and had them all.
    ({f'{CACHE}{{result="hit"}}': 60.0, f'{CACHE}{{result="miss"}}': 0.0}, 100.0),
    # The run that compiles.
    ({f'{CACHE}{{result="hit"}}': 0.0, f'{CACHE}{{result="miss"}}': 60.0}, 0.0),
    # A cache that was asked for nothing says nothing.
    ({f'{CACHE}{{result="hit"}}': 0.0, f'{CACHE}{{result="miss"}}': 0.0}, None),
])
def test_the_hit_share_tells_a_warm_run_from_one_that_compiled(m0, want):
    assert read("setup_compile_cache_hit_share", m0) == want


def test_a_phase_missing_from_the_family_is_not_read_as_zero():
    """``setup_profile_s`` is three phases: without one of them the sum
    would under-read, so it reads nothing."""
    m0 = {k: v for k, v in M0.items() if "kv_cache" not in k}
    assert read("setup_profile_s", m0) is None
    assert read("setup_ready_s", m0) == pytest.approx(39.5)  # what is there, summed
    m0 = {k: v for k, v in M0.items() if 'stage="lower"' not in k}
    assert read("setup_first_use_python_s", m0) is None


def test_the_split_stays_under_setup_s():
    """Start plus first uses, by the program's clock, is less than the
    ``setup_s`` the benchmark takes around them (waves, check, ramp)."""
    assert read("setup_ready_s", M0) + M0["vllm:step_first_use_seconds_total"] == 61.0
    assert read("setup_first_use_python_s", M0) <= M0["vllm:step_first_use_seconds_total"]
