"""Pieces of ``run_cell.py`` that need no server."""

from __future__ import annotations

import pytest

from benchmark import run_cell as rc
from benchmark.harness.manifest import Cell, load_manifest
from benchmark.harness.server import engine_cli_args

from .xplane_writer import xspace


def test_a_trace_without_a_device_plane_is_told_from_one_with(tmp_path):
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [(0, 10, "f")]}]}
    dev = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [(0, 10, "%fusion.1 = x")]}]}
    a = tmp_path / "a" / "plugins" / "profile" / "t"
    b = tmp_path / "b" / "plugins" / "profile" / "t"
    a.mkdir(parents=True)
    b.mkdir(parents=True)
    (a / "h.xplane.pb").write_bytes(xspace([host]))
    (b / "h.xplane.pb").write_bytes(xspace([host, dev]))
    assert not rc.has_device_plane(tmp_path / "a")
    assert rc.has_device_plane(tmp_path / "b")
    assert not rc.has_device_plane(tmp_path / "none")


@pytest.mark.parametrize("key", ["p50_abs", "p99_abs", "worst_sequence_mean_abs"])
def test_each_number_compared_is_printed_beside_its_limit(key):
    cell = Cell(load_manifest(), "mistral7b.chat")
    limits = cell.numbers["correct_limits"]
    sound = {k: v / 2 for k, v in limits.items()}
    ok, lines = rc.decide_correct(cell, sound)
    assert ok and len(lines) == len(limits) == 3
    line = next(l for l in lines if f"{key} = " in l)
    assert f"limit {limits[key]:g}" in line and "ok" in line
    # One number over its limit is enough, whatever the others read.
    bad, lines = rc.decide_correct(cell, {**sound, key: limits[key] * 2})
    assert not bad and sum("OVER" in l for l in lines) == 1


@pytest.mark.parametrize("args,want", [
    ({}, []),
    ({"kv_cache_dtype": "fp8"}, ["--kv-cache-dtype", "fp8"]),
    ({"enable_prefix_caching": False, "max_model_len": 8192},
     ["--no-enable-prefix-caching", "--max-model-len", "8192"]),
    ({"precompile": True}, ["--precompile"]),
])
def test_engine_arguments_become_the_serve_commands_flags(args, want):
    assert engine_cli_args(args) == want
