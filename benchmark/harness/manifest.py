"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``. Its configuration is
``benchmark/configs/<config>.json``, its traffic mix
``benchmark/traffic/<traffic>.json``, its own numbers (offered rate, knee,
engine arguments for its traffic, warm-up waves, limits of ``correct``)
``benchmark/cells/<name>.json``, each per-layer metric
``benchmark/layer_metrics/<metric>.py`` and the configuration's plain
reference ``benchmark/reference/<family>.py``. Adding a cell adds files and
entries; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def load_module(path: Path):
    """Import one file of the benchmark by path (a layer metric, a
    kernel's counting functions, a reference)."""
    if not path.is_file():
        raise FileNotFoundError(f"the benchmark has no file {path}")
    name = "bench_" + re.sub(r"[^A-Za-z0-9_]", "_", str(path.relative_to(BENCH_DIR)))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """Everything one run needs to know, gathered from the data files."""

    def __init__(self, manifest: dict, name: str) -> None:
        entries = [w for w in manifest["workloads"] if w["name"] == name]
        if len(entries) != 1:
            known = [w["name"] for w in manifest["workloads"]]
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has {known}")
        self.entry = entries[0]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.config_name = self.entry["config"]
        self.traffic_name = self.entry["traffic"]
        cfg_entry = next(
            c for c in manifest["configs"] if c["name"] == self.config_name
        )
        self.config_file = ROOT / cfg_entry["file"]
        self.config = load_json(self.config_file)
        self.traffic = load_json(BENCH_DIR / "traffic" / f"{self.traffic_name}.json")
        self.numbers = load_json(BENCH_DIR / "cells" / f"{name}.json")
        self.end_to_end = [
            m for m in manifest["end_to_end"]
            if name in m.get("workloads", [name])
        ]
        self.per_layer = [
            m for m in manifest["per_layer"]
            if name in m.get("workloads", [name])
        ]

    @property
    def hf_config(self) -> dict:
        """The model's ``config.json`` as it is run: the configuration
        file without the benchmark's own group."""
        return {k: v for k, v in self.config.items() if k != "bench"}

    @property
    def engine_args(self) -> dict:
        """The ``serve`` command's arguments off their defaults: what the
        configuration needs on this chip, then what this cell's deployment
        sets for its traffic (``cells/<cell>.json``), which wins."""
        return {**self.config["bench"].get("engine_args", {}),
                **self.numbers.get("engine_args", {})}

    @property
    def family(self) -> str:
        return self.config["bench"]["family"]

    def layer_metric(self, metric_name: str):
        return load_module(BENCH_DIR / "layer_metrics" / f"{metric_name}.py")


def kernel_counts(kernel: str):
    """The counting functions of one kernel: ``benchmark/kernels/<kernel>.py``."""
    return load_module(BENCH_DIR / "kernels" / f"{kernel}.py")


def peaks_for(device_kind: str) -> dict:
    table = load_json(BENCH_DIR / "peaks.json")["device_kinds"]
    if device_kind not in table:
        raise KeyError(
            f"benchmark/peaks.json has no peaks for device_kind {device_kind!r} "
            f"(it has {sorted(table)}); add the chip with its source, never a default"
        )
    return table[device_kind]
