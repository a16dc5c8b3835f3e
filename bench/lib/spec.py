"""Find everything a cell needs by name.

``BENCHMARK.json`` at the checkout root lists the configurations, cells and
metrics. Each configuration, traffic mix, per-layer metric reader, output
reference and cell limit is a file of its own under ``bench/``, so a later
change adds a cell by adding files and entries, never by editing one:

    bench/configs/<config>.json         sizes, engine settings, provenance
    bench/traffic/<traffic>.json        generator parameters
    bench/layer_metrics/<metric>.py     reader of one per-layer metric; a
                                        ``<base>.<variant>`` metric without
                                        a file of its own uses ``<base>.py``
    bench/references/<reference>.py     plain reference named by a config
    bench/limits/<cell>.json            limits of the output check
    bench/peaks.json                    peak rates by ``device_kind``
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(RuntimeError):
    """A cell, file or entry the harness was asked for does not exist."""


def _read_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


class Bench:
    """``BENCHMARK.json`` and the files it names, rooted at ``root``."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "bench")
        self.spec = _read_json(os.path.join(root, "BENCHMARK.json"))

    # ---------------------------------------------------------- entries --
    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")

    def config_entry(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return c
        raise SpecError(f"no config {name!r} in BENCHMARK.json")

    def end_to_end(self, cell: str) -> list:
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list:
        """Per-layer metrics read in ``cell``: those listing it, and those
        without a list whose end-to-end metric the cell reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.spec["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    # ------------------------------------------------------------ files --
    def _file(self, *parts: str) -> str:
        return os.path.join(self.bench_dir, *parts)

    def config(self, name: str) -> dict:
        entry = self.config_entry(name)
        cfg = _read_json(os.path.join(self.root, entry["file"]))
        cfg["name"] = name
        return cfg

    def traffic(self, name: str) -> dict:
        t = _read_json(self._file("traffic", f"{name}.json"))
        t["name"] = name
        return t

    def limits(self, cell: str) -> dict:
        return _read_json(self._file("limits", f"{cell}.json"))

    def peaks(self) -> dict:
        return _read_json(self._file("peaks.json"))["devices"]

    def reader_path(self, metric: str) -> str:
        """``bench/layer_metrics/<metric>.py``, else the base name's."""
        for stem in (metric, metric.split(".")[0]):
            path = self._file("layer_metrics", f"{stem}.py")
            if os.path.isfile(path):
                return path
        raise SpecError(f"no reader for per-layer metric {metric!r}")


def load_module(path: str, name: Optional[str] = None):
    """Import one file as a module (readers and references are files
    found by name, not members of a package)."""
    name = name or "bench_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(bench: Bench, metric: str, ctx: dict):
    """Run the reader of ``metric`` on a run's context; None when the run
    gave it nothing to read."""
    mod = load_module(bench.reader_path(metric),
                      "bench_reader_" + metric.replace(".", "_"))
    return mod.read(ctx, metric)
