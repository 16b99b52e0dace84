"""BENCHMARK.json and the files it names: everything a cell is, as data.

A cell (one entry of `workloads`) is found by name and brings: its
configuration's file (`configs[].file`), its traffic mix
(`benchmark/traffic/<traffic>.json`), its own parameters
(`benchmark/cells/<cell>.json`: the fixed rate a sweep found, the limits of
the comparison that decides `correct`), the driver for the mix's `kind`
(`benchmark/drivers/<kind>.py`) and the per-layer readers
(`benchmark/metrics/<metric>.py`). Nothing here, or anywhere in the
harness, branches on a cell's name.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _read(path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    mix_name: str
    mix: dict
    params: dict
    end_to_end: list     # the manifest's end-to-end entries for this cell
    per_layer: list      # the manifest's per-layer entries for this cell
    peaks: dict = None   # the device's row of peaks.json, set by the run


def load_manifest(root=ROOT) -> dict:
    return _read(os.path.join(root, "BENCHMARK.json"))


def _reported(metric: dict, cell_name: str, cell_e2e=None) -> bool:
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return cell_e2e is None or metric["moves"] in cell_e2e


def load_cell(name: str, root=ROOT, bench_dir=HERE) -> Cell:
    manifest = load_manifest(root)
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    config = next(c for c in manifest["configs"]
                  if c["name"] == entry["config"])
    e2e = [m for m in manifest["end_to_end"] if _reported(m, name)]
    names = {m["name"] for m in e2e}
    return Cell(
        name=name, chips=int(entry["chips"]), config_name=config["name"],
        config=_read(os.path.join(root, config["file"])),
        mix_name=entry["traffic"],
        mix=_read(os.path.join(bench_dir, "traffic",
                               entry["traffic"] + ".json")),
        params=_read(os.path.join(bench_dir, "cells", name + ".json")),
        end_to_end=e2e,
        per_layer=[m for m in manifest["per_layer"]
                   if _reported(m, name, names)])


def load_driver(kind: str):
    return importlib.import_module("benchmark.drivers." + kind)


def load_reader(metric_name: str, bench_dir=HERE):
    """The `read(trace, host, cell)` of benchmark/metrics/<metric>.py
    (loaded by path: a metric's name may hold dots)."""
    path = os.path.join(bench_dir, "metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + metric_name.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_peaks(device_kind: str, bench_dir=HERE) -> dict:
    table = _read(os.path.join(bench_dir, "peaks.json"))["device_kinds"]
    if device_kind not in table:
        raise SystemExit(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json: "
            "a device without published peaks is an error, not a default")
    return table[device_kind]
