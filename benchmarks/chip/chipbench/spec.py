"""Where the benchmark finds its parts, by name.

``BENCHMARK.json`` at the repository root names every configuration, cell
and metric. Each part lives in a file of its own under ``benchmarks/chip``
and is found from its name alone, so a later change adds a cell, a traffic
mix or a metric by adding files:

- ``configs/<config>.json``  — the configuration (also named by ``file``);
- ``traffic/<traffic>.json`` — the traffic mix, read by ``loadgen``;
- ``metrics/<metric>.py``    — a reader with ``read(ctx) -> float | None``;
- ``cells/<cell>.json``      — the cell's own settings: traffic parameters
  that hold for it alone (the offered rate) and the limits of the numbers
  ``correct`` compares;
- ``peaks.json``             — the device peaks, keyed by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import List

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: "
                   f"{', '.join(w['name'] for w in bench['workloads'])}")


def config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def kind(cfg: dict) -> str:
    """``train`` for a training-step configuration; a PROFET bank's file
    leaves ``kind`` out and is ``bank``."""
    return cfg.get("kind", "bank")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def cell_settings(cell_name: str) -> dict:
    return load_json(BENCH / "cells" / f"{cell_name}.json")


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; known: {', '.join(sorted(table))}")
    return table[device_kind]


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end(bench: dict, cell_name: str) -> List[dict]:
    """The end-to-end metrics a cell reports in a ``--trace 0`` run."""
    return [m for m in bench["end_to_end"] if _applies(m, cell_name)]


def per_layer(bench: dict, cell_name: str) -> List[dict]:
    """The per-layer metrics a cell reports in a ``--trace 1`` run: those
    that list the cell, or, without a list, every cell that reports the
    end-to-end metric the metric moves."""
    e2e = {m["name"] for m in end_to_end(bench, cell_name)}
    return [m for m in bench["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def reader(metric_name: str):
    """The ``read(ctx)`` function of ``metrics/<metric_name>.py``."""
    path = BENCH / "metrics" / f"{metric_name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric_name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
