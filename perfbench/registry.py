"""Find the benchmark's pieces by the names ``BENCHMARK.json`` gives.

Each piece is a file of its own, so a later change adds a cell with new
files and new ``BENCHMARK.json`` entries and edits nothing here:

* a configuration is ``<file>`` of its ``configs`` entry (JSON);
* a traffic mix is ``traffic/<traffic>.json``, a data file naming its
  ``kind``;
* a traffic kind is ``kinds/<kind>.py`` with a ``Driver`` class;
* a cell's correctness limits are ``limits/<workload>.json``;
* a per-layer metric is ``metrics/<name>.py`` with ``read(ctx)``;
* a matrix generator is ``matrices/<generator>.py`` with ``build(params)``.
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise KeyError(f"no {kind} named {name!r} (looked for {path})")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark() -> dict:
    return _json(os.path.join(CHECKOUT, "BENCHMARK.json"))


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; known: "
                   f"{sorted(e['name'] for e in entries)}")


def workload(bench: dict, name: str) -> dict:
    return _by_name(bench["workloads"], name, "workload")


def load_config(bench: dict, name: str) -> dict:
    entry = _by_name(bench["configs"], name, "configuration")
    return _json(os.path.join(CHECKOUT, entry["file"]))


def load_traffic(name: str) -> dict:
    path = os.path.join(HERE, "traffic", f"{name}.json")
    if not os.path.isfile(path):
        raise KeyError(f"no traffic mix named {name!r} (looked for {path})")
    return _json(path)


def load_kind(name: str):
    """The ``Driver`` class of one traffic kind."""
    return _module("kinds", name).Driver


def load_limits(cell: str) -> dict:
    path = os.path.join(HERE, "limits", f"{cell}.json")
    if not os.path.isfile(path):
        raise KeyError(f"no limits for workload {cell!r} (looked for {path})")
    return _json(path)


def load_metric(name: str):
    """The reader ``read(ctx) -> float | None`` of one per-layer metric."""
    return _module("metrics", name).read


def load_generator(name: str):
    """``build(params) -> scipy CSR`` of one matrix generator."""
    return _module("matrices", name).build


def metrics_for(entries: list, cell: str) -> list:
    """The metric entries a cell reports: those with no ``workloads`` key,
    and those whose ``workloads`` name the cell."""
    return [m for m in entries if cell in m.get("workloads", [cell])]


def peak(device_kind: str) -> dict:
    table = _json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no published peak for device_kind "
                       f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]
