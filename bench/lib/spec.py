"""A cell, found by name: its entry in ``BENCHMARK.json``, its configuration
(``configs/<config>.json``), its traffic mix (``traffic/<traffic>.json``),
the limits of its correctness numbers (``limits/<cell>.json``) and the
per-layer metrics that list it."""

from __future__ import annotations

import importlib
import json
import os
import re
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    model: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: str | None = None) -> Cell:
    """``benchmark``: another ``BENCHMARK.json``, whose directory then holds
    the ``configs``, ``traffic`` and ``limits`` (the tests' tiny cells)."""
    data = os.path.dirname(benchmark) if benchmark else BENCH_DIR
    bench = _load(benchmark or os.path.join(ROOT, "BENCHMARK.json"))
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has {sorted(entries)}")
    w = entries[name]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        model=_load(os.path.join(data, "configs", f"{w['config']}.json")),
        traffic=_load(os.path.join(data, "traffic", f"{w['traffic']}.json")),
        limits=_load(os.path.join(data, "limits", f"{name}.json")),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )


def kind_module(cell: Cell):
    """The driver of a cell's traffic kind: ``bench/lib/<kind>.py``, which
    gives the kind's ``Run``, ``checked_run``, ``calibration_faults``,
    ``end_to_end`` and ``work``."""
    kind = cell.traffic["kind"]
    if not re.fullmatch(r"[a-z_]+", kind) or not os.path.exists(
            os.path.join(BENCH_DIR, "lib", f"{kind}.py")):
        raise SystemExit(f"no driver bench/lib/{kind}.py for traffic kind {kind!r}")
    return importlib.import_module(f"bench.lib.{kind}")


def peaks(device_kind: str) -> dict:
    table = _load(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]
