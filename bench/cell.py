"""Find a cell, its configuration, traffic mix, limits and metrics by name.

Everything here is read from ``BENCHMARK.json`` and from files named after
the entries in it, so a new cell, configuration, traffic mix or metric is a
new file and a new entry, never an edit of this code.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration file as run
    traffic: dict           # the traffic mix's parameters
    limits: dict            # compared number -> limit
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def resolve(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    return from_files(name, w["config"], w["traffic"], int(w["chips"]), root)


def from_files(name: str, config: str, traffic: str, chips: int,
               root: Path = ROOT) -> Cell:
    """A cell from the files its names point at: ``bench/configs/<config>``,
    ``bench/traffic/<traffic>``, ``bench/limits/<name>``; its metrics are
    those of ``BENCHMARK.json`` that apply to it."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    bench = root / "bench"
    return Cell(
        name=name, chips=chips, config_name=config,
        config=json.loads((bench / "configs" / f"{config}.json").read_text()),
        traffic=json.loads((bench / "traffic" / f"{traffic}.json").read_text()),
        limits=json.loads((bench / "limits" / f"{name}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The published peaks of one chip of this kind. A device that is not
    in the table is an error, never a default."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; known: {sorted(table)}")
    return table[device_kind]


def metric_reader(name: str, root: Path = ROOT):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
