"""``correct`` comes out false when the timed path of a cell is broken
underneath: a step that returns its state unchanged, half of the batch left
out, an answer altered where it is produced, the halo exchange left out (on
cells that shard the spatial axis), and the control (the reference in the
program's place with its activations, or its activations and parameters, in
bfloat16). A sound run at the same size comes out true. Every cell of
``BENCHMARK.json`` runs at its tiny sizes, on as many devices as it has
chips: in one child process per cell where this process sees fewer."""
import functools
import json
import re
from pathlib import Path

import pytest

from bench.cell import resolve
from bench.tests import bench_faults

SPEC = json.loads((Path(__file__).resolve().parents[2]
                   / "BENCHMARK.json").read_text())


def _cases():
    for w in SPEC["workloads"]:
        cell = resolve(w["name"])
        for fault in bench_faults.FAULTS:
            if fault != "halo" or bench_faults.shards_space(cell):
                yield w["name"], fault


CASES = list(_cases())


@functools.cache
def _results(name):
    """fault -> result of all the cases of cell ``name`` at its tiny
    sizes, run once; or, where that run failed, the error, raised again by
    each of its cases, so that a child that hangs costs one timeout."""
    try:
        return bench_faults.run(bench_faults.tiny(name),
                                [f for n, f in CASES if n == name])
    except Exception as e:
        return e


@pytest.mark.parametrize("name,fault", CASES)
def test_every_cell_catches_each_fault(name, fault):
    results = _results(name)
    if isinstance(results, Exception):
        raise results
    r = results[fault]
    assert r["devices"] == resolve(name).chips
    assert r["correct"] is (fault == "sound"), r["checks"]


def test_a_configuration_without_tiny_sizes_is_an_error(monkeypatch,
                                                        tmp_path):
    monkeypatch.setattr(bench_faults, "HERE", tmp_path)
    with pytest.raises(FileNotFoundError,
                       match=re.escape(
                           str(tmp_path / "tiny" / "resnet50.json"))):
        bench_faults.tiny("resnet50.b128")


def test_a_halo_fault_needs_a_spatially_sharded_cell():
    with pytest.raises(ValueError, match="no halo to break"):
        with bench_faults.planted("halo", bench_faults.tiny("resnet50.b128")):
            pass
