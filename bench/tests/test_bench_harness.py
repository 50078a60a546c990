"""The benchmark's harness on the CPU: refusal without a chip, resolution
of every cell by name, names and units, and the resident batches."""
import importlib
import json
import os
import re
import shutil
import subprocess
import sys
from functools import partial
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import cell as cells
from bench import train
from bench.reference import cnn as ref

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
COMPARED = {"loss_gap", "loss0_gap", "grad_norm_gap", "grad_gap",
            "grad_median_gap", "delta_gap"}


def _run_cpu(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120)


def test_run_refuses_the_cpu_and_prints_no_result():
    out = _run_cpu(ROOT)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert "{" not in out.stdout


def test_run_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_cpu(tmp_path)
    assert out.returncode != 0
    assert "{" not in out.stdout


def test_benchmark_keys_names_and_units():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in SPEC["configs"]] + CELLS \
        + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(names)) == len(names)
    for n in names + [w["traffic"] for w in SPEC["workloads"]]:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    moves = {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["moves"] in moves for m in SPEC["per_layer"])


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_its_files(name):
    c = cells.resolve(name)
    assert c.chips in (1, 4)
    n = 1
    for v in c.traffic["mesh"].values():
        n *= v
    assert n == c.chips
    assert c.limits and set(c.limits) <= COMPARED
    assert c.config["peak"] in cells.peaks("TPU v5 lite")
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "samples_per_s"}
    assert c.per_layer
    for m in c.per_layer:
        assert callable(cells.metric_reader(m["name"]))


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        cells.peaks("cpu")


@pytest.mark.parametrize("name", [c["name"] for c in SPEC["configs"]])
def test_reference_weights_fit_the_program_model(name):
    """The benchmark's weights, from the reference of the configuration's
    family, have the program's parameter tree: same leaves, same shapes."""
    from repro.configs import get_config
    from repro.launch.build import build_model
    entry = next(c for c in SPEC["configs"] if c["name"] == name)
    config = json.loads((ROOT / entry["file"]).read_text())
    family = importlib.import_module(f"bench.reference.{config['family']}")
    arch = train.register_config(name, config)
    spec = build_model(get_config(arch)).params_spec()
    want = jax.tree.map(lambda s: tuple(s.shape), spec,
                        is_leaf=lambda s: hasattr(s, "axes"))
    got = jax.eval_shape(partial(family.init_params, m=config["model"]),
                         jax.random.key(0))
    assert jax.tree.map(lambda a: a.shape, got) == want


def test_resident_batches_follow_the_seed_and_step():
    m = {"kind": "resnet", "img": 8, "in_ch": 3, "n_classes": 10}
    tr = {"global_batch": 2, "resident_batches": 3}
    mesh = jax.make_mesh((1,), ("data",))
    whole = NamedSharding(mesh, P())
    shardings = {"images": whole, "labels": whole}

    def batches(seed):
        return train.make_resident(ref, m, tr, shardings,
                                   train.seed_key(seed, 1))

    big = 2 ** 33 + 7
    a, b, c = batches(big), batches(big), batches(7)
    la, lb = train.ResidentLoader(a), train.ResidentLoader(b)
    for step in (0, 1, 2, 5):
        x, y = la.batch_at(step), lb.batch_at(step)
        np.testing.assert_array_equal(x["images"], y["images"])
        np.testing.assert_array_equal(x["labels"], y["labels"])
    assert la.batch_at(4) is la.batch_at(1)
    assert not np.array_equal(a[0]["images"], a[1]["images"])
    # seeds that agree in their low 32 bits still give different batches
    assert not np.array_equal(a[0]["images"], c[0]["images"])
