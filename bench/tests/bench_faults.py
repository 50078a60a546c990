"""Drive a cell at a size the CPU can hold, with the timed path broken.

    python -m bench.tests.bench_faults resnet50.b128 sound control
    python -m bench.tests.bench_faults - sound halo < tiny_cell.json

Used by the fault tests: it skips the harness's look for a chip, shrinks the
cell's model and batch to the sizes in ``bench/tests/tiny/<config>.json``,
keeps its traffic's other parameters and its limits, plants one fault in the
program, and returns each run's ``correct``, the numbers compared and the
devices it ran on. The reference is the configuration's family's
(``bench/reference/<family>.py``), as in a run.

A cell runs on as many devices as it has chips. Where this process sees
fewer, as a CPU process sees one, the faults asked of the cell run in one
child process on that many host devices (``JAX_PLATFORMS=cpu``,
``--xla_force_host_platform_device_count``), which gets the shrunk cell as
JSON on its standard input (the workload ``-``) and has a timeout of its
own. The second form above is that child's: a shrunk cell, run in the
process that reads it.

The faults: ``sound`` (none); ``unchanged`` (the step returns its state
unchanged); ``half_batch`` (the step sees the first half of each batch);
``answer`` (the new parameters 1% off where the step produces them);
``halo`` (the rows that ``parallel/halo._exchange`` returns are zeroed, so
each shard's convolution sees zero padding where it should see its
neighbour's rows: only on cells whose ``strategy`` shards the spatial
axis); ``control`` and ``control_params`` (the plain reference in the
program's place, its activations, or its activations and parameters, in
bfloat16).
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os
import subprocess
import sys
import time
from contextlib import contextmanager
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import train
from bench.cell import Cell, resolve
from repro.parallel.strategies import STRATEGIES

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
FAULTS = ("sound", "unchanged", "half_batch", "answer", "halo", "control",
          "control_params")
SEED = 2 ** 32 + 11
CHILD_TIMEOUT = 300      # seconds for all the faults of one child


def tiny(name: str) -> Cell:
    """The cell ``name`` at the sizes of ``bench/tests/tiny/<config>.json``:
    ``model`` and ``overrides`` replace those keys of the configuration's
    ``model`` and ``overrides``, and ``batch`` is the global batch. Widths
    that the program fixes stay as they are. A configuration without that
    file is an error that names it."""
    c = resolve(name)
    path = HERE / "tiny" / f"{c.config_name}.json"
    if not path.is_file():
        raise FileNotFoundError(
            f"no tiny sizes for configuration {c.config_name!r}: {path}")
    sizes = json.loads(path.read_text())
    config = dict(c.config, model=dict(c.config["model"], **sizes["model"]),
                  overrides=dict(c.config.get("overrides") or {},
                                 **sizes["overrides"]))
    return dataclasses.replace(
        c, config_name=f"{c.config_name}_tiny", config=config,
        traffic=dict(c.traffic, global_batch=sizes["batch"]))


def shards_space(cell: Cell) -> bool:
    """Whether the cell's strategy shards the spatial axis, so that its
    convolutions exchange halos: the only cells a ``halo`` fault breaks."""
    return STRATEGIES[cell.traffic["strategy"]].get("spatial") is not None


def reference_in_place(cell: Cell, param_dtype):
    """The control: the reference in the program's place, its activations
    in bfloat16 and its parameters in ``param_dtype``, on the program's
    state and metrics."""
    cfg = cell.config
    ref = importlib.import_module(f"bench.reference.{cfg['family']}")
    step = partial(ref.train_step, m=cfg["model"], opt=cfg["optimizer"],
                   lr=cell.traffic["lr"], dtype=jnp.bfloat16,
                   precision=cfg["matmul_precision"])

    def control(state, batch):
        params = jax.tree.map(lambda p: p.astype(param_dtype),
                              state["params"])
        p, mm, vv, loss, _, norm = step(
            params, state["opt"]["m"], state["opt"]["v"],
            state["step"].astype(jnp.float32) + 1, batch)
        p = jax.tree.map(lambda a, b: a.astype(b.dtype), p, state["params"])
        return ({"params": p, "opt": {"m": mm, "v": vv},
                 "step": state["step"] + 1}, {"loss": loss, "grad_norm": norm})

    return control


@contextmanager
def _zeroed_halos():
    import repro.parallel.halo as halo
    saved = halo._exchange

    def exchange(x, lo, hi, axis):
        return tuple(jnp.zeros_like(r) for r in saved(x, lo, hi, axis))

    halo._exchange = exchange
    try:
        yield
    finally:
        halo._exchange = saved


@contextmanager
def planted(fault: str, cell: Cell):
    """Break the program's timed path: the step that
    ``launch.train.build_trainer`` jits, or for ``halo`` the exchange of its
    spatially sharded convolutions."""
    if fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}; known: {FAULTS}")
    if fault == "halo":
        if not shards_space(cell):
            raise ValueError(f"cell {cell.name!r} does not shard the spatial "
                             f"axis ({cell.traffic['strategy']!r}): it has "
                             f"no halo to break")
        with _zeroed_halos():
            yield
        return
    import repro.launch.train as lt
    saved = lt.make_train_step

    def make(model, opt, ctx, **kw):
        step = saved(model, opt, ctx, **kw)

        def unchanged(state, batch):
            return state, step(state, batch)[1]

        def half_batch(state, batch):
            return step(state, jax.tree.map(
                lambda x: x[: x.shape[0] // 2], batch))

        def answer(state, batch):
            # the new parameters, 1% off where the step produces them
            s, m = step(state, batch)
            return dict(s, params=jax.tree.map(lambda p: p * 1.01,
                                               s["params"])), m

        faults = {"unchanged": unchanged, "half_batch": half_batch,
                  "answer": answer}
        if fault == "control":
            return reference_in_place(cell, jnp.float32)
        if fault == "control_params":
            return reference_in_place(cell, jnp.bfloat16)
        return faults.get(fault, step)

    lt.make_train_step = make
    try:
        yield
    finally:
        lt.make_train_step = saved


def drive(cell: Cell, fault: str = "sound") -> dict:
    """One run of ``cell`` in this process, on its first ``chips`` devices,
    with ``fault`` planted: its ``correct``, the numbers compared and the
    count of devices."""
    devices = jax.devices()
    if len(devices) < cell.chips:
        raise RuntimeError(f"cell {cell.name!r} needs {cell.chips} devices; "
                           f"this process sees {len(devices)}")
    with planted(fault, cell):
        r = train.drive(cell, devices[:cell.chips], seed=SEED, seconds=0.2,
                        trace=False, peak={cell.config["peak"]: 1e12},
                        t_process=time.perf_counter(), log=lambda s: None)
    return {"correct": r["correct"], "checks": r["checks"],
            "devices": r["device"]["count"]}


def in_child(cell: Cell, faults) -> dict:
    """``drive`` of each fault, in one child process on ``cell.chips`` host
    devices of the CPU, which has ``CHILD_TIMEOUT`` seconds for them all."""
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"{flags} --xla_force_host_platform_device_count="
                         f"{cell.chips}".strip(),
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT), str(ROOT / "src"),
                    os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep))
    out = subprocess.run(
        [sys.executable, "-m", "bench.tests.bench_faults", "-", *faults],
        input=json.dumps(dataclasses.asdict(cell)), cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=CHILD_TIMEOUT)
    if out.returncode != 0:
        raise RuntimeError(f"the child running {cell.name!r} exited with "
                           f"{out.returncode}:\n{out.stderr[-4000:]}")
    results = {}
    for line in out.stdout.splitlines():
        if line.startswith("{"):
            r = json.loads(line)
            results[r.pop("fault")] = r
    return results


def run(cell: Cell, faults) -> dict:
    """fault -> ``drive``'s result, in this process where it sees as many
    devices as the cell has chips, else in one child."""
    if len(jax.devices()) >= cell.chips:
        return {f: drive(cell, f) for f in faults}
    return in_child(cell, faults)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("workload", help="a workload of BENCHMARK.json, shrunk "
                    "here; or - for a shrunk cell as JSON on standard input, "
                    "run in this process")
    ap.add_argument("faults", nargs="+", choices=FAULTS)
    args = ap.parse_args(argv)
    if args.workload != "-":
        results = run(tiny(args.workload), args.faults)
        for f, r in results.items():
            print(json.dumps({"fault": f, **r}), flush=True)
        return
    cell = Cell(**json.load(sys.stdin))
    for f in args.faults:
        print(json.dumps({"fault": f, **drive(cell, f)}),
              flush=True)


if __name__ == "__main__":
    main()
