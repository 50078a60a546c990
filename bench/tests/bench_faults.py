"""Drive a cell at a size the CPU can hold, with the timed path broken.

    python -m bench.tests.bench_faults resnet50.b128 sound control

Used by ``test_bench_faults.py``: it skips the harness's look for a chip,
shrinks the cell's model and batch, keeps its traffic's other parameters
and its limits, plants one fault in the program, and returns the run's
result.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from contextlib import contextmanager
from functools import partial

import jax
import jax.numpy as jnp

from bench import train
from bench.cell import resolve
from bench.reference import cnn as ref

#: model sizes a CPU run holds; widths that the program fixes (ResNet's
#: 2048-wide head) stay as they are
TINY_MODEL = {"stage_sizes": [1, 1, 1, 1], "n_classes": 10, "img": 16}
TINY_OVERRIDES = {"stage_sizes": (1, 1, 1, 1), "n_classes": 10}
TINY_BATCH = 4


def tiny(name: str):
    c = resolve(name)
    config = dict(c.config, model=dict(c.config["model"], **TINY_MODEL),
                  overrides=dict(c.config.get("overrides") or {},
                                 **TINY_OVERRIDES))
    return dataclasses.replace(
        c, config_name=f"{c.config_name}_tiny", config=config,
        traffic=dict(c.traffic, global_batch=TINY_BATCH))


def reference_in_place(cell, param_dtype):
    """The control: the reference in the program's place, its activations
    in bfloat16 and its parameters in ``param_dtype``, on the program's
    state and metrics."""
    cfg = cell.config
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
def planted(fault: str, cell):
    """Break the program's timed path: the step that
    ``launch.train.build_trainer`` jits."""
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


def drive(name: str, fault: str = "sound", seed: int = 2 ** 32 + 11) -> dict:
    c = tiny(name)
    with planted(fault, c):
        return train.drive(c, jax.devices()[:c.chips], seed=seed,
                           seconds=0.2, trace=False,
                           peak={c.config["peak"]: 1e12},
                           t_process=time.perf_counter(), log=lambda s: None)


if __name__ == "__main__":
    for f in sys.argv[2:]:
        r = drive(sys.argv[1], f)
        print(json.dumps({"fault": f, "correct": r["correct"],
                          "checks": r["checks"]}), flush=True)
