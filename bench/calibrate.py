"""Readings that the limits of ``correct`` are set from, on the chip.

    python bench/calibrate.py --workload resnet50.b128 --seeds 1-12 \
        --control-seeds 1-3

For each seed it builds the cell as a run does and takes the program's three
checked steps and the plain reference's, and prints the numbers that
``correct`` compares (the lower reading is their largest over the seeds).
For the control seeds it also reads, against the same reference:

- ``control``: the reference in the program's place with its activations
  held in bfloat16, the precision below the configuration's float32, and
  its parameters and Adam's moments in float32: what a change that moves
  the activations to bfloat16 would do;
- ``control_params``: the same with the parameters held in bfloat16 too,
  as a model built with ``dtype`` bfloat16 holds them;
- ``half_batch``: the reference in the program's place, its loss and
  gradient taken over the first half of each batch.

All of them, and the reference they are read against, compute their
products at the configuration's matmul precision.

A step that returns its state unchanged reads 1 on ``delta_gap`` by
construction and needs no run. The benchmark's own runs never call this.
One line of JSON per reading goes to standard output.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def seeds(spec: str) -> list[int]:
    out = []
    for part in spec.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b or a) + 1))
    return out


def read_seed(cell, devices, seed: int, controls: bool,
              built: dict | None = None) -> list[dict]:
    """The readings of one seed. ``built`` keeps the trainer that the first
    seed built, so later seeds reuse its compiled step."""
    import jax
    import jax.numpy as jnp
    from bench import train
    cfg, tr = cell.config, cell.traffic
    m, opt = cfg["model"], cfg["optimizer"]
    ref = importlib.import_module(f"bench.reference.{cfg['family']}")
    built = {} if built is None else built
    with tempfile.TemporaryDirectory() as tmp:
        pre = train.prepare(cell, devices, seed, tmp, ref,
                            built.get("trainer"))
        built["trainer"] = (pre.trainer, pre.arch)
        mesh, prog = pre.trainer.mesh, pre.prog
        batches = pre.loader.batches[:train.CHECK_STEPS]
        del pre
    key = train.seed_key(seed, 0)

    def run(b=batches, **kw):
        return train.reference_steps(ref, m, opt, tr["lr"],
                                     cfg["matmul_precision"], key, b, mesh,
                                     **kw)

    want = run()
    out = [{"seed": seed, "what": "program", **train.readings(prog, want)}]
    if controls:
        half = [jax.tree.map(lambda x: x[: x.shape[0] // 2], b)
                for b in batches]
        for what, kw in (
                ("control", {"dtype": jnp.bfloat16}),
                ("control_params", {"dtype": jnp.bfloat16,
                                    "param_dtype": jnp.bfloat16}),
                ("half_batch", {"b": half})):
            out.append({"seed": seed, "what": what,
                        **train.readings(run(**kw), want)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="1-3")
    args = ap.parse_args(argv)
    from bench.cell import resolve
    from bench.run import require_chips, use_cache
    cell = resolve(args.workload)
    devices = require_chips(cell.chips)
    use_cache()
    ctl = set(seeds(args.control_seeds))
    built: dict = {}
    for s in sorted(set(seeds(args.seeds)) | ctl):
        t0 = time.perf_counter()
        for r in read_seed(cell, devices, s, s in ctl, built):
            r["seconds"] = time.perf_counter() - t0
            print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
