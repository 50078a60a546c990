"""Compile a cell's train step for a described TPU v5e and print its memory.

    JAX_PLATFORMS=cpu python bench/fit.py --workload resnet50.b128
    JAX_PLATFORMS=cpu python bench/fit.py --workload resnet50.b128 \
        --batch 256

No chip is used: the TPU compiler compiles for a v5e that is described and
not attached, and refuses a program that does not fit its memory. The step
is assembled from the same pieces as ``launch.train.build_trainer``, handed
the described devices and abstract shapes. ``--img``, ``--batch``,
``--mesh`` and ``--strategy`` override the cell's sizes to try others. Run
by hand: a whole-step compile takes tens of seconds to minutes.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def compile_step(cell, devices, *, img=None, batch=None, mesh_shape=None,
                 strategy=None):
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.launch.build import build_model, cnn_batch_specs
    from repro.nn.module import ShardingCtx, tree_abstract
    from repro.optim.optimizers import OptimizerConfig
    from repro.parallel.strategies import make_rules
    from repro.runtime.elastic import state_shardings
    from repro.training.steps import make_train_step, train_state_spec

    from bench.train import register_config
    tr = dict(cell.traffic)
    config = dict(cell.config, overrides=dict(cell.config.get("overrides")
                                              or {}))
    if img is not None:
        config["overrides"]["img"] = img
    name = register_config(f"{cell.config_name}_fit", config) \
        if config["overrides"] else config["arch"]
    cfg = get_config(name)
    shape = mesh_shape or tuple(tr["mesh"].values())
    n = int(np.prod(shape))
    mesh = Mesh(np.array(devices[:n]).reshape(shape), tuple(tr["mesh"]))
    strategy = strategy or tr["strategy"]
    batch = batch or tr["global_batch"]
    model = build_model(cfg)
    opt = OptimizerConfig(lr=tr["lr"], zero1=strategy != "pipeline")
    rules = make_rules(strategy)
    step = make_train_step(model, opt, ShardingCtx(mesh, rules))
    shard = state_shardings(model, opt, mesh, rules)
    abstract = tree_abstract(train_state_spec(model, opt))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, shard)
    batch_spec = cnn_batch_specs(cfg, batch, mesh, rules)
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        state, batch_spec).compile()
    return compiled, time.perf_counter() - t0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--img", type=int)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--mesh", help="data x model, e.g. 1x4")
    ap.add_argument("--strategy")
    ap.add_argument("--hlo", help="write the compiled HLO text here")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from bench.cell import resolve
    jax.config.update("jax_enable_compilation_cache", False)
    cell = resolve(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = tuple(int(x) for x in args.mesh.split("x")) if args.mesh else None
    what = (f"{args.workload} img={args.img} batch={args.batch} "
            f"mesh={mesh} strategy={args.strategy}")
    try:
        compiled, secs = compile_step(cell, topo.devices, img=args.img,
                                      batch=args.batch, mesh_shape=mesh,
                                      strategy=args.strategy)
    except Exception as e:  # noqa: BLE001 — the compiler's refusal is the report
        print(f"REFUSED {what}: {type(e).__name__}: {str(e)[:2000]}")
        return 1
    ma = compiled.memory_analysis()
    gib = 2 ** 30
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(f"FITS {what}: compile {secs:.1f} s; per device: arguments "
          f"{ma.argument_size_in_bytes / gib:.3f} GiB, outputs "
          f"{ma.output_size_in_bytes / gib:.3f} GiB, aliased "
          f"{ma.alias_size_in_bytes / gib:.3f} GiB, temporaries "
          f"{ma.temp_size_in_bytes / gib:.3f} GiB, total {total / gib:.3f} GiB")
    if args.hlo:
        Path(args.hlo).write_text(compiled.as_text())
    return 0


if __name__ == "__main__":
    sys.exit(main())
