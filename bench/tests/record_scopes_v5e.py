"""Record the trace fixture of ``test_bench_scopes.py`` on one TPU v5e:

    python3 bench/tests/record_scopes_v5e.py [--out DIR]

writes the trace, ``resnet_narrow_v5e.xplane.pb.gz``, and the traced step's
compiled HLO, ``resnet_narrow_v5e.hlo.txt.gz``, to ``DIR``
(``bench/tests/data``). Three steps of a narrow ResNet (width 8, one
bottleneck a stage, 32x32 images, batch 8, AdamW) go through
``runtime.fault_tolerance.run_with_recovery``, with the benchmark's
``bench.*`` spans inside the loop's ``train.*`` spans as ``bench/train.py``
opens them. The step is compiled and run before the trace starts, and
neither Python calls nor HLO protos are traced; both files are gzipped, so
that together they stay under 1 MB.
"""
from __future__ import annotations

import argparse
import gzip
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

NAME = "resnet_narrow_v5e"
STEPS = 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=str(ROOT / "bench" / "tests" / "data"))
    args = ap.parse_args(argv)

    from bench import trace
    from repro.checkpoint.checkpointing import Checkpointer
    from repro.models.cnn import ResNet, ResNetConfig
    from repro.nn.module import NULL_CTX, tree_init
    from repro.optim.optimizers import OptimizerConfig
    from repro.runtime.fault_tolerance import run_with_recovery
    from repro.training.steps import make_train_step, train_state_spec

    model = ResNet(ResNetConfig("narrow", (1, 1, 1, 1), n_classes=10,
                                width=8))
    opt = OptimizerConfig(lr=1e-3)
    step = jax.jit(make_train_step(model, opt, NULL_CTX), donate_argnums=(0,))
    state = tree_init(train_state_spec(model, opt), jax.random.PRNGKey(0))
    keys = jax.random.split(jax.random.PRNGKey(1), STEPS)
    batches = [{"images": jax.random.normal(k, (8, 32, 32, 3)),
                "labels": jax.random.randint(k, (8,), 0, 10)} for k in keys]
    hlo = step.lower(state, batches[0]).compile().as_text()
    for b in batches:                       # compile and warm
        state, out = step(state, b)
    float(out["loss"])

    class Loader:
        def batch_at(self, i):
            with TraceAnnotation("bench.batch"):
                return batches[i % STEPS]

    def step_fn(s, b):
        with TraceAnnotation("bench.step"):
            return step(s, b)

    def on_metrics(i, m):
        with TraceAnnotation("bench.readback"):
            float(m["loss"])

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    out_dir = Path(args.out)
    with tempfile.TemporaryDirectory() as tmp:
        ckpt = Checkpointer(f"{tmp}/ckpt")
        with jax.profiler.trace(f"{tmp}/trace", profiler_options=opts):
            run_with_recovery(step_fn, state, Loader(), ckpt, n_steps=STEPS,
                              ckpt_every=10 ** 9, on_metrics=on_metrics)
        out_dir.mkdir(parents=True, exist_ok=True)
        pb = out_dir / f"{NAME}.xplane.pb.gz"
        with open(trace.find_xplane(f"{tmp}/trace"), "rb") as src, \
                gzip.open(pb, "wb") as dst:
            shutil.copyfileobj(src, dst)
    with gzip.open(out_dir / f"{NAME}.hlo.txt.gz", "wt") as f:
        f.write(hlo)
    print(f"{pb}: {pb.stat().st_size} bytes; HLO "
          f"{(out_dir / f'{NAME}.hlo.txt.gz').stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
