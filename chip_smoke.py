"""Proof that the training path runs on a TPU v5e: ResNet-50 at its published
widths, trained through ``repro.launch.train``'s own pieces.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the (2, 2) mesh of a four-chip host

One chip: five steps at batch 64 under ``--strategy data`` (any restart is a
failure), step 0's loss against the same loss at highest matmul precision,
and one step with the Pallas conv kernel against the XLA-conv step.

Four chips: one step under ``ds`` (spatial halo exchange) and one under
``df`` (filter split) at global batch 64, each against the same step on one
of the chips under ``data``.

Everything runs in this one process (a chip belongs to one process). With no
TPU, or outside the repo, it exits non-zero and prints no result. The last
line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import math
import sys
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

SRC = Path(__file__).resolve().parent / "src"
sys.path.insert(0, str(SRC))

ARCH = "resnet50"
BATCH = 64            # per chip on one chip; global on four
STEPS = 5

# Step 0's loss vs the same loss at highest precision, and the Pallas step vs
# the XLA-conv step. A float32 conv on the TPU at default precision takes
# bf16 passes (8-bit mantissa, ~4e-3 relative per rounding), compounded over
# ResNet-50's 53 conv and dense layers; a Pallas matmul may round differently
# from XLA's. A wrong layer moves the loss by far more than this.
LOSS_RTOL = 2e-2
GRAD_NORM_RTOL = 5e-2
# ds / df vs one chip, both at highest precision: only the order of float32
# sums differs between the partitioned programs (BatchNorm's batch mean is
# global under GSPMD) — the bound the CPU check of df already holds.
SHARDED_RTOL = 5e-4

FAILURES: list[str] = []


def check(ok: bool, what: str) -> None:
    print(f"{'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        FAILURES.append(what)


def close(a: float, b: float, rtol: float) -> bool:
    return math.isfinite(a) and math.isfinite(b) and abs(a - b) <= rtol * abs(b)


def device_report(chips: int) -> dict:
    """Print what JAX sees; exit non-zero unless it is ``chips`` TPUs."""
    devs = jax.devices()
    d = devs[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)} jax={jax.__version__} libtpu={libtpu}",
          flush=True)
    if d.platform != "tpu":
        sys.exit(f"no TPU found: JAX's devices are {d.platform} "
                 f"({d.device_kind}); this smoke runs only on a TPU")
    if len(devs) != chips:
        sys.exit(f"expected {chips} TPU chip(s), JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def trainer(strategy: str, ckpt_dir: str, *, smoke: bool = False,
            batch: int = BATCH, steps: int = STEPS, mesh=None):
    """The Trainer ``repro.launch.train.main`` builds for these flags.
    ``ckpt_every`` is above the step count and the directory is fresh, so
    the run neither resumes nor saves mid-run."""
    from repro.launch.train import build_trainer, parse_args
    argv = ["--arch", ARCH, "--strategy", strategy, "--batch", str(batch),
            "--steps", str(steps), "--ckpt-dir", ckpt_dir,
            "--ckpt-every", str(steps + 1)] + (["--smoke"] if smoke else [])
    return build_trainer(parse_args(argv), mesh=mesh)


def compile_timed(jitted, *args, label: str):
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    print(f"compile {label}: {time.perf_counter() - t0:.1f} s", flush=True)
    return compiled


def step_metrics(compiled, state, batch) -> tuple[float, float]:
    _, m = compiled(state, batch)
    return float(m["loss"]), float(m["grad_norm"])


def one_chip(kind: str, *, smoke: bool = False, batch: int = BATCH,
             steps: int = STEPS) -> None:
    from repro.nn.module import NULL_CTX
    from repro.runtime.fault_tolerance import run_with_recovery
    from repro.training.steps import make_train_step

    with tempfile.TemporaryDirectory() as ckpt_dir:
        t = trainer("data", ckpt_dir, smoke=smoke, batch=batch, steps=steps)
        batch0 = t.loader.batch_at(0)
        print(f"{ARCH}: {t.model.cfg}, batch shapes "
              f"{ {k: v.shape for k, v in batch0.items()} }, strategy data, "
              f"mesh {dict(t.mesh.shape)}", flush=True)

        # the reference: the same loss_fn, parameters and batch, evaluated
        # on the chip at highest matmul precision (before the train step
        # donates the initial state)
        with jax.default_matmul_precision("highest"):
            ref = compile_timed(
                jax.jit(lambda p, b: t.model.loss_fn(p, b, NULL_CTX)[0]),
                t.state["params"], batch0, label="reference loss (highest)")
            ref_loss = float(ref(t.state["params"], batch0))

        # one step with the Pallas conv kernel (default blocks), same
        # parameters and batch; not donated, so the state stays for the run
        ctx_pl = dataclasses.replace(t.ctx, use_pallas=True)
        step_pl = compile_timed(
            jax.jit(make_train_step(t.model, t.opt, ctx_pl)), t.state,
            batch0, label="pallas-conv train step")
        pl_loss, pl_gn = step_metrics(step_pl, t.state, batch0)

        step = compile_timed(t.step, t.state, batch0, label="train step")
        seen = []

        def on_metrics(s, m):
            seen.append((s, float(m["loss"]), float(m["grad_norm"]),
                         time.perf_counter()))
            print(f"step {s} loss {seen[-1][1]:.6f} "
                  f"grad_norm {seen[-1][2]:.6f}", flush=True)

        # max_restarts=0: the first failed step raises instead of being
        # restarted out of sight
        state, final = run_with_recovery(
            step, t.state, t.loader, t.ckpt, n_steps=steps,
            ckpt_every=steps + 1, max_restarts=0, on_metrics=on_metrics)

        check(final == steps and [s for s, *_ in seen] == list(range(steps)),
              f"{steps} steps taken through run_with_recovery, no restart")
        check(all(math.isfinite(v) for _, l, g, _ in seen for v in (l, g)),
              "every step's loss and grad norm are finite")
        loss0, gn0 = seen[0][1], seen[0][2]
        check(close(loss0, ref_loss, LOSS_RTOL),
              f"step 0 loss {loss0:.6f} vs highest-precision {ref_loss:.6f} "
              f"(rel {abs(loss0 - ref_loss) / abs(ref_loss):.2e}, "
              f"tol {LOSS_RTOL})")
        check(close(pl_loss, loss0, LOSS_RTOL),
              f"pallas step loss {pl_loss:.6f} vs xla {loss0:.6f} "
              f"(rel {abs(pl_loss - loss0) / abs(loss0):.2e}, "
              f"tol {LOSS_RTOL})")
        check(close(pl_gn, gn0, GRAD_NORM_RTOL),
              f"pallas step grad norm {pl_gn:.6f} vs xla {gn0:.6f} "
              f"(rel {abs(pl_gn - gn0) / abs(gn0):.2e}, "
              f"tol {GRAD_NORM_RTOL})")
        check("tpu_custom_call" in step_pl.as_text(),
              "pallas step's compiled text holds tpu_custom_call")

        # warm step time on the host clock, for information only
        warm_batch = t.loader.batch_at(steps)
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            state, m = step(state, warm_batch)
            jax.block_until_ready((state, m))
            times.append(time.perf_counter() - t0)
        print(f"warm step: {np.median(times) * 1e3:.1f} ms median of 3 "
              f"({kind}, host clock around block_until_ready; "
              f"information, not a benchmark)", flush=True)
        stats = jax.devices()[0].memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            print(f"peak device memory: "
                  f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB ({kind})")


def four_chips(*, smoke: bool = False, batch: int = BATCH) -> None:
    from repro.launch.mesh import make_host_mesh

    def sharded_step(strategy: str, ckpt_dir: str, mesh=None):
        t = trainer(strategy, ckpt_dir, smoke=smoke, batch=batch, steps=1,
                    mesh=mesh)
        b = t.loader.batch_at(0)
        with jax.default_matmul_precision("highest"):
            compiled = compile_timed(t.step, t.state, b,
                                     label=f"{strategy} train step")
        spans = {len(x.sharding.device_set)
                 for x in jax.tree.leaves((t.state, b))}
        loss, gn = step_metrics(compiled, t.state, b)
        print(f"{strategy}: mesh {dict(t.mesh.shape)} loss {loss:.6f} "
              f"grad_norm {gn:.6f}", flush=True)
        return loss, gn, spans, compiled.as_text()

    n = len(jax.devices())
    with tempfile.TemporaryDirectory() as ckpt_dir:
        ref_loss, ref_gn, spans, _ = sharded_step(
            "data", ckpt_dir, mesh=make_host_mesh(n_devices=1))
        check(spans == {1}, "reference step on one chip")
        for strategy in ("ds", "df"):
            loss, gn, spans, text = sharded_step(strategy, ckpt_dir)
            check(spans == {n},
                  f"{strategy}: parameters and batch span all {n} devices "
                  f"(device sets seen: {sorted(spans)})")
            if strategy == "ds":
                check("collective-permute" in text,
                      "ds step's compiled text holds collective-permute "
                      "(the halo exchange)")
            check(close(loss, ref_loss, SHARDED_RTOL),
                  f"{strategy} loss {loss:.6f} vs one chip {ref_loss:.6f} "
                  f"(rel {abs(loss - ref_loss) / abs(ref_loss):.2e}, "
                  f"tol {SHARDED_RTOL})")
            check(close(gn, ref_gn, SHARDED_RTOL),
                  f"{strategy} grad norm {gn:.6f} vs one chip {ref_gn:.6f} "
                  f"(rel {abs(gn - ref_gn) / abs(ref_gn):.2e}, "
                  f"tol {SHARDED_RTOL})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    device = device_report(args.chips)
    if not (SRC / "repro").is_dir():
        sys.exit(f"no repro package under {SRC}: run this from a checkout "
                 f"of the repository")
    from repro.launch.cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    if args.chips == 1:
        one_chip(device["kind"])
    else:
        four_chips()
    if FAILURES:
        print(f"{len(FAILURES)} check(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
