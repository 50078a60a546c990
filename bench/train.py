"""One training cell: set-up, the timed window, the trace and the check.

The run goes through the program's own pieces: ``launch.train.build_trainer``
assembles the jitted step and its state, and ``runtime.fault_tolerance.
run_with_recovery`` drives the window with ``Trainer.step``, as
``launch.train.main`` does. What the benchmark adds around them:

- weights and ``resident_batches`` distinct batches, made on the device
  from the seed, each in one jitted call;
- steps 0 to 2 through the same step and feed, whose loss, first gradient
  (read from Adam's first moment after one step) and parameter change are
  kept for the check;
- a few more warm steps that size the window to ``--seconds``;
- the window: from the first timed step's dispatch to the last step's
  completion; the loop's closing save falls after it;
- with tracing on, a few more steps under the profiler, reduced over the
  whole steps that the runs of the compiled step bound (``bench/trace.py``)
  and read with the program's scopes and spans (``bench/scopes.py``);
- the compiled step's memory as the compiler reports it;
- once the program's state is freed, the plain reference over steps 0 to 2
  at the configuration's matmul precision, and the comparison that decides
  ``correct``.

Beside the result, standard error gets the window's steps and stalls (any
step over ``STALL`` times the median), the garbage collector's pauses and
the process's CPU time in the window, and the device's memory statistics,
so that a slow run shows where its time went.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import math
import resource
import tempfile
import time
from functools import partial
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from . import scopes
from . import trace as trace_mod
from .cell import Cell, metric_reader

CHECK_STEPS = 3          # steps the reference follows
STALL = 2.0              # a window step this many times the median stalled


class ResidentLoader:
    """The resident batches, handed to the loop by step index."""

    def __init__(self, batches: list[dict]):
        self.batches = batches
        self.first_dispatch: dict[int, float] = {}

    def batch_at(self, step: int) -> dict:
        with TraceAnnotation("bench.batch"):
            self.first_dispatch.setdefault(step, time.perf_counter())
            return self.batches[step % len(self.batches)]


def seed_key(seed: int, stream: int) -> jax.Array:
    """A key from every bit of a seed of up to 64 bits, one per stream."""
    seed %= 2 ** 64
    k = jax.random.fold_in(jax.random.key(stream), seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, seed >> 32)


def register_config(name: str, config: dict) -> str:
    """The registered arch, or a copy of it under ``name`` with the
    configuration's overrides of the model's sizes."""
    from repro.configs.base import get_config, register
    base = get_config(config["arch"])
    over = config.get("overrides") or {}
    if not over:
        return config["arch"]
    model = dataclasses.replace(base.model, **over)
    register(name)(lambda: dataclasses.replace(base, name=name, model=model))
    return name


def build_trainer(cell: Cell, devices, seed: int, ckpt_dir: str):
    from repro.launch.compat import make_mesh
    from repro.launch.train import build_trainer as program_build, parse_args
    tr = cell.traffic
    arch = register_config(cell.config_name, cell.config)
    mesh = make_mesh(tuple(tr["mesh"].values()), tuple(tr["mesh"]),
                     devices=devices)
    args = parse_args([
        "--arch", arch, "--batch", str(tr["global_batch"]),
        "--strategy", tr["strategy"], "--lr", repr(tr["lr"]),
        "--ckpt-dir", ckpt_dir, "--ckpt-every", str(10 ** 9),
        "--seed", str(seed % 2 ** 31)])
    return program_build(args, mesh=mesh), arch


def batch_shardings(t, arch: str, batch: int) -> dict:
    """The shardings the program gives a batch of the cell: those of
    ``launch.build.cnn_batch_specs`` under the trainer's rules."""
    from repro.configs import get_config
    from repro.launch.build import cnn_batch_specs
    specs = cnn_batch_specs(get_config(arch), batch, t.mesh, t.ctx.rules)
    return {k: v.sharding for k, v in specs.items()}


def make_resident(ref, m: dict, tr: dict, shardings: dict, key) -> list[dict]:
    """The cell's distinct batches, made on the device in one call."""
    make = partial(ref.make_batches, m=m, batch=tr["global_batch"],
                   count=tr["resident_batches"])
    return jax.jit(make, out_shardings=[shardings] * tr["resident_batches"])(key)


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def leaf_delta_norms(a, b):
    return leaf_norms(jax.tree.map(lambda x, y: x - y, a, b))


@jax.jit
def tree_copy(tree):
    return jax.tree.map(jnp.copy, tree)


def leaf_gaps(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per leaf: the gap between the program's and the reference's norm,
    over the reference's norm of that leaf or of the median leaf, whichever
    is larger."""
    return np.abs(prog - ref) / np.maximum(ref, np.median(ref))


def readings(prog: dict, ref: dict) -> dict:
    """Every number the check can compare, from the program's (or a
    control's) checked steps and the reference's. ``BENCHMARK``'s limits
    file of a cell names the ones compared there.

    - ``loss_gap``: the largest relative gap of the three steps' losses;
    - ``loss0_gap``: the relative gap of step 0's loss, the forward pass
      alone, before any update;
    - ``grad_norm_gap``: relative gap of step 0's gradient norm before
      clipping;
    - ``grad_gap`` / ``grad_median_gap``: the worst and the median leaf of
      the first gradient as the optimizer got it (after clipping);
    - ``delta_gap``: the worst leaf of the parameters' change over the three
      steps, leaving out leaves whose reference gradient is under a
      thousandth of the median leaf's (they move by round-off alone).
    """
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    keep = ref["grad"] >= 1e-3 * np.median(ref["grad"])
    grad = leaf_gaps(prog["grad"], ref["grad"])
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "loss0_gap": float(abs(lp[0] - lr[0]) / abs(lr[0])),
        "grad_norm_gap": float(abs(prog["grad_norm"] - ref["grad_norm"])
                               / ref["grad_norm"]),
        "grad_gap": float(np.max(grad)),
        "grad_median_gap": float(np.median(grad)),
        "delta_gap": float(np.max(leaf_gaps(prog["delta"][keep],
                                            ref["delta"][keep]))),
    }


_REFERENCE_FNS: dict = {}


def reference_fns(ref, m: dict, opt: dict, lr: float, precision: str,
                  dtype, param_dtype, sharding):
    """The jitted init and step of the reference, made once per process
    and set of sizes, so a process that checks many seeds compiles once."""
    key = (ref.__name__, repr(sorted(m.items())), repr(sorted(opt.items())),
           lr, precision, jnp.dtype(dtype).name, jnp.dtype(param_dtype).name,
           sharding)
    if key not in _REFERENCE_FNS:
        _REFERENCE_FNS[key] = (
            jax.jit(lambda k: jax.tree.map(lambda x: x.astype(param_dtype),
                                           ref.init_params(k, m)),
                    out_shardings=sharding),
            jax.jit(partial(ref.train_step, m=m, opt=opt, lr=lr, dtype=dtype,
                            precision=precision)))
    return _REFERENCE_FNS[key]


def reference_steps(ref, m: dict, opt: dict, lr: float, precision: str, key,
                    batches, mesh, dtype=jnp.float32,
                    param_dtype=jnp.float32) -> dict:
    """The plain reference over the checked steps, from the same seed and
    batches, its products at ``precision`` (the configuration's). With
    ``dtype`` bfloat16 it is a control: activations held in bfloat16, and
    with ``param_dtype`` bfloat16 the parameters too; Adam's moments stay
    float32."""
    init, step = reference_fns(ref, m, opt, lr, precision, dtype, param_dtype,
                               NamedSharding(mesh, P()))
    params = init(key)
    p0 = tree_copy(params)
    mom = vel = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                             params)
    out = {"losses": []}
    for i in range(CHECK_STEPS):
        params, mom, vel, loss, g, norm = step(
            params, mom, vel, jnp.float32(i + 1), batches[i])
        out["losses"].append(float(loss))
        if i == 0:
            out["grad"] = np.asarray(leaf_norms(g))
            out["grad_norm"] = float(norm)
        del g
    out["delta"] = np.asarray(leaf_delta_norms(params, p0))
    return out


def fresh_optimizer_state(t) -> dict:
    """Zero moments and step 0, placed as the trainer places them."""
    from repro.nn.module import tree_abstract
    from repro.training.steps import train_state_spec
    spec = tree_abstract(train_state_spec(t.model, t.opt))
    parts = {"opt": spec["opt"], "step": spec["step"]}
    return jax.jit(lambda: jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype), parts),
        out_shardings={k: t.shardings[k] for k in parts})()


@dataclasses.dataclass
class Prepared:
    """A built trainer after the checked steps, ready for the window."""

    trainer: object
    arch: str
    state: dict
    loader: ResidentLoader
    step_fn: object
    dispatch_s: list        # host seconds of each call into the step
    prog: dict              # losses, first-gradient and change norms


def prepare(cell: Cell, devices, seed: int, tmp: str, ref,
            built=None) -> Prepared:
    """Build the program's trainer with the benchmark's weights and batches,
    and run the checked steps through the window's own step and feed.
    ``built``: a (trainer, arch) that an earlier seed built, whose step is
    reused from a fresh state, as its builder makes it (zero moments, step
    0), so that one process checks many seeds with one compile."""
    tr, m, opt = cell.traffic, cell.config["model"], cell.config["optimizer"]
    t, arch = built or build_trainer(cell, devices, seed, tmp)
    # the benchmark's weights replace the program's own, so that the
    # reference can start from the same ones
    params = jax.jit(partial(ref.init_params, m=m),
                     out_shardings=t.shardings["params"])(seed_key(seed, 0))
    fresh = fresh_optimizer_state(t) if t.state is None else t.state
    state = {"params": params, "opt": fresh["opt"], "step": fresh["step"]}
    t.state = None
    batches = make_resident(ref, m, tr,
                            batch_shardings(t, arch, tr["global_batch"]),
                            seed_key(seed, 1))
    if len(batches) < CHECK_STEPS:
        raise ValueError("the checked steps need distinct batches: "
                         f"resident_batches >= {CHECK_STEPS}")
    loader = ResidentLoader(batches)

    dispatch_s = []

    def step_fn(state, b):
        with TraceAnnotation("bench.step"):
            a = time.perf_counter()
            out = t.step(state, b)
            dispatch_s.append(time.perf_counter() - a)
            return out

    p0 = tree_copy(params)
    del params
    prog = {"losses": []}
    for i in range(CHECK_STEPS):
        state, out = step_fn(state, loader.batch_at(i))
        prog["losses"].append(float(out["loss"]))
        if i == 0:
            prog["grad_norm"] = float(out["grad_norm"])
            # Adam's first moment after one step is (1 - b1) times the
            # gradient the optimizer got
            prog["grad"] = np.asarray(
                leaf_norms(state["opt"]["m"])) / (1 - opt["b1"])
    prog["delta"] = np.asarray(leaf_delta_norms(state["params"], p0))
    return Prepared(t, arch, state, loader, step_fn, dispatch_s, prog)


def window_report(marks: np.ndarray, dispatch_s: list, gc_s: list,
                  usage: tuple) -> str:
    """One line on where the window's time went: the steps' median and
    longest, every stall (a step over ``STALL`` times the median, with the
    host's share of it spent dispatching), the collector's pauses and the
    process's CPU time."""
    steps = np.diff(marks)
    med = float(np.median(steps))
    slow = np.flatnonzero(steps > STALL * med)
    stalls = ", ".join(f"step {i}: {1e3 * steps[i]:.1f} ms (dispatch "
                       f"{1e3 * dispatch_s[i]:.1f})" for i in slow[:8])
    u0, u1 = usage
    return (f"window steps: median {1e3 * med:.2f} ms, longest "
            f"{1e3 * steps.max():.1f} ms; {len(slow)} stalls over {STALL:g}x "
            f"the median, {float(np.sum(steps[slow] - med)):.3f} s beyond it"
            f"{': ' + stalls if stalls else ''}; gc {len(gc_s)} pauses, "
            f"{sum(gc_s):.3f} s; process CPU "
            f"{u1.ru_utime + u1.ru_stime - u0.ru_utime - u0.ru_stime:.2f} s")


def drive(cell: Cell, devices, *, seed: int, seconds: float, trace: bool,
          peak: dict, t_process: float, log=print) -> dict:
    """Run one cell on ``devices``; returns the result line as a dict."""
    tr, cfg = cell.traffic, cell.config
    m, opt = cfg["model"], cfg["optimizer"]
    ref = importlib.import_module(f"bench.reference.{cfg['family']}")
    flops = importlib.import_module(f"bench.flops.{cfg['family']}")
    batch = tr["global_batch"]
    with tempfile.TemporaryDirectory() as tmp:
        pre = prepare(cell, devices, seed, tmp, ref)
        log(f"set-up: built and checked steps done at "
            f"{time.perf_counter() - t_process:.1f} s")
        t, arch, state, loader, step_fn = (pre.trainer, pre.arch, pre.state,
                                           pre.loader, pre.step_fn)
        prog, mesh, batches = pre.prog, t.mesh, pre.loader.batches
        dispatch_s = pre.dispatch_s
        del pre
        # warm steps that size the window
        dts = []
        w0 = CHECK_STEPS
        for i in range(w0, w0 + tr["warm_steps"]):
            a = time.perf_counter()
            state, out = step_fn(state, loader.batch_at(i))
            float(out["loss"])
            dts.append(time.perf_counter() - a)
        start = w0 + tr["warm_steps"]
        n = max(1, round(seconds / min(dts)))
        n_traced = tr["trace_steps"] if trace else 0
        trace_dir = str(Path(tmp) / "trace")
        done, losses, gc_s, gc_at = [], [], [], [0.0]
        del dispatch_s[:]

        def on_metrics(s, out):
            with TraceAnnotation("bench.readback"):
                losses.append(float(out["loss"]))
                done.append(time.perf_counter())
                if trace and s == start + n - 1:
                    jax.profiler.start_trace(trace_dir)

        def on_gc(phase, info):
            if phase == "start":
                gc_at[0] = time.perf_counter()
            else:
                gc_s.append(time.perf_counter() - gc_at[0])

        from repro.runtime.fault_tolerance import run_with_recovery
        gc.callbacks.append(on_gc)
        usage0 = resource.getrusage(resource.RUSAGE_SELF)
        try:
            state, _ = run_with_recovery(
                step_fn, state, loader, t.ckpt, n_steps=start + n + n_traced,
                start_step=start, ckpt_every=10 ** 9, on_metrics=on_metrics)
        finally:
            gc.callbacks.remove(on_gc)
        usage1 = resource.getrusage(resource.RUSAGE_SELF)
        if trace:
            jax.profiler.stop_trace()
        t_start = loader.first_dispatch[start]
        setup_s = t_start - t_process
        window = done[n - 1] - t_start
        marks = np.array([t_start] + done[:n])
        # the TPU's allocator keeps a compiled program's temporaries in a
        # region it reserves apart from the buffers it counts as in use
        mem = [d.memory_stats() or {} for d in devices]
        mem_peak = max(s.get("peak_bytes_in_use", 0)
                       + s.get("peak_bytes_reserved", 0) for s in mem)
        # the compiled step, from the compilation cache: its memory as the
        # compiler plans it, and its HLO, which names the trace's ops
        compiled = t.step.lower(state, loader.batches[0]).compile()
        ma = compiled.memory_analysis()
        mem_compiled = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                        + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        hlo = compiled.as_text() if trace else None
        del state, t, loader, compiled
        batches = batches[:CHECK_STEPS]

        step_flops = flops.train_flops_per_sample(m) * batch
        samples_per_s = n * batch / window
        end_to_end = {
            "samples_per_s": samples_per_s,
            "mfu": 100 * step_flops / batch * samples_per_s
            / (cell.chips * peak[cfg["peak"]]),
            "setup_s": setup_s,
        }
        window_losses = losses[:n]
        log(f"window: {n} steps in {window:.4f} s; loss "
            f"{window_losses[0]:.4f} -> {window_losses[-1]:.4f}")
        log(window_report(marks, dispatch_s, gc_s, (usage0, usage1)))
        log(f"device memory: compiled step {mem_compiled} bytes (arguments "
            f"{ma.argument_size_in_bytes}, outputs {ma.output_size_in_bytes}, "
            f"aliased {ma.alias_size_in_bytes}, temporaries "
            f"{ma.temp_size_in_bytes}); memory_stats of the fullest chip "
            f"{max(mem, key=lambda s: s.get('peak_bytes_in_use', 0))}")

        result = {"correct": False, "attempted": n,
                  "failed": int(sum(not math.isfinite(x)
                                    for x in window_losses))}
        if trace:
            lay = scopes.reduce(trace_mod.find_xplane(trace_dir), hlo,
                                n_devices=cell.chips)
            red = lay.red
            log(f"traced window: {red.steps} steps in {red.window_s:.6f} s, "
                f"device busy {red.busy_s:.6f} s")
            ctx = SimpleNamespace(
                cell=cell, arch=arch, trace=red, layers=lay, peak=peak,
                flops=flops, model=m, batch=batch, chips=cell.chips,
                steps=red.steps, step_flops=step_flops,
                mean_step_s=window / n, itemsize=2)
            metrics = {}
            for spec in cell.per_layer:
                v = metric_reader(spec["name"])(ctx)
                if v is not None:
                    metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
            result["breakdown"] = lay.breakdown()
            device_extra = {"busy_s": red.busy_s, "window_s": red.window_s,
                            "window_steps": red.steps}
        else:
            metrics = {spec["name"]: {"value": end_to_end[spec["name"]],
                                      "unit": spec["unit"]}
                       for spec in cell.end_to_end}
            device_extra = {}

        # the check: the reference follows the three checked steps
        t_ref = time.perf_counter()
        ref_out = reference_steps(ref, m, opt, tr["lr"],
                                  cfg["matmul_precision"], seed_key(seed, 0),
                                  batches, mesh)
        log(f"reference: {time.perf_counter() - t_ref:.1f} s")
    numbers = readings(prog, ref_out)
    checks = {k: {"value": numbers[k], "limit": limit}
              for k, limit in cell.limits.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values()) and result["failed"] == 0
    d0 = devices[0]
    result.update({
        "correct": correct, "metrics": metrics,
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devices), "memory_peak_bytes": int(mem_peak),
                   "memory_compiled_bytes": int(mem_compiled),
                   **device_extra},
        "checks": checks})
    return result
