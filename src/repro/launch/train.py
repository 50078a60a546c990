"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-32b --smoke \
        --steps 200 --batch 16 --seq 128 --strategy df

Builds the (smoke or full) model, a deterministic sharded loader, the jitted
train step under the chosen strategy's rules, and runs the fault-tolerant
loop (checkpoint/restart, straggler watch). On this CPU box use --smoke; on
a real pod the same driver runs the full configs.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Any

import jax

from ..configs import get_config
from ..checkpoint.checkpointing import Checkpointer, config_hash
from ..data.pipeline import DataConfig, ShardedLoader
from ..models.cnn import CosmoFlowConfig, ResNetConfig, VGGConfig
from ..models.encdec import EncDecConfig
from ..models.transformer import LMConfig
from ..models.vlm import VLMConfig
from ..nn.module import ShardingCtx, tree_init
from ..optim.optimizers import OptimizerConfig
from ..parallel.strategies import make_rules
from ..runtime.elastic import state_shardings
from ..runtime.fault_tolerance import run_with_recovery
from ..training.steps import make_train_step, train_state_spec
from .build import build_model
from .cache import use_compile_cache
from .mesh import make_host_mesh


def data_config_for(mc, batch: int, seq: int, seed: int = 0) -> DataConfig:
    if isinstance(mc, LMConfig):
        return DataConfig("lm", batch, seq_len=seq, vocab=mc.vocab, seed=seed)
    if isinstance(mc, EncDecConfig):
        return DataConfig("encdec", batch, seq_len=min(seq, mc.max_target_positions),
                          vocab=mc.vocab, frames=mc.max_source_positions,
                          d_frames=mc.d_model, seed=seed)
    if isinstance(mc, VLMConfig):
        return DataConfig("vlm", batch, seq_len=seq, vocab=mc.lm.vocab,
                          n_patches=mc.n_patches, d_vision=mc.d_vision,
                          seed=seed)
    if isinstance(mc, (ResNetConfig, VGGConfig)):
        img = getattr(mc, "img", 224)
        return DataConfig("image", batch, image=img, classes=mc.n_classes,
                          seed=seed)
    if isinstance(mc, CosmoFlowConfig):
        return DataConfig("volume", batch, image=mc.img, channels=mc.in_ch,
                          n_targets=mc.n_targets, seed=seed)
    raise TypeError(type(mc))


def _main_elastic(args, cfg, mc, model) -> None:
    """--elastic: the oracle-guided elastic loop (runtime/elastic.py).

    The Oracle session owns the machine description the cluster flags
    build; the controller tunes for the live device count, and on
    SliceLost (slice death, or ``--straggler-patience`` consecutive
    straggler alerts) it degrades the ClusterSpec, re-tunes, reshards the
    checkpoint plan-to-plan, and resumes."""
    from ..api import Oracle
    from ..core.cluster import ClusterSpec
    from ..runtime.elastic import run_elastic
    ses = Oracle(cfg, "train_4k", ClusterSpec.from_cli_args(args),
                 smoke=args.smoke, batch=args.batch, seq=args.seq)
    fwd_kw = {}
    if cfg.family in ("lm", "vlm"):
        fwd_kw = dict(scan_layers=args.scan_layers, attn_impl="chunked",
                      q_chunk=min(256, args.seq))
    opt = OptimizerConfig(lr=args.lr)     # zero1 follows each plan's switch
    ckpt = Checkpointer(f"{args.ckpt_dir}/{args.arch}",
                        config_tag=config_hash((args.arch, args.smoke)))
    dcfg = data_config_for(mc, args.batch, args.seq, args.seed)

    t_start = time.time()
    losses = []

    def on_metrics(s, m):
        losses.append(float(m["loss"]))
        if s % args.log_every == 0:
            print(f"step {s:5d} loss {float(m['loss']):.4f} "
                  f"grad_norm {float(m['grad_norm']):.3f} "
                  f"({(time.time()-t_start):.1f}s)", flush=True)

    state, final, events = run_elastic(
        ses, dcfg, ckpt, n_steps=args.steps, model=model, opt=opt,
        ckpt_every=args.ckpt_every, seed=args.seed, fwd_kw=fwd_kw,
        straggler_patience=args.straggler_patience, on_metrics=on_metrics)
    for ev in events:
        print(f"elastic event @ step {ev.step}: {ev.cause}, "
              f"p {ev.p_before}→{ev.p_after}, re-tuned {ev.strategy} "
              f"(mesh {ev.mesh_shape[0]}x{ev.mesh_shape[1]}), resumed "
              f"from step {ev.resumed_from}")
    if losses:
        print(f"done at step {final}; loss {losses[0]:.4f} → "
              f"{losses[-1]:.4f} ({len(events)} elastic event(s))")
    else:
        print(f"done at step {final}; no new steps "
              f"(checkpoint already at --steps)")


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--strategy", default="df",
                    help="rules-table name, or 'auto' to let the oracle "
                         "auto-tuner pick strategy/mesh/memory switches")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--schedule", default="auto",
                    help="pipeline schedule: gpipe | one_f_one_b | "
                         "interleaved; 'auto' follows the tuned plan "
                         "(--strategy auto) or gpipe otherwise")
    ap.add_argument("--virtual-stages", type=int, default=2,
                    help="v for the interleaved schedule (chunks per rank)")
    ap.add_argument("--segments", type=int, default=None,
                    help="requested microbatch count S for pipeline "
                         "schedules (default: the tuned plan's, else 8); "
                         "the step resolves the largest deployable S <= "
                         "this and reports it in metrics")
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scan-layers", action="store_true", default=True)
    ap.add_argument("--elastic", action="store_true",
                    help="oracle-guided elastic loop (runtime/elastic.py): "
                         "tune for the current devices; on slice loss or "
                         "repeated stragglers, re-tune on the surviving "
                         "ClusterSpec, reshard the checkpoint plan-to-plan "
                         "and resume (DESIGN.md §12)")
    ap.add_argument("--straggler-patience", type=int, default=3,
                    help="--elastic: consecutive StragglerAlerts before the "
                         "loop checkpoints and remeshes around the slow host")
    # machine description for --strategy auto (default: the preset of the
    # attached device; --cluster takes a fitted experiments/cluster_fit.json
    # artifact)
    from ..core.cluster import add_cluster_args
    add_cluster_args(ap, default_system=None)
    return ap.parse_args(argv)


@dataclass
class Trainer:
    """What ``main`` assembles before its loop: the model, the sharding
    context, the jitted step (state donated), the initial state placed per
    the strategy's rules, the loader and the checkpointer."""

    model: Any
    mesh: Any
    ctx: ShardingCtx
    opt: OptimizerConfig
    step: Any
    state: Any
    shardings: Any
    loader: ShardedLoader
    ckpt: Checkpointer


def build_trainer(args, mesh=None) -> Trainer:
    """Assemble the run the flags describe. ``mesh`` overrides the mesh of a
    named strategy (default: every device, ``make_host_mesh``)."""
    cfg = get_config(args.arch)
    mc = cfg.smoke_model if args.smoke else cfg.model
    model = build_model(cfg, smoke=args.smoke)
    strategy, plan = args.strategy, None
    if strategy == "auto":
        # oracle-in-the-loop: tune (strategy, mesh split, memory switches)
        # for the machine the cluster flags describe (default: the attached
        # device), then deploy the plan (DESIGN.md §8/§11)
        from ..core.autotune import autotune, stats_for_model
        from ..core.cluster import ClusterSpec
        from ..core.oracle import TimeModel
        from ..parallel.pipeline import (pipeline_block_count,
                                         pipeline_supported)
        n = len(jax.devices())
        cluster = ClusterSpec.from_cli_args(args)
        plan = autotune(stats_for_model(mc, args.seq),
                        TimeModel(cluster.system),
                        cluster.oracle_config(
                            B=args.batch, D=args.batch,
                            virtual_stages=max(args.virtual_stages, 1)), n,
                        schedules=("all" if args.schedule == "auto"
                                   else (args.schedule,)),
                        fallback=cfg.strategy, cluster=cluster,
                        allow_remat=cfg.family != "cnn",
                        allow_pipeline=pipeline_supported(mc) is None,
                        max_stages=pipeline_block_count(mc))
        print(plan.describe())
        strategy = plan.exec_strategy("train")
        mesh = make_host_mesh(model=plan.p2 if n % plan.p2 == 0 else None)
        opt = OptimizerConfig(lr=args.lr, zero1=plan.zero1)
    else:
        mesh = mesh or make_host_mesh()
        opt = OptimizerConfig(lr=args.lr, zero1=strategy != "pipeline")
    rules = make_rules(strategy)
    ctx = ShardingCtx(mesh, rules)

    fwd_kw = {}
    if cfg.family in ("lm", "vlm"):
        fwd_kw = dict(scan_layers=args.scan_layers, attn_impl="chunked",
                      q_chunk=min(256, args.seq))
    if plan is not None and cfg.family in ("lm", "vlm", "encdec"):
        fwd_kw["remat"] = plan.remat    # deploy the plan's remat switch
    if strategy == "pipeline":
        # stage schedule (gpipe / 1F1B / interleaved) over the mesh's model
        # axis; S = what the plan's projection assumed (default 8) — the
        # step resolves the largest deployable S <= requested and surfaces
        # it in metrics (pipeline_segments); stage cuts = the DP
        # partitioner over per-block costs
        from ..core.autotune import stats_for_model
        from ..parallel.pipeline import (make_pipeline_train_step,
                                         pipeline_block_costs)
        if args.accum != 1:
            raise SystemExit("--accum > 1 is not supported with "
                             "--strategy pipeline (the pipeline "
                             "microbatches are the accumulation schedule)")
        seg = args.segments or (plan.segments if plan is not None else 8)
        schedule = args.schedule
        virtual = max(args.virtual_stages, 1)
        if plan is not None:
            schedule = plan.schedule if schedule == "auto" else schedule
            virtual = plan.virtual_stages
        elif schedule == "auto":
            schedule = "gpipe"
        costs = pipeline_block_costs(model, stats_for_model(mc, args.seq),
                                     **fwd_kw)
        print(f"pipeline schedule={schedule}"
              + (f" v={virtual}" if schedule == "interleaved" else "")
              + f" segments<={seg}")
        step = jax.jit(make_pipeline_train_step(
            model, opt, ctx, block_costs=costs, segments=seg,
            schedule=schedule, virtual_stages=virtual,
            **fwd_kw), donate_argnums=(0,))
    else:
        step = jax.jit(make_train_step(model, opt, ctx, accum=args.accum,
                                       **fwd_kw), donate_argnums=(0,))
    # the state starts out placed per the rules (params and step on the
    # strategy's table, optimizer state on ZeRO-1's), not on one device
    shardings = state_shardings(model, opt, mesh, rules)
    state = jax.device_put(
        tree_init(train_state_spec(model, opt), jax.random.PRNGKey(args.seed)),
        shardings)
    loader = ShardedLoader(data_config_for(mc, args.batch, args.seq,
                                           args.seed), mesh)
    ckpt = Checkpointer(f"{args.ckpt_dir}/{args.arch}",
                        config_tag=config_hash((args.arch, args.smoke)))
    return Trainer(model, mesh, ctx, opt, step, state, shardings, loader,
                   ckpt)


def main(argv=None) -> None:
    args = parse_args(argv)
    use_compile_cache()
    if args.elastic:
        cfg = get_config(args.arch)
        mc = cfg.smoke_model if args.smoke else cfg.model
        return _main_elastic(args, cfg, mc, build_model(cfg, smoke=args.smoke))
    t = build_trainer(args)

    t_start = time.time()
    losses = []

    def on_metrics(s, m):
        losses.append(float(m["loss"]))
        if s % args.log_every == 0:
            print(f"step {s:5d} loss {float(m['loss']):.4f} "
                  f"grad_norm {float(m['grad_norm']):.3f} "
                  f"({(time.time()-t_start):.1f}s)", flush=True)

    state, start = t.state, t.ckpt.latest_step() or 0
    if start:
        state, start = t.ckpt.restore(state, shardings=t.shardings)
        print(f"resumed from step {start}")
    state, final = run_with_recovery(
        t.step, state, t.loader, t.ckpt, n_steps=args.steps,
        start_step=start, ckpt_every=args.ckpt_every, on_metrics=on_metrics)
    if losses:
        print(f"done at step {final}; loss {losses[0]:.4f} → {losses[-1]:.4f}")
    else:   # resumed at/past --steps: zero new steps this run
        print(f"done at step {final}; no new steps "
              f"(checkpoint already at --steps)")


if __name__ == "__main__":
    main()
