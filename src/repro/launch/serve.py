"""Serving CLI: the continuous-batching engine behind a traffic replay.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --smoke \
        --rate 4 --prompt-len 32 --gen 16 --requests 8

Thin glue only — the engine (repro/serve/engine.py) owns the request
queue, the paged KV pool and the jitted prefill/decode cells (donated
cache, zeros allocated straight from the pool spec); this file resolves
the arch + strategy, shapes the mesh, generates the trace and prints the
report. ``--strategy auto`` asks the training auto-tuner for the serving
layout and, when the winner's model width cannot tile the device count,
falls back to the best plan over widths that can (``model_widths``) —
with a warning, never by silently dropping the model axis.
"""
from __future__ import annotations

import argparse
import json
import time
import warnings

import jax
import numpy as np

from ..configs import get_config
from ..nn.module import ShardingCtx, tree_init
from ..parallel.strategies import make_rules
from ..serve import Engine, ServeConfig, TrafficModel
from .build import build_model
from .cache import use_compile_cache
from .mesh import make_host_mesh


def resolve_auto_strategy(mc, args, n: int):
    """Tuner-picked serving layout: (strategy name, model width).

    Re-tunes over the divisors of ``n`` when the unconstrained winner's
    p2 cannot tile the mesh — the runner-up that tiles replaces it.
    """
    from ..core.autotune import autotune, stats_for_model
    from ..core.cluster import ClusterSpec
    from ..core.oracle import TimeModel
    cluster = ClusterSpec.from_cli_args(args)
    stats = stats_for_model(mc, args.prompt_len + args.gen)
    B = args.max_batch
    # switches=None: the serving exec path deploys no memory switches
    # (no optimizer to ZeRO-shard, no backward to remat), so the plan
    # must not claim feasibility through them.
    # allow_pipeline=False: every pipeline schedule is a training
    # schedule (fill/drain over microbatches) — serving never ranks them.
    kw = dict(fallback="serve_tp", cluster=cluster, switches=None,
              allow_pipeline=False)
    plan = autotune(stats, TimeModel(cluster.system),
                    cluster.oracle_config(B=B, D=B), n, **kw)
    if n % plan.p2:
        tiling = tuple(k for k in range(1, n + 1) if n % k == 0)
        warnings.warn(
            f"tuned model width p2={plan.p2} cannot tile {n} devices; "
            f"re-tuning over widths {tiling} for the best plan that does",
            stacklevel=2)
        plan = autotune(stats, TimeModel(cluster.system),
                        cluster.oracle_config(B=B, D=B), n,
                        model_widths=tiling, **kw)
    print(plan.describe())
    return plan.exec_strategy("decode"), plan.p2


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--strategy", default="serve_tp",
                    help="serve_tp | serve_seqkv | a rules-table name | "
                         "'auto' (oracle auto-tuner picks the layout)")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="continuous-batch width (decode slots)")
    ap.add_argument("--max-len", type=int, default=None,
                    help="per-sequence KV capacity "
                         "(default: padded prompt + gen)")
    ap.add_argument("--block-tokens", type=int, default=16,
                    help="paged-cache allocation granularity")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="prompt tokens prefilled per engine step")
    ap.add_argument("--kv-shards", type=int, default=None,
                    help="cache span shards (default: mesh model size "
                         "for serve_seqkv, else 1)")
    # traffic
    ap.add_argument("--rate", type=float, default=8.0,
                    help="request arrival rate (req/s); the trace replays "
                         "open-loop against it")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--closed-loop", action="store_true",
                    help="enqueue the whole trace up front (max-throughput "
                         "mode, ignores arrival times)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json-out", default=None,
                    help="write the report summary as JSON")
    from ..core.cluster import add_cluster_args
    add_cluster_args(ap, default_system=None)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if cfg.family != "lm":
        raise SystemExit(
            f"the serving engine decodes lm archs, not {cfg.family}")
    model = build_model(cfg, smoke=args.smoke)
    mc = cfg.smoke_model if args.smoke else cfg.model
    n = len(jax.devices())

    strategy, width = args.strategy, n
    if strategy == "auto":
        strategy, width = resolve_auto_strategy(mc, args, n)
    mesh = make_host_mesh(model=width)
    ctx = ShardingCtx(mesh, make_rules(strategy))
    kv_shards = args.kv_shards if args.kv_shards is not None else (
        int(mesh.shape.get("model", 1)) if strategy == "serve_seqkv" else 1)

    traffic = TrafficModel(rate=args.rate, prompt_len=args.prompt_len,
                           gen_len=args.gen)
    trace = traffic.trace(args.requests, mc.vocab, seed=args.seed)
    chunk = args.prefill_chunk
    max_prompt = max(len(r.prompt) for r in trace)
    max_len = args.max_len or (-(-max_prompt // chunk) * chunk + args.gen)
    # geometry alignment: the per-shard span must be a multiple of both the
    # block span and the prefill chunk — a multiple of chunk·shards covers
    # both (chunk is itself a whole number of block spans)
    align = chunk * kv_shards
    max_len = -(-max_len // align) * align

    scfg = ServeConfig(max_len=max_len, max_batch=args.max_batch,
                       block_tokens=args.block_tokens, prefill_chunk=chunk,
                       kv_shards=kv_shards)
    params = tree_init(model.params_spec(), jax.random.PRNGKey(args.seed))
    t0 = time.time()
    eng = Engine(model, params, ctx, scfg, seed=args.seed)
    print(f"engine up in {time.time() - t0:.1f}s: {eng.geo}, "
          f"{eng.alloc.capacity} blocks, strategy={strategy}, "
          f"mesh={dict(mesh.shape)}")

    report = eng.run(trace, honor_arrivals=not args.closed_loop)
    summary = report.summary()
    print(json.dumps(summary, indent=1))
    first = report.requests[0] if report.requests else None
    if first is not None:
        print(f"first request's tokens: {np.asarray(first.tokens)}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"strategy": strategy, "mesh": dict(mesh.shape),
                       "config": {"max_batch": scfg.max_batch,
                                  "max_len": scfg.max_len,
                                  "block_tokens": scfg.block_tokens,
                                  "prefill_chunk": scfg.prefill_chunk,
                                  "kv_shards": scfg.kv_shards},
                       **summary}, f, indent=1)
        print(f"wrote {args.json_out}")


if __name__ == "__main__":
    main()
