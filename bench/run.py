"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python bench/run.py --workload resnet50.b128 --seed 7 --seconds 30 --trace 0

Runs on the machine it is started on and needs as many TPU chips as the cell
asks for: without them it exits non-zero and prints no result. The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and
last the numbers that decided ``correct``, each beside its limit. The same
numbers close standard error.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# import as the ``bench`` package from the checkout's root, and never let
# ``bench/trace.py`` stand in for the standard library's ``trace``
if sys.path and Path(sys.path[0]).resolve() == BENCH:
    sys.path.pop(0)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

#: the persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"


def require_chips(chips: int):
    """The first ``chips`` TPU devices; exits non-zero without them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"no TPU found: JAX's devices are {devs[0].platform} "
                 f"({devs[0].device_kind}); the benchmark runs only on a TPU")
    if len(devs) < chips:
        sys.exit(f"the cell needs {chips} TPU chips; JAX sees {len(devs)}")
    return devs[:chips]


def use_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"no program under {ROOT / 'src'}: run from a checkout")
    from bench.cell import peaks, resolve
    cell = resolve(args.workload)
    devices = require_chips(cell.chips)
    peak = peaks(devices[0].device_kind)
    use_cache()
    from bench.train import drive
    result = drive(cell, devices, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), peak=peak, t_process=T_PROCESS,
                   log=lambda s: print(s, file=sys.stderr, flush=True))
    for name, c in result["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
