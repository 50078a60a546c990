"""Tune loop: analytic prune → measure survivors → cache winners.

``tune_kernels(cluster)`` is the whole pipeline: for every (kernel, shape)
in the tuning set, enumerate candidate block sizes, kill the ones the
``HardwareSpec.from_cluster`` arithmetic rejects (VMEM capacity, roofline
knee), time the survivors, and persist the per-bucket winner to
``experiments/kernel_tune.json`` stamped with the cluster fingerprint.

The winner is the argmin over *measured* times and the default blocks are
always among the measured candidates, so ``measured_us ≤ default_us`` holds
by construction in every entry — the property scripts/check.sh gates on.

``DEFAULT_SHAPES`` mirrors benchmarks/bench_kernels.py exactly so the tuned
bench rows hit tuned buckets; ``SMOKE_SHAPES`` are the tiny CI equivalents.
"""
from __future__ import annotations

from .cache import DEFAULT_TUNE_PATH, KernelTuneCache
from .measure import _inputs, time_candidate
from .space import prune

#: (kernel, dims) pairs — full shapes = the bench_kernels.py full suite
DEFAULT_SHAPES = (
    ("conv2d_gemm", dict(B=4, H=32, W=32, C=64, F=128,
                         kh=3, kw=3, sh=1, sw=1, e=4)),
    ("flash_attention", dict(B=1, H=4, S=512, D=64, causal=1, e=4)),
    ("rmsnorm", dict(R=4096, D=1024, e=4)),
    ("ssd_scan", dict(B=1, S=512, H=4, P=16, N=32, e=4)),
)

#: tiny CI shapes: same kernels, seconds not minutes in interpret mode
SMOKE_SHAPES = (
    ("conv2d_gemm", dict(B=1, H=8, W=8, C=8, F=16,
                         kh=3, kw=3, sh=1, sw=1, e=4)),
    ("flash_attention", dict(B=1, H=2, S=64, D=16, causal=1, e=4)),
    ("rmsnorm", dict(R=128, D=128, e=4)),
    ("ssd_scan", dict(B=1, S=64, H=2, P=4, N=8, e=4)),
)

SHAPE_SETS = {"full": DEFAULT_SHAPES, "smoke": SMOKE_SHAPES}


def tune_kernels(cluster, *, shapes="full", path: str | None = None,
                 iters: int = 3, warmup: int = 1, slack: float = 2.0,
                 top_k: int = 4, backend: str | None = None,
                 verbose: bool = False) -> KernelTuneCache:
    """Run the full prune→measure→cache pipeline for ``cluster``.

    ``shapes``: "full" | "smoke" | explicit ((kernel, dims), ...).
    ``path``: artifact destination; None ⇒ the committed default; "" ⇒ don't
    persist (tests that only want the in-memory cache).
    """
    from ...core.roofline import HardwareSpec
    from .space import bucket

    hw = HardwareSpec.from_cluster(cluster)
    if isinstance(shapes, str):
        shapes = SHAPE_SETS[shapes]
    if backend is None:
        import jax
        backend = jax.default_backend()
    cache = KernelTuneCache(fingerprint=cluster.fingerprint(),
                            backend=backend, cluster_name=cluster.name)
    for kernel, dims in shapes:
        survivors = prune(kernel, dims, hw, slack=slack, top_k=top_k)
        inputs = _inputs(kernel, dims)      # shared across candidates
        timed = []
        for cand in survivors:
            t = time_candidate(kernel, dims, cand.blocks_dict,
                               backend=backend, iters=iters, warmup=warmup,
                               inputs=inputs)
            timed.append((t, cand))
            if verbose:
                print(f"  {kernel} {cand.blocks_dict} "
                      f"predicted {cand.predicted_s * 1e6:9.1f}us "
                      f"measured {t * 1e6:9.1f}us"
                      f"{'  [default]' if cand.is_default else ''}")
        best_t, best = min(timed, key=lambda tc: tc[0])
        default_t = min(t for t, c in timed if c.is_default)
        cand_rows = [{"blocks": c.blocks_dict,
                      "predicted_us": round(c.predicted_s * 1e6, 3),
                      "measured_us": round(t * 1e6, 3),
                      "is_default": bool(c.is_default)}
                     for t, c in sorted(timed, key=lambda tc: tc[0])]
        cache.put(kernel, bucket(kernel, dims), blocks=best.blocks_dict,
                  measured_us=best_t * 1e6, default_us=default_t * 1e6,
                  predicted_us=best.predicted_s * 1e6, trials=len(timed),
                  candidates=cand_rows)
        if verbose:
            print(f"{kernel}: winner {best.blocks_dict} "
                  f"{best_t * 1e6:.1f}us vs default {default_t * 1e6:.1f}us "
                  f"({len(timed)} candidates measured)")
    if path is None:
        path = DEFAULT_TUNE_PATH
    if path:
        cache.save(path)
    return cache
