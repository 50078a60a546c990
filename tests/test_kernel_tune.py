"""Kernel autotuner (kernels/autotune; DESIGN.md §13).

Three layers under test:

* **space** — the analytic pruner is pure arithmetic: candidates resolve to
  divisors, VMEM-infeasible tilings are rejected, the kernel default always
  survives (the measure loop needs its row), and shape buckets round size
  dims to the NEAREST power of two so a halo tile (H + kh − 1 rows) shares
  its base shape's entry.
* **cache** — the artifact lifecycle: round-trip, stale-fingerprint
  invalidation (machine description changed ⇒ warn + kernel defaults, never
  silently deploy), corrupt/wrong-version artifacts degrade the same way.
* **deployment** — tuned blocks actually reach the kernels: a cache entry
  with a distinctive block_f is observed arriving at ``pl.pallas_call``'s
  grid through HaloConv, and ``build_cell(use_pallas=True)`` resolves tiles
  from the explicit argument / the plan / the committed artifact in that
  order.
"""
import json
import warnings

import numpy as np
import pytest

from repro.core.cluster import ClusterSpec
from repro.core.roofline import HardwareSpec
from repro.kernels.autotune import (KernelTuneCache, bucket,
                                    enumerate_candidates, load_tiles, prune,
                                    tune_kernels)
from repro.kernels.autotune.tune import SMOKE_SHAPES
from repro.kernels.util import largest_divisor, resolve_block_rows

TPU = ClusterSpec.of("tpu")
HW = HardwareSpec.from_cluster(TPU)

CONV_DIMS = dict(B=1, H=8, W=8, C=8, F=16, kh=3, kw=3, sh=1, sw=1, e=4)


# ---------------------------------------------------------------------------
# shared divisor helpers (the satellite bugfixes ride on these)
# ---------------------------------------------------------------------------

def test_largest_divisor():
    assert largest_divisor(512, 128) == 128     # divides: cap wins
    assert largest_divisor(100, 128) == 100     # cap clamps to n
    assert largest_divisor(100, 64) == 50       # largest divisor ≤ cap
    assert largest_divisor(96, 36) == 32
    assert largest_divisor(37, 16) == 1         # prime: only 1 fits
    assert largest_divisor(1, 128) == 1


def test_resolve_block_rows_divisor_path():
    assert resolve_block_rows(4096, 256) == (256, 4096)
    assert resolve_block_rows(100, 64) == (50, 100)    # 50 ≥ min_block
    assert resolve_block_rows(8, 256) == (8, 8)        # br == cap: no pad


def test_resolve_block_rows_pads_pathological_rows():
    # prime row count: every proper divisor is 1 — pad instead of
    # serializing the grid to R single-row programs
    br, rp = resolve_block_rows(37, 16)
    assert (br, rp) == (16, 48) and rp % br == 0
    br, rp = resolve_block_rows(8209, 256)             # prime > block
    assert (br, rp) == (256, 8448) and rp % br == 0


# ---------------------------------------------------------------------------
# search space + analytic pruner
# ---------------------------------------------------------------------------

def test_bucket_rounds_size_dims_keeps_structure():
    base = bucket("conv2d_gemm", CONV_DIMS)
    assert "F16" in base and "C8" in base and "kh3" in base
    # halo tile: H + kh − 1 = 10 rounds to 8 → SAME bucket as the base shape
    halo = bucket("conv2d_gemm", {**CONV_DIMS, "H": 10})
    assert halo == base
    # structural dims are exact: a different F is a different bucket
    assert bucket("conv2d_gemm", {**CONV_DIMS, "F": 32}) != base


def test_candidates_resolve_to_divisors():
    for kernel, dims in SMOKE_SHAPES:
        for c in enumerate_candidates(kernel, dims, HW):
            for name, v in c.blocks:
                n = {"block_f": dims.get("F"), "block_q": dims.get("S"),
                     "block_k": dims.get("S"), "chunk": dims.get("S"),
                     "block_rows": None}[name]
                if n is not None:
                    assert n % v == 0, (kernel, name, v, n)


def test_prune_rejects_vmem_and_keeps_default():
    tiny = HardwareSpec(vmem_bytes=2**20)   # 1 MiB: only small blocks fit
    dims = dict(R=4096, D=1024, e=4)
    full = enumerate_candidates("rmsnorm", dims, HW)
    assert any(c.vmem_bytes > 0.9 * tiny.vmem_bytes for c in full)
    kept = prune("rmsnorm", dims, tiny)
    assert kept and all(
        c.vmem_bytes <= 0.9 * tiny.vmem_bytes for c in kept)
    for kernel, dims in SMOKE_SHAPES:
        assert any(c.is_default for c in prune(kernel, dims, HW)), kernel


def test_prune_orders_by_predicted_time():
    for kernel, dims in SMOKE_SHAPES:
        kept = prune(kernel, dims, HW)
        preds = [c.predicted_s for c in kept if not c.is_default]
        assert preds == sorted(preds)
        assert all(c.predicted_s > 0 for c in kept)


# ---------------------------------------------------------------------------
# cache lifecycle
# ---------------------------------------------------------------------------

def _cache_with_entry(fp="fp-a", block_f=4):
    cache = KernelTuneCache(fingerprint=fp, backend="cpu", cluster_name="t")
    cache.put("conv2d_gemm", bucket("conv2d_gemm", CONV_DIMS),
              blocks={"block_f": block_f}, measured_us=10.0, default_us=20.0,
              predicted_us=12.0, trials=3)
    return cache


def test_cache_roundtrip(tmp_path):
    path = str(tmp_path / "kt.json")
    cache = _cache_with_entry()
    cache.save(path)
    again = KernelTuneCache.load(path, fingerprint="fp-a")
    assert again.entries == cache.entries
    assert again.fingerprint == "fp-a"
    tiles = again.tiles()
    assert tiles.blocks_for("conv2d_gemm", CONV_DIMS) == {"block_f": 4}
    assert tiles.conv_block_f(**{k: CONV_DIMS[k] for k in
                                 ("B", "H", "W", "C", "F", "kh", "kw")}) == 4
    # unknown bucket → kernel default
    assert tiles.blocks_for("conv2d_gemm", {**CONV_DIMS, "F": 64}) == {}
    assert tiles.conv_block_f(B=1, H=8, W=8, C=8, F=64, kh=3, kw=3) == 128


def test_cache_stale_fingerprint_warns_and_resets(tmp_path):
    path = str(tmp_path / "kt.json")
    _cache_with_entry(fp="fp-a").save(path)
    with pytest.warns(UserWarning, match="stale"):
        fresh = KernelTuneCache.load(path, fingerprint="fp-b")
    assert fresh.entries == {} and fresh.fingerprint == "fp-b"
    # deployment view: stale artifact ⇒ empty tiles ⇒ kernel defaults
    with pytest.warns(UserWarning, match="stale"):
        tiles = load_tiles(path, cluster=TPU)
    assert len(tiles) == 0
    assert tiles.conv_block_f(**{k: CONV_DIMS[k] for k in
                                 ("B", "H", "W", "C", "F", "kh", "kw")}) == 128


def test_cache_corrupt_and_wrong_version_warn(tmp_path):
    path = str(tmp_path / "kt.json")
    path2 = str(tmp_path / "kt2.json")
    with open(path, "w") as f:
        f.write("{not json")
    with pytest.warns(UserWarning, match="corrupt"):
        fresh = KernelTuneCache.load(path, fingerprint="fp")
    assert fresh.entries == {}
    d = _cache_with_entry().to_json()
    d["version"] = 99
    with open(path2, "w") as f:
        json.dump(d, f)
    with pytest.warns(UserWarning, match="version"):
        fresh = KernelTuneCache.load(path2, fingerprint="fp-a")
    assert fresh.entries == {}
    # missing file: silently fresh (first run), no warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        fresh = KernelTuneCache.load(str(tmp_path / "absent.json"))
    assert fresh.entries == {}


def test_tune_kernels_end_to_end(tmp_path):
    """The measure loop on one tiny shape: artifact written, winner never
    slower than the measured default (argmin includes the default row)."""
    path = str(tmp_path / "kt.json")
    shapes = (("rmsnorm", dict(R=128, D=128, e=4)),)
    cache = tune_kernels(TPU, shapes=shapes, path=path, iters=1, warmup=1)
    assert len(cache.entries) == 1
    (entry,) = cache.entries.values()
    assert entry["measured_us"] <= entry["default_us"] + 1e-9
    assert entry["trials"] >= 1 and entry["blocks"]
    tiles = load_tiles(path, cluster=TPU)       # fingerprint matches
    assert tiles.blocks_for("rmsnorm", dict(R=128, D=128, e=4)) \
        == entry["blocks"]
    # a different machine description invalidates the artifact
    other = ClusterSpec.of("paper")
    assert other.fingerprint() != TPU.fingerprint()
    with pytest.warns(UserWarning, match="stale"):
        assert len(load_tiles(path, cluster=other)) == 0


# ---------------------------------------------------------------------------
# deployment threading
# ---------------------------------------------------------------------------

def test_tuned_block_reaches_pallas_call(monkeypatch):
    """Acceptance pin: a cache entry's block_f arrives at pl.pallas_call's
    grid when HaloConv deploys through ShardingCtx.kernel_tiles."""
    import importlib

    import jax
    # the package attribute "conv2d_gemm" is shadowed by the function
    # re-export in kernels/__init__, so fetch the module via importlib
    cg = importlib.import_module("repro.kernels.conv2d_gemm.conv2d_gemm")
    from repro.nn.module import ShardingCtx, tree_init
    from repro.parallel.halo import HaloConv
    from repro.parallel.strategies import make_rules

    seen = {}
    real = cg.pl.pallas_call

    def spy(kernel, *, grid, **kw):
        seen["grid"] = grid
        return real(kernel, grid=grid, **kw)

    monkeypatch.setattr(cg.pl, "pallas_call", spy)
    conv = HaloConv(in_channels=8, out_channels=16, kernel=(3, 3))
    params = tree_init(conv.params_spec(), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 8, 8))
    tiles = _cache_with_entry(block_f=4).tiles()
    rules = make_rules("data")
    ctx = ShardingCtx(mesh=None, rules=rules, use_pallas=True,
                      kernel_tiles=tiles)
    y = conv.apply(params, x, ctx)
    # grid = (batch, output-row blocks, F / block_f); 8 rows fit one block
    assert seen["grid"] == (1, 1, 16 // 4)      # tuned block_f=4 deployed
    ctx0 = ShardingCtx(mesh=None, rules=rules, use_pallas=True)
    y0 = conv.apply(params, x, ctx0)
    assert seen["grid"] == (1, 1, 1)            # default 128 → divisor 16
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                               rtol=1e-5, atol=1e-5)


def test_build_cell_resolution_order(monkeypatch):
    """build_cell(use_pallas=True): explicit kernel_tiles > plan.kernel_tiles
    > the committed artifact (fingerprint-checked via ``system``)."""
    import dataclasses

    import repro.kernels.autotune as at
    from repro.configs import get_config
    from repro.core.autotune import plan_for_arch
    from repro.launch import build as build_mod
    from repro.launch.mesh import make_host_mesh

    seen = {}
    real_ctx = build_mod.ShardingCtx

    def ctx_spy(*a, **kw):
        ctx = real_ctx(*a, **kw)
        seen["tiles"] = ctx.kernel_tiles
        return ctx

    monkeypatch.setattr(build_mod, "ShardingCtx", ctx_spy)
    cfg = get_config("resnet50")
    mesh = make_host_mesh()
    explicit = _cache_with_entry(block_f=8).tiles()

    # 1. explicit argument wins
    build_mod.build_cell(cfg, "train_4k", mesh, "data", smoke=True,
                         use_pallas=True, kernel_tiles=explicit)
    assert seen["tiles"] is explicit

    # 2. the plan's tiles deploy when no explicit arg
    from_plan = _cache_with_entry(block_f=2).tiles()
    plan = dataclasses.replace(
        plan_for_arch(cfg, "train_4k", int(mesh.size), smoke=True),
        kernel_tiles=from_plan)
    build_mod.build_cell(cfg, "train_4k", mesh, "auto", smoke=True,
                         plan=plan, use_pallas=True)
    assert seen["tiles"] is from_plan

    # 3. fallback: the committed artifact via load_tiles
    from_disk = _cache_with_entry(block_f=16).tiles()
    monkeypatch.setattr(at, "load_tiles", lambda *a, **kw: from_disk)
    build_mod.build_cell(cfg, "train_4k", mesh, "data", smoke=True,
                         use_pallas=True)
    assert seen["tiles"] is from_disk

    # use_pallas=False: no tiles, no artifact read
    build_mod.build_cell(cfg, "train_4k", mesh, "data", smoke=True)
    assert seen["tiles"] is None


def test_oracle_session_tiles_lifecycle(tmp_path):
    """Oracle.tune_kernels attaches tiles to subsequent plans; rebinding the
    cluster (the fingerprint changes) drops them."""
    from repro.api import Oracle

    ses = Oracle("resnet50", "train_4k", "tpu", smoke=True)
    path = str(tmp_path / "kt.json")
    cache = ses.tune_kernels(shapes=(("rmsnorm", dict(R=128, D=128, e=4)),),
                             path=path, iters=1, warmup=1)
    assert cache.fingerprint == ses.cluster.fingerprint()
    plan = ses.tune(8)
    assert plan.kernel_tiles is not None and len(plan.kernel_tiles) == 1
    ses2 = ses.with_cluster("paper")
    assert ses2.tune(8).kernel_tiles is None    # stale tiles never survive
