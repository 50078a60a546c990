"""Session facade (repro.api.Oracle) + ClusterSpec (DESIGN.md §11).

Pins the ISSUE-5 acceptance surface:
  * session ↔ legacy parity: project / sweep / tune answers within 1e-12
    of the loose-object call signatures they replace,
  * topology constraints: a (4,2)-torus rejects model axes spanning both
    dims, and a constrained ClusterSpec provably changes the tuner's plan
    vs the unconstrained one,
  * ``ClusterSpec.fitted_from`` round-trips synthetic measurements (α/β
    recovered by the Hockney fit, φ/σ exactly),
  * the deduplicated CLI wiring (ClusterSpec.from_cli_args); the PR-5
    sweep.parse_*_table deprecation shims are retired for good.
"""
import argparse

import numpy as np
import pytest

from repro.api import Oracle
from repro.core import (OracleConfig, PAPER_V100_CLUSTER, TimeModel,
                        stats_for)
from repro.core.autotune import autotune, plan_for_arch
from repro.core.cluster import (ClusterSpec, Measurement, Torus,
                                add_cluster_args, parse_phi_table,
                                parse_sigma_table)
from repro.core.hardware import Level
from repro.core.oracle import project
from repro.core.sweep import sweep
from repro.models.cnn import RESNET50, CosmoFlowConfig

TM = TimeModel(PAPER_V100_CLUSTER)


# ---------------------------------------------------------------------------
# session ↔ legacy parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p", [8, 64, 1024])
def test_session_project_matches_legacy(p):
    stats = stats_for(RESNET50)
    cfg = OracleConfig(B=2 * p, D=1_281_167)
    ses = Oracle("resnet50", "train_4k", "paper", batch=2 * p,
                 dataset=1_281_167)
    for s in ("data", "spatial", "filter", "channel", "df", "ds"):
        a = project(s, stats, TM, cfg, p)
        b = ses.project(s, p)
        assert np.isclose(a.total_s, b.total_s, rtol=1e-12, atol=0)
        assert np.isclose(a.mem_bytes, b.mem_bytes, rtol=1e-12, atol=0)
        assert (a.p1, a.p2, a.feasible) == (b.p1, b.p2, b.feasible)


def test_session_sweep_matches_legacy():
    stats = stats_for(RESNET50)
    cfg = OracleConfig(B=128, D=1_281_167)
    ses = Oracle("resnet50", "train_4k", "paper", batch=128,
                 dataset=1_281_167)
    a = sweep(stats, TM, cfg, [1, 2, 8, 12, 64])
    b = ses.sweep([1, 2, 8, 12, 64])
    assert len(a) == len(b)
    np.testing.assert_allclose(a.total_s, b.total_s, rtol=1e-12)
    np.testing.assert_allclose(a.mem_bytes, b.mem_bytes, rtol=1e-12)
    assert (a.feasible == b.feasible).all()


@pytest.mark.parametrize("p", [8, 64])
def test_session_tune_matches_plan_for_arch(p):
    from repro.configs import get_config
    want = plan_for_arch(get_config("resnet50"), "train_4k", p)
    got = Oracle("resnet50", "train_4k").tune(p)
    assert want == got


def test_session_cluster_swap_is_one_argument():
    """The multi-cluster scenario the redesign exists for: same session
    question, different machine, different answer."""
    ses_gpu = Oracle("resnet50", "train_4k", "paper", batch=2048)
    ses_tpu = ses_gpu.with_cluster("tpu")
    a, b = ses_gpu.project("data", 64), ses_tpu.project("data", 64)
    assert a.total_s != b.total_s          # different α–β/peak actually used
    assert ses_tpu.cluster.name == "tpu-v5e-256"
    # with_cluster leaves the original session untouched
    assert ses_gpu.cluster.name == "v100-abci"


# ---------------------------------------------------------------------------
# topology constraints
# ---------------------------------------------------------------------------

def test_torus_rejects_model_axis_spanning_dims():
    t = Torus((4, 2))
    assert t.model_widths() == (1, 2, 4)
    assert not t.split_mask(8, 1, 8)      # p2=8 would span both dims
    assert t.split_mask(8, 2, 4)          # ring of 4 in dim 0
    assert t.split_mask(8, 4, 2)
    assert t.split_mask(8, 1, 8, strategy="pipeline")   # chains may snake
    assert not t.split_mask(6, 3, 2)      # 6 does not tile the 8-PE torus
    # model axis confined to the extent-2 dim
    t2 = Torus((4, 2), model_dims=(1,))
    assert t2.model_widths() == (1, 2)
    assert not t2.split_mask(8, 2, 4)


def test_sweep_prunes_topology_infeasible_splits():
    stats = stats_for(CosmoFlowConfig(img=128))
    cfg = OracleConfig(B=2, D=1584)
    cluster = ClusterSpec.from_system(
        PAPER_V100_CLUSTER, topology=Torus((4, 2)))
    res = sweep(stats, TM, cfg, [8], cluster=cluster)
    free = sweep(stats, TM, cfg, [8])
    # spatial at p=8 needs a model ring of 8 — pruned on the (4,2) torus
    sp = res.select((res.strategy == "spatial"))
    assert not sp.feasible.any()
    assert "topology" in str(sp.limit[0])
    assert free.select(free.strategy == "spatial").feasible.any()
    # and the surviving ring widths are exactly the torus divisors —
    # except pipeline (stage chain may snake across dims) and summa, whose
    # (r × c) grid legitimately embeds its two rings in two DISTINCT dims
    ok = res.select(res.ok & (res.strategy != "pipeline")
                    & (res.strategy != "summa"))
    assert set(np.unique(ok.p2)) <= {1, 2, 4}
    sm = res.select(res.ok & (res.strategy == "summa"))
    assert 8 in sm.p2                     # the 4×2 grid fills the torus
    for r_, c_ in zip(sm.p2r, sm.p2c):
        r_, c_ = int(r_), int(c_)
        assert (4 % r_ == 0 and 2 % c_ == 0) \
            or (2 % r_ == 0 and 4 % c_ == 0), (r_, c_)
    pipe = res.select(res.ok & (res.strategy == "pipeline"))
    assert 8 in pipe.p2                   # the chain exemption is real
    # the α–β numbers themselves are untouched — only feasibility moved
    np.testing.assert_allclose(res.total_s, free.total_s, rtol=1e-12)


def test_topology_changes_the_chosen_plan_pinned():
    """Acceptance pin: a topology-constrained ClusterSpec provably changes
    the tuner's plan vs the unconstrained one (1D strategies — summa is
    excluded here because its 2D grid legitimately EMBEDS in the torus,
    which the second half pins)."""
    from repro.core.autotune import DEPLOYABLE_STRATEGIES
    no_summa = tuple(s for s in DEPLOYABLE_STRATEGIES if s != "summa")
    stats = stats_for(CosmoFlowConfig(img=128))
    cfg = OracleConfig(B=2, D=1584)
    free = autotune(stats, TM, cfg, 8, fallback="ds", allow_pipeline=False,
                    strategies=no_summa)
    assert (free.strategy, free.p2) == ("spatial", 8)   # test_autotune pin
    cluster = ClusterSpec.from_system(
        PAPER_V100_CLUSTER, topology=Torus((4, 2)))
    bound = autotune(stats, TM, cfg, 8, fallback="ds", allow_pipeline=False,
                     cluster=cluster, strategies=no_summa)
    assert bound.feasible
    assert (bound.strategy, bound.p2) != (free.strategy, free.p2)
    assert bound.strategy == "ds" and bound.p2 in (2, 4)
    # summa's (r × c) grid rides TWO torus dims, so the same constraint
    # does NOT displace it: the full-set winner keeps its plan, grid
    # embedded with each ring in its own dim
    free_2d = autotune(stats, TM, cfg, 8, fallback="ds",
                       allow_pipeline=False)
    bound_2d = autotune(stats, TM, cfg, 8, fallback="ds",
                        allow_pipeline=False, cluster=cluster)
    assert free_2d.strategy == "summa" and bound_2d == free_2d
    # the same constraint through the session facade
    ses = Oracle("cosmoflow", "train_4k", cluster, batch=2, dataset=1584,
                 mem_cap=TM.system.mem_capacity)
    plan = ses.tune(8)
    assert plan.strategy == "summa" and (plan.p2r, plan.p2c) == (4, 1)


def test_exhausted_model_dims_force_pure_data():
    """resnet50 @ p=1024 tunes to df (512×2) among the 1D strategies
    (test_autotune pin; the full set now prefers a summa grid); a torus
    with no model-capable dim must fall back to pure DP — summa included,
    since BOTH its rings need a model dim."""
    from repro.core.autotune import DEPLOYABLE_STRATEGIES
    no_summa = tuple(s for s in DEPLOYABLE_STRATEGIES if s != "summa")
    stats = stats_for(RESNET50)
    cfg = OracleConfig(B=2048, D=2048)
    free = autotune(stats, TM, cfg, 1024, fallback="data",
                    allow_pipeline=False, strategies=no_summa)
    assert (free.strategy, free.p1, free.p2) == ("df", 512, 2)
    full = autotune(stats, TM, cfg, 1024, fallback="data",
                    allow_pipeline=False)
    assert full.strategy == "summa" and full.total_s <= free.total_s
    cluster = ClusterSpec.from_system(
        PAPER_V100_CLUSTER, topology=Torus((1024,), model_dims=()))
    bound = autotune(stats, TM, cfg, 1024, fallback="data",
                     allow_pipeline=False, cluster=cluster)
    assert bound.feasible
    assert (bound.strategy, bound.p1, bound.p2) == ("data", 1024, 1)


def test_plan_for_arch_prunes_via_cluster():
    from repro.configs import get_config
    cluster = ClusterSpec.from_system(
        PAPER_V100_CLUSTER, topology=Torus((4, 2)))
    plan = plan_for_arch(get_config("cosmoflow"), "train_4k", 8,
                         cluster=cluster)
    assert plan.p2 in (1, 2, 4), plan.describe()
    # ClusterSpec also rides the legacy ``system`` parameter
    plan2 = plan_for_arch(get_config("cosmoflow"), "train_4k", 8,
                          system=cluster)
    assert plan == plan2


# ---------------------------------------------------------------------------
# fitted_from + artifact round-trip
# ---------------------------------------------------------------------------

def _synthetic_measurements(lvl: Level, level: str = "data", p: int = 8):
    out = []
    for pattern, factor in (("ar", 2 * (p - 1)), ("ag", p - 1)):
        sizes = (1 << 12, 1 << 16, 1 << 20, 1 << 23)
        secs = tuple(factor * (lvl.alpha + n / p * lvl.beta) for n in sizes)
        out.append(Measurement(level=level, kind="collective",
                               pattern=pattern, p=p, nbytes=sizes,
                               seconds=secs))
    out.append(Measurement(level=level, kind="contention",
                           alone_s=0.01, shared_s=0.017, flows=2))
    out.append(Measurement(level=level, kind="overlap",
                           comp_s=0.02, comm_s=0.01, both_s=0.022))
    return out


def test_fitted_from_roundtrips_synthetic_measurements():
    true = Level("syn", alpha=2e-5, beta=1 / 7e9)
    ms = _synthetic_measurements(true)
    spec = ClusterSpec.fitted_from(ms, base="host")
    got = spec.level("data")
    assert np.isclose(got.alpha, true.alpha, rtol=1e-6)
    assert np.isclose(got.beta, true.beta, rtol=1e-6)
    assert np.isclose(dict(spec.phi)["data"], 1.7, rtol=1e-12)
    assert np.isclose(dict(spec.sigma)["data"], 0.8, rtol=1e-12)
    # noiseless fit → residual ~0; residuals are reported either way
    assert dict(spec.fit_residuals)["data/alpha_beta"] < 1e-9
    # dict-shaped measurements (the JSON artifact) fit identically
    spec2 = ClusterSpec.fitted_from([m.to_json() for m in ms], base="host")
    assert spec2.level("data") == got
    # and the full spec round-trips through its JSON artifact form
    spec3 = ClusterSpec.from_json(spec.to_json())
    assert spec3 == spec
    assert spec3.fit_residuals == spec.fit_residuals


def test_fitted_phi_sigma_are_clamped():
    ms = [Measurement(level="data", kind="contention",
                      alone_s=0.01, shared_s=0.05, flows=2),   # >2x
          Measurement(level="model", kind="overlap",
                      comp_s=0.02, comm_s=0.01, both_s=0.035)]  # "negative"
    spec = ClusterSpec.fitted_from(ms, base="host")
    assert dict(spec.phi)["data"] == 2.0          # clamped to flows
    assert dict(spec.sigma)["model"] == 0.0       # clamped to [0, 1]


def test_calibrate_closes_the_loop_into_projections():
    """Oracle.calibrate(): fitted φ/σ/α/β must actually reach the
    session's next projection (synthetic measurements — no timing)."""
    ses = Oracle("resnet50", "train_4k", "paper", batch=128)
    before = ses.project("df", 64).total_s
    true = Level("syn", alpha=5e-4, beta=1 / 1e9)   # much slower wire
    spec = ClusterSpec.fitted_from(
        _synthetic_measurements(true), base=ses.cluster)
    ses2 = ses.with_cluster(spec)
    after = ses2.project("df", 64)
    assert after.total_s > before                  # slower fitted data level
    assert ses2.cfg.phi_levels == spec.phi
    assert ses2.cfg.sigma_levels == spec.sigma


# ---------------------------------------------------------------------------
# CLI dedup + deprecation shims
# ---------------------------------------------------------------------------

def _parse(argv, default_system="paper"):
    ap = argparse.ArgumentParser()
    add_cluster_args(ap, default_system=default_system)
    return ap.parse_args(argv)


def test_from_cli_args_is_the_one_wiring():
    a = _parse(["--phi", "data=2.0,model=1.2", "--sigma", "model=0.5",
                "--topology", "4x2", "--model-dims", "1"])
    spec = ClusterSpec.from_cli_args(a)
    assert spec.phi == (("data", 2.0), ("model", 1.2))
    assert spec.sigma == (("model", 0.5),)
    assert spec.topology == Torus((4, 2), model_dims=(1,))
    assert spec.system == PAPER_V100_CLUSTER
    cfg = spec.oracle_config(B=64)
    assert cfg.phi_levels == spec.phi and cfg.sigma_levels == spec.sigma
    # defaults: no tables, no topology — bit-identical legacy behavior
    bare = ClusterSpec.from_cli_args(_parse([]))
    assert bare.phi is None and bare.sigma is None and bare.topology is None


@pytest.mark.parametrize("platform,kind,want", [
    ("cpu", "cpu", "host"), ("tpu", "TPU v5 lite", "tpu"),
    ("tpu", "TPU v4", None), ("gpu", "NVIDIA H100", None)])
def test_default_system_follows_the_device(monkeypatch, platform, kind, want):
    """With no --system (the train/serve default) the preset is the
    attached device's; a device no preset models is an error."""
    import types
    import jax
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda: [dev])
    args = _parse([], default_system=None)
    if want is None:
        with pytest.raises(ValueError, match="no --system preset"):
            ClusterSpec.from_cli_args(args)
    else:
        assert ClusterSpec.from_cli_args(args) == ClusterSpec.of(want)


def test_session_tune_uses_the_sessions_stats():
    """A session seq override must reach tune(): the plan ranks exactly
    the stats project()/sweep() report, not shape.seq_len recomputes."""
    ses = Oracle("qwen1.5-4b", "train_4k", "paper", smoke=True, seq=64,
                 batch=8)
    from repro.parallel.pipeline import pipeline_supported
    mc = ses.model_cfg
    want = autotune(ses.stats, ses.tm, ses.cfg, 8,
                    fallback=ses.arch_cfg.strategy_for("train_4k"),
                    cluster=ses.cluster,
                    allow_remat=True,
                    allow_pipeline=pipeline_supported(mc) is None,
                    max_stages=mc.n_layers)
    got = ses.tune(8)
    assert want == got
    # and a default-seq session differs (the override is load-bearing)
    other = Oracle("qwen1.5-4b", "train_4k", "paper", smoke=True,
                   batch=8).tune(8)
    assert other.total_s != got.total_s


def test_model_dims_without_topology_is_rejected(tmp_path):
    with pytest.raises(ValueError, match="--model-dims requires"):
        ClusterSpec.from_cli_args(
            _parse(["--model-dims", "0"]))
    # but it may re-constrain a topology carried by a --cluster artifact
    import json
    spec = ClusterSpec.from_system(PAPER_V100_CLUSTER,
                                   topology=Torus((4, 2)))
    art = tmp_path / "fit.json"
    art.write_text(json.dumps(spec.to_json()))
    got = ClusterSpec.from_cli_args(
        _parse(["--cluster", str(art), "--model-dims", ""]))
    assert got.topology == Torus((4, 2), model_dims=())
    assert got.topology.model_widths() == (1,)


def test_parse_tables_reject_unknown_levels():
    assert parse_phi_table(None) is None
    assert parse_sigma_table("model=0.5") == (("model", 0.5),)
    with pytest.raises(ValueError, match="not consumed"):
        parse_phi_table("pod=2.0")
    with pytest.raises(ValueError, match="LEVEL=VALUE"):
        parse_sigma_table("model")


def test_both_clis_share_the_cluster_flags():
    """sweep.__main__ and autotune.__main__ must expose the same --phi/
    --sigma/--topology wiring (the satellite dedup) and agree on what the
    flags mean."""
    from importlib.util import find_spec
    for name in ("repro.core.sweep", "repro.core.autotune"):
        src = open(find_spec(name).origin).read()
        assert "add_cluster_args(ap" in src, name
        assert "ClusterSpec.from_cli_args" in src, name
        # the copy-pasted table parsers are gone for good (no shims either)
        assert "def _parse_level_table" not in src, name


def test_sweep_shims_are_retired():
    """The PR-5 transition shims are gone: core.sweep no longer exports the
    parser names at all — core.cluster is the one home."""
    from repro.core import sweep as sweep_mod
    for name in ("parse_phi_table", "parse_sigma_table"):
        assert not hasattr(sweep_mod, name), name


# ---------------------------------------------------------------------------
# deployment plumbing
# ---------------------------------------------------------------------------

def test_session_build_deploys_the_tuned_plan():
    """Oracle(...).tune(p) → .build(mesh): the built cell carries exactly
    the session's plan (strategy, split, switches, optimizer)."""
    from repro.launch.build import mesh_device_count
    from repro.launch.mesh import make_host_mesh
    ses = Oracle("qwen1.5-4b", "train_4k", "host", smoke=True)
    mesh = make_host_mesh()
    cell = ses.build(mesh)
    plan = cell.meta["plan"]
    want = ses.tune(mesh_device_count(mesh),
                    model_width=mesh.shape.get("model"))
    assert plan == want
    assert cell.strategy == want.exec_strategy("train")
    assert cell.meta["opt"].zero1 == want.zero1
    assert cell.kind == "train"


def test_session_validate_smoke():
    """validate() measures the reduced model on the (single-device) host
    mesh and projects the same point — the Fig-3 loop as one method."""
    from repro.launch.mesh import make_host_mesh
    ses = Oracle("qwen1.5-4b", "train_4k", "host", smoke=True)
    pts = ses.validate(make_host_mesh(), ("data",), batch_size=4, seq=32)
    assert len(pts) == 1 and pts[0].strategy == "data"
    assert pts[0].measured_s > 0 and pts[0].projected_s > 0
