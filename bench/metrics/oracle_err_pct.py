"""How far the planner's projected step time lies from the measured one:
100 * |T_proj - T_meas| / T_meas. T_proj is ``core.oracle.project`` for the
cell's strategy, chips and global batch on the program's preset for the
device; T_meas is the mean step of the untraced window of the same run."""


def read(ctx):
    from repro.configs import get_config
    from repro.core.autotune import stats_for_model
    from repro.core.cluster import ClusterSpec, device_system
    from repro.core.oracle import TimeModel, project

    tr = ctx.cell.traffic
    cluster = ClusterSpec.of(device_system())
    mc = get_config(ctx.arch).model
    mesh = tr["mesh"]
    proj = project(tr["strategy"], stats_for_model(mc),
                   TimeModel(cluster.system),
                   cluster.oracle_config(B=ctx.batch, D=ctx.batch),
                   p=ctx.chips, p1=mesh["data"], p2=mesh["model"])
    t_proj = proj.per_iteration()["total_s"]
    return 100.0 * abs(t_proj - ctx.mean_step_s) / ctx.mean_step_s
