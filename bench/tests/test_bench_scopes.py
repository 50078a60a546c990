"""The program's names in a trace: scope paths from the compiled step's HLO,
the loop's ``train.*`` spans, and what ``bench/scopes.py`` reads from them,
on made-up traces, on the CPU and on traces recorded on a TPU v5e."""
import dataclasses
import gzip
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import scopes, trace
from bench.cell import metric_reader, peaks
from bench.flops import cnn as flops
from bench.trace import Op, Run, Span

DATA = Path(__file__).resolve().parent / "data"
STEP = "jit_train_step"
LOOP_SPANS = ("train.batch", "train.dispatch", "train.wait", "train.metrics")


@pytest.mark.parametrize("op_name, path", [
    ("jit(train_step)/transpose(jvp(stage1))/block0/batchnorm/jit(_var)/mul",
     "stage1/block0/batchnorm"),
    ("jit(train_step)/jvp(stem)/jit(relu)/max", "stem"),
    ("jit(train_step)/optimizer/sub", "optimizer"),
    ("jit(train_step)/jvp(jit(_std))/jit(_var)/reduce_sum", ""),
    ("jit(f)/jvp(model)/bn/reduce_sum", "model/bn"),
    ("jit(f)/vmap(jvp(a/b))/c/mul", "a/b/c"),
    ("reduce_sum", ""),
])
def test_scope_path_drops_transforms_jits_and_the_primitive(op_name, path):
    assert scopes.scope_path(op_name) == path


def test_hlo_scopes_reads_op_name_metadata():
    text = ('  %fusion.3 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop, '
            'calls=%fused_computation.3, metadata={op_type="mul" '
            'op_name="jit(s)/transpose(jvp(head))/mul" source_file="x.py"}\n'
            '  ROOT %copy.1 = f32[2]{0} copy(f32[2]{0} %fusion.3)\n')
    assert scopes.hlo_op_names(text) == {
        "fusion.3": "jit(s)/transpose(jvp(head))/mul"}
    assert scopes.hlo_scopes(text) == {"fusion.3": "head"}


def _narrow_resnet_step():
    from repro.models.cnn import ResNet, ResNetConfig
    from repro.nn.module import NULL_CTX, tree_init
    from repro.optim.optimizers import OptimizerConfig
    from repro.training.steps import make_train_step, train_state_spec
    model = ResNet(ResNetConfig("narrow", (1, 1, 1, 1), n_classes=10,
                                width=8))
    opt = OptimizerConfig(lr=1e-3)
    state = tree_init(train_state_spec(model, opt), jax.random.PRNGKey(0))
    batch = {"images": jnp.ones((2, 32, 32, 3)),
             "labels": jnp.zeros((2,), jnp.int32)}
    return jax.jit(make_train_step(model, opt, NULL_CTX)), state, batch


def test_scope_mapper_finds_every_scope_of_a_narrow_resnet_step():
    step, state, batch = _narrow_resnet_step()
    text = step.lower(state, batch).compile().as_text()
    names = scopes.hlo_op_names(text)
    paths = {n: scopes.scope_path(o) for n, o in names.items()}
    assert paths == scopes.hlo_scopes(text)
    found = set(paths.values())
    for scope in ["optimizer", "batchnorm", "stem", "head", "loss",
                  "stage0", "stage1", "stage2", "stage3", "block0"]:
        assert any(scopes.under(p, scope) for p in found), scope
    # BatchNorm's forward and its transpose alike
    bn = [names[n] for n, p in paths.items() if scopes.under(p, "batchnorm")]
    assert any("transpose(" in o for o in bn)
    assert any("transpose(" not in o and "jvp(" in o for o in bn)
    assert any(p.startswith("stage2/block0/batchnorm") for p in found)
    assert any(p.startswith("stem/batchnorm") for p in found)


def _made_up():
    """Two steps on one device. Device ops: step 1 from 0.10 to 0.40,
    step 2 from 0.60 to 0.90, each 0.10 after its run starts; a third run
    from 0.94 closes the window. Host: the benchmark's spans nested in the
    loop's."""
    spans = [
        Span("train.batch", 0.00, 0.02), Span("bench.batch", 0.00, 0.01),
        Span("train.dispatch", 0.02, 0.10), Span("bench.step", 0.03, 0.09),
        Span("train.wait", 0.10, 0.42),
        Span("train.metrics", 0.42, 0.50), Span("bench.readback", 0.43, 0.49),
        Span("train.batch", 0.50, 0.52), Span("bench.batch", 0.50, 0.51),
        Span("train.dispatch", 0.52, 0.58), Span("bench.step", 0.52, 0.57),
        Span("train.wait", 0.58, 0.91),
        Span("train.metrics", 0.91, 0.95), Span("bench.readback", 0.915, 0.94),
    ]
    ops = []
    for t in (0.10, 0.60, 1.04):
        ops += [Op(0, "convolution.1", "convolution", t, t + 0.10),
                Op(0, "fusion.2", "other", t + 0.10, t + 0.22),  # fuses a conv
                Op(0, "fusion.3", "other", t + 0.22, t + 0.27),  # BN apply
                Op(0, "fusion.4", "other", t + 0.27, t + 0.30)]  # Adam
    scope = {"convolution.1": "stage0/block0",
             "fusion.2": "stage0/block0/batchnorm",
             "fusion.3": "stage0/block0/batchnorm",
             "fusion.4": "optimizer"}
    kinds = {"convolution.1": "convolution", "fusion.2": "convolution"}
    runs = [Run(0, STEP, t, t + 0.40) for t in (0.00, 0.50, 0.94)]
    return ops, spans, runs, scope, kinds


def _layers(ops, spans, runs, scope, kinds):
    """What ``scopes.reduce`` makes of a loaded trace."""
    red = trace.reduce_ops(ops, spans, runs, STEP)
    return scopes.Layers(red, scope, kinds,
                         [s for s in spans if s.name.startswith("train.")],
                         scopes.idle_gaps(ops, spans, red))


def test_readings_of_a_made_up_trace():
    lay = _layers(*_made_up())
    assert lay.red.window == (0.00, 0.94) and lay.red.steps == 2
    r = lay.readings()
    assert r["optimizer_ms"] == pytest.approx(30.0)
    assert r["bn_ms"] == pytest.approx(50.0)       # fusion.3 alone
    # step 1's wait ends at 0.42, step 2's starts at 0.58
    assert r["loop_host_ms"] == pytest.approx(160.0)
    assert set(r) == {"optimizer_ms", "bn_ms", "loop_host_ms"}
    assert lay.by_scope() == {
        "stage0/block0": pytest.approx(0.2),
        "stage0/block0/batchnorm": pytest.approx(0.34),
        "optimizer": pytest.approx(0.06)}
    assert lay.unscoped_share == 0.0
    assert lay.seconds_under("batchnorm") == pytest.approx(0.34)
    assert lay.seconds_under("stage0") == pytest.approx(0.54)


def test_gaps_are_named_by_the_loops_spans_then_the_benchmarks():
    ops, spans, runs, scope, kinds = _made_up()
    lay = _layers(ops, spans, runs, scope, kinds)
    # the same gaps as trace.reduce_ops finds, in the same order
    assert [s for _, s in lay.gaps] == [s for _, s in lay.red.gaps]
    assert [n for n, _ in lay.red.gaps] == \
        ["bench.step", "loop", "bench.readback"]
    # 0 to 0.10: train.dispatch covers 0.08 of it, train.batch 0.02;
    # 0.40 to 0.60: train.metrics 0.08, train.dispatch 0.06, the rest less;
    # 0.90 to 0.94: train.metrics 0.03, train.wait 0.01
    assert [n for n, _ in lay.gaps] == \
        ["train.dispatch", "train.metrics", "train.metrics"]
    # where no train.* span covers a gap, the benchmark's rule stands
    assert scopes.gap_name([Span("bench.readback", 0, 1)], 0.2, 0.3) == \
        "bench.readback"
    assert scopes.gap_name(spans, 0.96, 0.99) == "loop"
    b = lay.breakdown()
    assert b["device_ops"][0] == ["fusion.2 @ stage0/block0/batchnorm",
                                  pytest.approx(0.24)]
    assert [s for _, s in b["device_ops"]] == \
        [s for _, s in lay.red.breakdown()["device_ops"]]
    assert b["idle_gaps"][0] == ["train.metrics", pytest.approx(0.2)]


def test_readings_leave_out_what_a_program_without_names_lacks():
    ops, spans, runs, _, kinds = _made_up()
    bench_only = [s for s in spans if s.name.startswith("bench.")]
    lay = _layers(ops, bench_only, runs, {}, kinds)
    assert lay.readings() == {}
    assert scopes.loop_host_s(bench_only) is None
    assert [n for n, _ in lay.gaps] == [n for n, _ in lay.red.gaps]
    assert lay.breakdown() == lay.red.breakdown()
    assert lay.unscoped_share == 1.0


def _assert_step_order(spans, n_steps: int, saves: list[int]):
    """What the benchmark reads of the loop's spans, however far ahead of
    the device it dispatches: each step opens one of each ``train.*`` span
    of a step, its own in the order batch, dispatch, wait, metrics; no two
    spans overlap; the save after step ``s`` follows that step's
    metrics."""
    spans = sorted(spans, key=lambda s: s.start)
    by = {n: [s for s in spans if s.name == n] for n in LOOP_SPANS}
    assert [len(v) for v in by.values()] == [n_steps] * len(LOOP_SPANS)
    for step in range(n_steps):
        own = [by[n][step] for n in LOOP_SPANS]
        assert all(a.end <= b.start for a, b in zip(own, own[1:])), step
    assert all(a.end <= b.start for a, b in zip(spans, spans[1:]))
    saved = [s for s in spans if s.name == "train.checkpoint"]
    assert len(saved) == len(saves)
    for c, step in zip(saved, saves):
        assert c.start >= by["train.metrics"][step].end


def test_the_loop_opens_its_spans_in_step_order(tmp_path):
    from repro.checkpoint.checkpointing import Checkpointer
    from repro.runtime.fault_tolerance import run_with_recovery

    class Loader:
        def batch_at(self, step):
            return np.full((4,), float(step), np.float32)

    step_fn = jax.jit(lambda s, b: (s + b.sum(), {"loss": s}))
    step_fn(jnp.float32(0), np.ones(4, np.float32))
    ckpt = Checkpointer(str(tmp_path / "ckpt"))
    with jax.profiler.trace(str(tmp_path / "trace")):
        run_with_recovery(step_fn, jnp.float32(0), Loader(), ckpt,
                          n_steps=3, ckpt_every=2, on_metrics=lambda s, m: None)
    _, spans, _ = trace.load(trace.find_xplane(str(tmp_path / "trace")))
    assert {s.name for s in spans} == {*LOOP_SPANS, "train.checkpoint"}
    # a save after step 1 (ckpt_every 2) and the loop's closing one
    _assert_step_order(spans, 3, saves=[1, 2])
    assert scopes.loop_host_s(spans) > 0


# A made-up loop that dispatches ``ahead`` steps before it waits on the
# oldest, as ``run_with_recovery`` does with ``ahead`` 0. Seconds:
RUN_S = 0.080          # a run of the step on the device: three ops
LAUNCH_S = 0.0001      # the device's idle between runs queued back to back
START_S = 0.002        # from a dispatch's start to its run's start
READY_S = 0.0005       # from a run's end to the end of the wait on it
HOST_S = {"train.batch": 0.001, "train.dispatch": 0.004,
          "train.metrics": 0.002}


def _loop(ahead: int, n_steps: int = 8):
    """Host spans, module runs and device ops of ``n_steps`` steps, and the
    end of the wait on step 2, inside whose metrics the trace starts."""
    spans, runs, ops, t, free = [], [], [], 0.0, 0.0

    def span(name, t0, t1=None):
        spans.append(Span(name, t0, HOST_S[name] + t0 if t1 is None else t1))
        return spans[-1].end

    for i in range(n_steps + ahead):
        if i < n_steps:                      # dispatch step i
            t = span("train.batch", t)
            start = max(t + START_S, free + LAUNCH_S)
            t = span("train.dispatch", t)
            runs.append(Run(0, STEP, start, start + RUN_S))
            ops += [Op(0, "fusion.1", "other", start, start + 0.0001),
                    Op(0, "convolution.2", "convolution", start + 0.0001,
                       start + 0.05),
                    Op(0, "fusion.3", "other", start + 0.05, start + RUN_S)]
            free = start + RUN_S
        if i >= ahead:                       # wait on step i - ahead
            t = span("train.wait", t, max(t, runs[i - ahead].end + READY_S))
            if i - ahead == 2:
                waited = t
            t = span("train.metrics", t)
    span("train.checkpoint", t, t + 0.05)
    return spans, runs, ops, waited


@pytest.mark.parametrize("ahead, window_s, steps, idle_share, host_ms", [
    # the device idles from a run's end through the wait's end, the
    # metrics, the next batch and the next dispatch's first 2 ms; the host's
    # work between two waits is the metrics, a batch and a dispatch
    (0, 4 * 0.0855, 4, 0.0055 / 0.0855, 7.0),
    # runs back to back; the run of step 3 was under way when the trace
    # started and is left out; the drain at the last step dispatches nothing
    (1, 3 * 0.0801, 3, 0.0001 / 0.0801, (3 * 7.0 + 2.0) / 4),
    (2, 3 * 0.0801, 3, 0.0001 / 0.0801, (2 * 7.0 + 2 * 2.0) / 4),
])
def test_window_holds_whole_steps_however_far_ahead_the_loop_dispatches(
        ahead, window_s, steps, idle_share, host_ms):
    spans, runs, ops, waited = _loop(ahead)
    _assert_step_order(spans, 8, saves=[7])
    # the trace starts inside step 2's metrics, as ``bench/train.py``
    # starts it in the last timed step's; it records what was under way
    # then from that moment, as a TPU's profiler does
    t0 = waited + 0.001
    spans, runs, ops = ([dataclasses.replace(x, start=max(x.start, t0))
                         for x in xs if x.end > t0]
                        for xs in (spans, runs, ops))
    lay = _layers(ops, spans, runs, {}, {})
    red = lay.red
    assert red.window[1] - red.window[0] == pytest.approx(window_s, rel=1e-9)
    assert red.steps == steps
    assert red.idle_share == pytest.approx(idle_share, rel=1e-9)
    # 1 TFLOP a step on a chip of 100 TFLOP/s: 1 / (seconds a step) %
    ctx = SimpleNamespace(trace=red, layers=lay, steps=red.steps, chips=1,
                          step_flops=1e12, peak={"bf16_flops_per_s": 1e14},
                          cell=SimpleNamespace(
                              config={"peak": "bf16_flops_per_s"}))
    assert metric_reader("step_mfu")(ctx) == \
        pytest.approx(steps / window_s, rel=1e-9)
    assert metric_reader("loop_host_ms")(ctx) == \
        pytest.approx(host_ms, rel=1e-9)


RESNET50 = {"kind": "resnet", "stage_sizes": [3, 4, 6, 3], "width": 64,
            "n_classes": 1000, "img": 224, "in_ch": 3}
NARROW = {"kind": "resnet", "stage_sizes": [1, 1, 1, 1], "width": 8,
          "n_classes": 10, "img": 32, "in_ch": 3}


def _unpacked(name: str, tmp_path) -> str:
    """The path of a fixture's trace, gunzipped into ``tmp_path`` where
    it is committed gzipped."""
    if not name.endswith(".gz"):
        return str(DATA / name)
    out = tmp_path / name[:-3]
    with gzip.open(DATA / name, "rb") as f:
        out.write_bytes(f.read())
    return str(out)


#: the tiny conv trace was recorded without its step's HLO; its step is the
#: program ``jit__lambda``
TINY_CONV_HLO = "HloModule jit__lambda\n"


def _hlo(name: str | None) -> str:
    if name is None:
        return TINY_CONV_HLO
    with gzip.open(DATA / name, "rt") as f:
        return f.read()


def _ctx(red, model, batch):
    """What ``bench/train.py`` hands the metric readers, for the arch
    ``resnet50`` and ``model``'s shapes on one v5e over the window's
    steps."""
    cell = SimpleNamespace(config={"peak": "bf16_flops_per_s"},
                           traffic={"strategy": "data",
                                    "mesh": {"data": 1, "model": 1}})
    return SimpleNamespace(
        cell=cell, arch="resnet50", trace=red, peak=peaks("TPU v5 lite"),
        flops=flops, model=model, batch=batch, chips=1, steps=red.steps,
        step_flops=flops.train_flops_per_sample(model) * batch,
        mean_step_s=0.094, itemsize=2)


@pytest.mark.parametrize("name, hlo, model, batch, pinned, by_op_s", [
    ("tiny_conv_v5e.xplane.pb", None, RESNET50, 128,
     {"device_idle_pct": 99.30151392800772, "step_mfu": 375.80674331331807,
      "conv_roofline": None, "oracle_err_pct": 66733.48055875178},
     5.868899999991739e-05),
    ("resnet_narrow_v5e.xplane.pb.gz", "resnet_narrow_v5e.hlo.txt.gz",
     NARROW, 8,
     {"device_idle_pct": 96.43728761090217, "step_mfu": 0.007441537433143594,
      "conv_roofline": 9.066477815086234,
      "oracle_err_pct": 4090.6579136453906},
     0.00014776199999878142),
])
def test_existing_metrics_read_as_before_on_the_v5e_traces(
        name, hlo, model, batch, pinned, by_op_s, tmp_path):
    """The accepted metrics and the by-op total on the committed v5e traces,
    over the window of the step's runs; this module's gaps are the accepted
    reduction's."""
    path = _unpacked(name, tmp_path)
    text = _hlo(hlo)
    red = trace.reduce(path, text)
    ctx = _ctx(red, model, batch)
    read = {n: metric_reader(n)(ctx) for n in pinned}
    assert read == pytest.approx(pinned, rel=1e-9)
    assert sum(red.by_op.values()) == pytest.approx(by_op_s, rel=1e-9)
    lay = scopes.reduce(path, text)
    assert lay.red.busy == red.busy and lay.red.by_op == red.by_op
    assert [s for _, s in lay.gaps] == [s for _, s in red.gaps]
    if hlo is None:
        assert lay.gaps == red.gaps       # no train.* spans to rename them


def test_reduction_of_a_narrow_resnet_trace_recorded_on_a_v5e(tmp_path):
    """Three steps of a narrow ResNet on one TPU v5e, driven by
    ``run_with_recovery`` with the benchmark's spans inside the loop's
    (``record_scopes_v5e.py``): three runs of ``jit_train_step``, so a
    window of two steps."""
    path = _unpacked("resnet_narrow_v5e.xplane.pb.gz", tmp_path)
    lay = scopes.reduce(path, _hlo("resnet_narrow_v5e.hlo.txt.gz"))
    assert lay.red.steps == 2
    # real TPU fusion names, with scope paths from the compiled HLO
    b = lay.breakdown()
    scoped = [n for n, _ in b["device_ops"] if " @ " in n]
    assert any("fusion" in n for n in scoped)
    assert "fusion.404 @ stem" in scoped
    assert lay.readings() == pytest.approx(
        {"optimizer_ms": 0.014109499999997999, "bn_ms": 0.001752500000052476,
         "loop_host_ms": 1.9305200000000016}, rel=1e-9)
    # at this size layout copies, outside every scope, are a third of the
    # device time; on ResNet-50 at batch 128 they are 4% (PERF.md)
    assert lay.unscoped_share == pytest.approx(0.37219312136543364, rel=1e-9)
    # the loop's spans of the whole trace, its closing save among them
    names = sorted({s.name for s in lay.spans})
    assert names == ["train.batch", "train.checkpoint", "train.dispatch",
                     "train.metrics", "train.wait"]
    # every gap over 0.5 ms falls in the loop's spans; the longest ones
    # are the next step's dispatch
    long = [n for n, s in lay.gaps if s > 5e-4]
    assert long and all(n == "train.dispatch" for n in long)
