"""The program's names in a trace: scope paths from the compiled step's HLO,
the loop's ``train.*`` spans, and what ``bench/scopes.py`` reads from them,
on made-up traces, on the CPU and on traces recorded on a TPU v5e."""
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
from bench.trace import Op, Span

DATA = Path(__file__).resolve().parent / "data"


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
    step 2 from 0.60 to 0.90. Host: the benchmark's spans nested in the
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
    for t in (0.10, 0.60):
        ops += [Op(0, "convolution.1", "convolution", t, t + 0.10),
                Op(0, "fusion.2", "other", t + 0.10, t + 0.22),  # fuses a conv
                Op(0, "fusion.3", "other", t + 0.22, t + 0.27),  # BN apply
                Op(0, "fusion.4", "other", t + 0.27, t + 0.30)]  # Adam
    scope = {"convolution.1": "stage0/block0",
             "fusion.2": "stage0/block0/batchnorm",
             "fusion.3": "stage0/block0/batchnorm",
             "fusion.4": "optimizer"}
    kinds = {"convolution.1": "convolution", "fusion.2": "convolution"}
    return ops, spans, scope, kinds


def _layers(ops, spans, scope, kinds):
    red = trace.reduce_ops(ops, [s for s in spans
                                 if s.name.startswith("bench.")])
    t0, t1 = red.window
    return scopes.Layers(red, scope, kinds,
                         [s for s in spans if s.name.startswith("train.")
                          and s.end > t0 and s.start < t1],
                         scopes.idle_gaps(ops, spans, red))


def test_readings_of_a_made_up_trace():
    lay = _layers(*_made_up())
    r = lay.readings(steps=2)
    assert r["optimizer_ms"] == pytest.approx(30.0)
    assert r["bn_ms"] == pytest.approx(50.0)       # fusion.3 alone
    # step 1's wait ends at 0.42, step 2's dispatch at 0.58
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
    ops, spans, scope, kinds = _made_up()
    lay = _layers(ops, spans, scope, kinds)
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
    ops, spans, _, kinds = _made_up()
    bench_only = [s for s in spans if s.name.startswith("bench.")]
    lay = _layers(ops, bench_only, {}, kinds)
    assert lay.readings(steps=2) == {}
    assert scopes.loop_host_s(bench_only) is None
    assert [n for n, _ in lay.gaps] == [n for n, _ in lay.red.gaps]
    assert lay.breakdown() == lay.red.breakdown()
    assert lay.unscoped_share == 1.0


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
    spans = scopes.load_spans(trace.find_xplane(str(tmp_path / "trace")))
    assert [s.name for s in spans] == (
        ["train.batch", "train.dispatch", "train.wait", "train.metrics"] * 2
        + ["train.checkpoint"]
        + ["train.batch", "train.dispatch", "train.wait", "train.metrics",
           "train.checkpoint"])
    assert all(a.end <= b.start for a, b in zip(spans, spans[1:]))
    assert scopes.loop_host_s(spans) > 0


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


def _hlo(name: str | None) -> str | None:
    if name is None:
        return None
    with gzip.open(DATA / name, "rt") as f:
        return f.read()


def _ctx(red, model, batch):
    """What ``bench/train.py`` hands the metric readers, for the arch
    ``resnet50`` and ``model``'s shapes on one v5e over three steps."""
    cell = SimpleNamespace(config={"peak": "bf16_flops_per_s"},
                           traffic={"strategy": "data",
                                    "mesh": {"data": 1, "model": 1}})
    return SimpleNamespace(
        cell=cell, arch="resnet50", trace=red, peak=peaks("TPU v5 lite"),
        flops=flops, model=model, batch=batch, chips=1, steps=3,
        step_flops=flops.train_flops_per_sample(model) * batch,
        mean_step_s=0.094, itemsize=2)


@pytest.mark.parametrize("name, hlo, model, batch, pinned, by_op_s", [
    ("tiny_conv_v5e.xplane.pb", None, RESNET50, 128,
     {"device_idle_pct": 99.95267875191968, "step_mfu": 25.504161233241412,
      "conv_roofline": None, "oracle_err_pct": 66733.48055875178},
     8.788199999987256e-05),
    ("resnet_narrow_v5e.xplane.pb.gz", "resnet_narrow_v5e.hlo.txt.gz",
     NARROW, 8,
     {"device_idle_pct": 97.52916606054355, "step_mfu": 0.007741140814607403,
      "conv_roofline": 13.603518853932849,
      "oracle_err_pct": 4090.6579136453906},
     0.00014776599999877238),
])
def test_existing_metrics_read_as_before_on_the_v5e_traces(
        name, hlo, model, batch, pinned, by_op_s, tmp_path):
    """The accepted metrics and the by-op total on the committed v5e traces,
    pinned to what the benchmark read before the program opened spans and
    scopes; this module's gaps are the accepted reduction's."""
    path = _unpacked(name, tmp_path)
    text = _hlo(hlo)
    red = trace.reduce(path, hlo_text=text)
    ctx = _ctx(red, model, batch)
    read = {n: metric_reader(n)(ctx) for n in pinned}
    assert read == pytest.approx(pinned, rel=1e-9)
    assert sum(red.by_op.values()) == pytest.approx(by_op_s, rel=1e-9)
    lay = scopes.reduce(path, text or "")
    assert lay.red.busy == red.busy and lay.red.by_op == red.by_op
    assert [s for _, s in lay.gaps] == [s for _, s in red.gaps]
    if text is None:
        assert lay.gaps == red.gaps       # no train.* spans to rename them


def test_reduction_of_a_narrow_resnet_trace_recorded_on_a_v5e(tmp_path):
    """Three steps of a narrow ResNet on one TPU v5e, driven by
    ``run_with_recovery`` with the benchmark's spans inside the loop's
    (``record_scopes_v5e.py``)."""
    path = _unpacked("resnet_narrow_v5e.xplane.pb.gz", tmp_path)
    lay = scopes.reduce(path, _hlo("resnet_narrow_v5e.hlo.txt.gz"))
    # real TPU fusion names, with scope paths from the compiled HLO
    b = lay.breakdown()
    scoped = [n for n, _ in b["device_ops"] if " @ " in n]
    assert any("fusion" in n for n in scoped)
    assert "fusion.404 @ stem" in scoped
    assert lay.readings(steps=3) == pytest.approx(
        {"optimizer_ms": 0.009408666666664104, "bn_ms": 0.001172000000033054,
         "loop_host_ms": 1.928254499999997}, rel=1e-9)
    # at this size layout copies, outside every scope, are a third of the
    # device time; on ResNet-50 at batch 128 they are 4% (PERF.md)
    assert lay.unscoped_share == pytest.approx(0.37225748818530596, rel=1e-9)
    names = sorted({s.name for s in lay.spans})
    assert names == ["train.batch", "train.dispatch", "train.metrics",
                     "train.wait"]
    # every gap over 0.5 ms falls in the loop's spans; the longest ones
    # are the next step's dispatch
    long = [n for n, s in lay.gaps if s > 5e-4]
    assert long and all(n == "train.dispatch" for n in long)
