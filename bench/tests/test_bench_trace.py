"""The trace reduction: interval arithmetic and attribution on made-up
ops, and the whole reduction on a small trace recorded on a TPU v5e."""
from pathlib import Path

import pytest

from bench import trace
from bench.trace import Op, Run, Span

DATA = Path(__file__).resolve().parent / "data"


def test_union_subtract_and_clip():
    assert trace.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert trace.subtract([(0, 10)], [(1, 2), (1.5, 3), (8, 12)]) == \
        [(0, 1), (3, 8)]
    assert trace.subtract([(0, 1), (2, 3)], []) == [(0, 1), (2, 3)]
    assert trace.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]
    assert trace.length([(0, 1.5), (2, 3)]) == 2.5


STEP = "jit_step"


def _made_up():
    spans = [Span("bench.batch", 0.0, 0.1), Span("bench.step", 0.1, 0.3),
             Span("bench.readback", 0.9, 1.0)]
    # the step's program runs on device 0 from 0.0 and again from 1.0: the
    # window holds one step; a run of another program does not bound it
    runs = [Run(0, STEP, 0.0, 0.8), Run(0, "jit_other", 0.85, 0.9),
            Run(0, STEP, 1.0, 1.5), Run(1, STEP, 0.0, 0.5)]
    ops = [
        # device 0: a convolution, a fusion over it, an all-reduce half
        # hidden under compute, and an idle gap while the host reads back
        Op(0, "convolution.1", "convolution", 0.2, 0.5),
        Op(0, "fusion.2", "fusion", 0.4, 0.6),
        Op(0, "all-reduce.3", "collective", 0.5, 0.8),
        # device 1: busy less, and one op outside the window
        Op(1, "convolution.1", "convolution", 0.2, 0.4),
        Op(1, "all-reduce.3", "collective", 0.4, 0.5),
        Op(1, "fusion.9", "fusion", 1.2, 1.5),
        # device 0: the step's next run, past the window
        Op(0, "convolution.1", "convolution", 1.2, 1.5),
    ]
    return ops, spans, runs


def test_reduction_of_made_up_ops():
    ops, spans, runs = _made_up()
    red = trace.reduce_ops(ops, spans, runs, STEP)
    assert red.window == (0.0, 1.0)
    assert red.steps == 1
    assert red.busy == {0: pytest.approx(0.6), 1: pytest.approx(0.3)}
    assert red.busy_s == pytest.approx(0.45)
    assert red.idle_share == pytest.approx(0.55)
    assert red.conv_s == pytest.approx((0.3 + 0.2) / 2)
    assert red.collective_s == pytest.approx((0.3 + 0.1) / 2)
    # device 0: the all-reduce runs alone from 0.6 to 0.8; device 1: alone
    # for all its 0.1
    assert red.collective_exposed_s == pytest.approx((0.2 + 0.1) / 2)
    gaps = sorted(red.gaps, key=lambda g: -g[1])
    # the longest: device 1 idle from 0.5 to 1.0, most of it between spans
    assert gaps[0] == ("loop", pytest.approx(0.5))
    # both devices idle from 0.0 to 0.2: half in bench.batch, half in
    # bench.step, and the earlier wins the tie
    assert gaps.count(("bench.batch", pytest.approx(0.2))) == 2
    b = red.breakdown()
    assert b["device_ops"][0][0] == "convolution.1"
    assert len(b["idle_gaps"]) <= 10


def test_window_needs_the_benchmarks_spans():
    """The window needs two runs of the step's program on the first device;
    the benchmark's spans no longer bound it."""
    ops, spans, runs = _made_up()
    for few in ([], runs[:1], runs[1:2] + runs[3:]):
        with pytest.raises(ValueError):
            trace.reduce_ops(ops, spans, few, STEP)
    with pytest.raises(ValueError):
        trace.reduce_ops(ops, spans, runs, "jit_missing")
    assert trace.reduce_ops(ops, [], runs, STEP).window == (0.0, 1.0)


def test_reduction_of_a_trace_recorded_on_a_v5e():
    """Three steps of a small conv step on one TPU v5e, each inside
    ``bench.batch`` / ``bench.step`` / ``bench.readback`` spans; the
    readback span sleeps 2 ms, so the device idles through it."""
    ops, spans, runs = trace.load(str(DATA / "tiny_conv_v5e.xplane.pb"))
    assert {o.device for o in ops} == {0}
    assert all("=" not in o.name for o in ops)      # short HLO names
    assert sorted({s.name for s in spans}) == [
        "bench.batch", "bench.readback", "bench.step"]
    # each run of the conv step follows a run of a multiply
    assert [r.module for r in runs] == ["jit_multiply", "jit__lambda"] * 3
    red = trace.reduce_ops(ops, spans, runs, "jit__lambda")
    assert red.steps == 2
    assert red.window == (runs[1].start, runs[5].start)
    assert 0 < red.busy_s < red.window_s
    assert 0 < red.idle_share < 1
    gaps = dict(sorted(red.gaps, key=lambda g: g[1]))
    assert gaps["bench.readback"] >= 0.002
    assert red.collective_s == 0
    assert sum(red.by_op.values()) >= red.busy_s
    assert len(red.breakdown()["device_ops"]) == 10


def test_hlo_kinds_finds_convolutions_inside_fusions():
    import jax
    import jax.numpy as jnp

    def f(x, w):
        y = jax.lax.conv_general_dilated(x, w, (1, 1), "SAME",
                                         dimension_numbers=("NHWC", "HWIO",
                                                            "NHWC"))
        return jnp.sum(jax.nn.relu(y * 2.0))

    text = jax.jit(jax.grad(f)).lower(jnp.ones((2, 8, 8, 4)),
                                      jnp.ones((3, 3, 4, 8))).compile().as_text()
    assert trace.step_module(text) == "jit_f"
    kinds = trace.hlo_kinds(text)
    assert list(kinds.values()).count("convolution") >= 2   # fwd and dgrad
    assert trace.op_name("%fusion.3 = f32[2] fusion(f32[2] %a), kind=kLoop") \
        == "fusion.3"
    assert trace.category("fusion.3", {"fusion.3": "convolution"}) == \
        "convolution"
    assert trace.category("all-reduce-done.2") == "collective"
