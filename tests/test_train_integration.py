"""Integration: loss decreases, bit-exact resume, fault injection, stragglers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointing import Checkpointer
from repro.data.pipeline import DataConfig, ShardedLoader
from repro.models import LMConfig, TransformerLM
from repro.nn import AttentionConfig, FFNConfig
from repro.nn.module import NULL_CTX, tree_init
from repro.optim.optimizers import OptimizerConfig
from repro.runtime.fault_tolerance import (StepTimer, StragglerAlert,
                                           run_with_recovery)
from repro.training.steps import make_train_step, train_state_spec

V = 128


def tiny_lm():
    cfg = LMConfig(name="t", vocab=V, d_model=32, n_layers=2,
                   attn=AttentionConfig(32, 4, 2, 8, dtype=jnp.float32),
                   ffn=FFNConfig(32, 64, dtype=jnp.float32),
                   dtype=jnp.float32)
    return TransformerLM(cfg)


def setup(seed=0, lr=1e-2):
    model = tiny_lm()
    opt = OptimizerConfig(lr=lr, name="adamw", zero1=False)
    step = jax.jit(make_train_step(model, opt, NULL_CTX, attn_impl="plain",
                                   scan_layers=False, remat=False))
    state = tree_init(train_state_spec(model, opt), jax.random.PRNGKey(seed))
    loader = ShardedLoader(DataConfig("lm", batch=8, seq_len=32, vocab=V))
    return step, state, loader


def test_loss_decreases():
    step, state, loader = setup()
    losses = []
    for t in range(30):
        state, m = step(state, loader.batch_at(t))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.8


def test_resume_bit_exact(tmp_path):
    step, state, loader = setup()
    ck = Checkpointer(tmp_path)
    # run 10, checkpoint, run 10 more
    for t in range(10):
        state, _ = step(state, loader.batch_at(t))
    ck.save(state, 10)
    cont = state
    for t in range(10, 20):
        cont, _ = step(cont, loader.batch_at(t))
    # restore and replay — must match bit-exactly (deterministic loader)
    restored, s0 = ck.restore(state)
    assert s0 == 10
    for t in range(10, 20):
        restored, _ = step(restored, loader.batch_at(t))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 cont["params"], restored["params"])


def test_injected_failure_recovers(tmp_path):
    step, state, loader = setup()
    ck = Checkpointer(tmp_path)
    final, nstep = run_with_recovery(
        step, state, loader, ck, n_steps=25, ckpt_every=5, async_ckpt=False,
        inject_failure_at=12)
    assert nstep == 25
    assert ck.latest_step() == 25


def test_straggler_alert():
    timer = StepTimer(threshold=2.0)
    for i in range(10):
        timer.observe(i, 0.1)
    with pytest.raises(StragglerAlert):
        timer.observe(10, 1.0)


def test_straggler_outlier_kept_out_of_baseline():
    """Regression: the straggler sample used to be appended to the window
    before raising, so a run of slow steps dragged the median up until the
    detector stopped firing. Every one of a burst of stragglers must
    alert, and the median baseline must not move."""
    timer = StepTimer(window=8, threshold=3.0)
    for i in range(8):
        timer.observe(i, 0.1)
    for i in range(8, 16):   # 0.35 > 3 × 0.1 — every step is a straggler
        with pytest.raises(StragglerAlert):
            timer.observe(i, 0.35)
        assert timer.median == pytest.approx(0.1)  # baseline unpolluted


def test_step_timer_reset_clears_baseline():
    timer = StepTimer(threshold=2.0)
    for i in range(10):
        timer.observe(i, 0.1)
    timer.reset()
    assert timer.median == 0.0
    # a fresh window needs 8 samples before alerting again — a re-meshed
    # plan's first (compile-heavy) step must not trip the old baseline
    timer.observe(0, 5.0)


def test_spaced_failures_do_not_exhaust_restart_budget(tmp_path):
    """Regression: the restart budget never reset, so 4 transient failures
    spread across a long run killed it even though each was followed by
    plenty of forward progress. The budget counts CONSECUTIVE failures —
    a checkpoint newer than the previous failure's resets it."""
    step, state, loader = setup()
    ck = Checkpointer(tmp_path)
    final, nstep = run_with_recovery(
        step, state, loader, ck, n_steps=40, ckpt_every=5, async_ckpt=False,
        inject_failure_at=(7, 13, 22, 33), max_restarts=3)
    assert nstep == 40
    assert ck.latest_step() == 40


def test_restart_budget_still_bounds_crash_loops(tmp_path):
    """A fault that recurs every time the same step replays (no forward
    progress, no checkpoint) must still exhaust the budget and surface."""
    step, state, loader = setup()
    ck = Checkpointer(tmp_path)

    def inject(s):
        if s == 3:
            raise RuntimeError("deterministic fault at step 3")

    with pytest.raises(RuntimeError, match="deterministic fault"):
        run_with_recovery(step, state, loader, ck, n_steps=10,
                          ckpt_every=100, async_ckpt=False, inject=inject,
                          max_restarts=3)


def test_grad_accumulation_matches_full_batch():
    model = tiny_lm()
    opt = OptimizerConfig(lr=1e-2, name="sgd", momentum=0.0, zero1=False,
                          grad_clip=1e9)
    s1 = jax.jit(make_train_step(model, opt, NULL_CTX, accum=1,
                                 attn_impl="plain", remat=False))
    s4 = jax.jit(make_train_step(model, opt, NULL_CTX, accum=4,
                                 attn_impl="plain", remat=False))
    state = tree_init(train_state_spec(model, opt), jax.random.PRNGKey(0))
    loader = ShardedLoader(DataConfig("lm", batch=8, seq_len=16, vocab=V))
    batch = loader.batch_at(0)
    a, _ = s1(state, batch)
    b, _ = s4(state, batch)
    jax.tree.map(lambda x, y: np.testing.assert_allclose(x, y, rtol=2e-5,
                                                         atol=2e-5),
                 a["params"], b["params"])


# --- the loop keeps one step in flight -------------------------------------

class _Recorder:
    """A loader that logs each ``batch_at`` into a shared event list."""

    def __init__(self, loader, events):
        self.loader, self.events = loader, events

    def batch_at(self, step):
        self.events.append(("batch", step))
        return self.loader.batch_at(step)


def donating_setup(seed=0):
    """``setup``'s step with the state donated, as the trainers build it:
    a save of a state already donated into the next step would fail."""
    model = tiny_lm()
    opt = OptimizerConfig(lr=1e-2, name="adamw", zero1=False)
    step = jax.jit(make_train_step(model, opt, NULL_CTX, attn_impl="plain",
                                   scan_layers=False, remat=False),
                   donate_argnums=0)
    state = tree_init(train_state_spec(model, opt), jax.random.PRNGKey(seed))
    return step, state, ShardedLoader(DataConfig("lm", batch=8, seq_len=32,
                                                 vocab=V))


def plain_run(n_steps):
    """Plain stepping: the losses and the host state after each step."""
    step, state, loader = donating_setup()
    losses, states = {}, {0: jax.device_get(state)}
    for t in range(n_steps):
        state, m = step(state, loader.batch_at(t))
        losses[t] = float(m["loss"])
        states[t + 1] = jax.device_get(state)
    return losses, states


def _assert_trees_equal(a, b):
    jax.tree.map(np.testing.assert_array_equal, a, b)


def test_next_batch_is_fetched_before_this_steps_metrics(tmp_path):
    """Step s+1's batch comes before step s's ``on_metrics`` except where
    the loop drains: before each save (ckpt_every 5) and at the last step,
    where step s's metrics come first."""
    step, state, loader = donating_setup()
    events = []
    n = 12
    run_with_recovery(step, state, _Recorder(loader, events),
                      Checkpointer(tmp_path), n_steps=n, ckpt_every=5,
                      async_ckpt=False,
                      on_metrics=lambda s, m: events.append(("metrics", s)))
    assert [e for e in events if e[0] == "metrics"] == \
        [("metrics", s) for s in range(n)]
    assert [e for e in events if e[0] == "batch"] == \
        [("batch", s) for s in range(n)]
    drains = {4, 9}          # the step before the saves at 5 and 10
    for s in range(n - 1):
        fetched = events.index(("batch", s + 1))
        reported = events.index(("metrics", s))
        assert (reported < fetched) == (s in drains), (s, events)


@pytest.mark.parametrize("async_ckpt", [False, True])
def test_checkpoints_hold_the_state_after_exactly_k_steps(tmp_path,
                                                          async_ckpt):
    """Saves at 5, 10 and 15 hold plain stepping's state bit for bit, and
    ``on_metrics`` sees plain stepping's losses, with the state donated
    into the step dispatched ahead."""
    ref_losses, ref_states = plain_run(15)
    step, state, loader = donating_setup()
    ck = Checkpointer(tmp_path, keep=10)
    losses = {}
    final, nstep = run_with_recovery(
        step, state, loader, ck, n_steps=15, ckpt_every=5,
        async_ckpt=async_ckpt,
        on_metrics=lambda s, m: losses.__setitem__(s, float(m["loss"])))
    assert nstep == 15 and ck.completed_steps() == [5, 10, 15]
    assert losses == ref_losses
    for k in (5, 10, 15):
        restored, s0 = ck.restore(ref_states[k], step=k)
        assert s0 == k
        _assert_trees_equal(restored, ref_states[k])
    _assert_trees_equal(jax.device_get(final), ref_states[15])


def test_injected_failures_replay_to_the_faultless_trajectory(tmp_path):
    """Failures at 7 and 13 fire while the step before each is in flight;
    that step is finished first, then the restart path replays from the
    checkpoint: losses and final params equal a run without faults."""
    ref_losses, ref_states = plain_run(16)
    step, state, loader = donating_setup()
    events, losses = [], {}

    def on_metrics(s, m):
        events.append(("metrics", s))
        losses[s] = float(m["loss"])

    final, nstep = run_with_recovery(
        step, state, _Recorder(loader, events), Checkpointer(tmp_path),
        n_steps=16, ckpt_every=5, async_ckpt=False,
        inject_failure_at=(7, 13), on_metrics=on_metrics)
    assert nstep == 16
    # step 6 was in flight when step 7's fault fired, and finished first
    assert events.index(("batch", 7)) < events.index(("metrics", 6))
    assert events.count(("metrics", 6)) == 2      # replayed from step 5
    assert losses == ref_losses
    _assert_trees_equal(jax.device_get(final["params"]),
                        ref_states[16]["params"])


def test_straggler_escalation_saves_the_state_after_the_straggling_step(
        tmp_path):
    """Simulated stragglers at 9 and 10 exhaust a patience of 2: the loop
    drains before step 11, saves the state after 11 steps and raises
    ``SliceLost`` at 11, with nothing dispatched past it."""
    from repro.runtime.fault_tolerance import SliceLost
    _, ref_states = plain_run(11)
    step, state, loader = donating_setup()
    events = []
    ck = Checkpointer(tmp_path)
    with pytest.raises(SliceLost) as info:
        run_with_recovery(
            step, state, _Recorder(loader, events), ck, n_steps=20,
            ckpt_every=100, async_ckpt=False, straggler_patience=2,
            inject=lambda s: 9.9 if s in (9, 10) else 0.01,
            on_metrics=lambda s, m: events.append(("metrics", s)))
    assert info.value.cause == "straggler"
    assert info.value.step == 11 and ck.latest_step() == 11
    assert max(s for kind, s in events if kind == "batch") == 10
    restored, _ = ck.restore(ref_states[11])
    _assert_trees_equal(restored, ref_states[11])
