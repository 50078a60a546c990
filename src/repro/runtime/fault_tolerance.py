"""Fault tolerance & elasticity for the training runtime.

Three mechanisms (scaled-out designs documented inline; all are exercised by
tests on virtual devices — tests/test_train_integration.py for the loop,
tests/test_chaos.py for the full elastic path):

* **checkpoint/restart** — ``run_with_recovery`` drives the train loop with
  periodic (optionally async) checkpoints; a transient step-time exception
  triggers restore-from-latest and replay. The data pipeline is (seed, step)-
  addressable so the resumed stream is identical. The restart budget counts
  *consecutive* failures: forward progress (a checkpoint newer than the one
  seen at the previous failure) resets it, so spaced transient faults over a
  long run never exhaust it while a crash loop still aborts. The loop keeps
  one step in flight: step s+1 is dispatched before the host waits on step
  s's loss, so the host's per-step work overlaps the device's. It drains
  (finishes the step in flight before dispatching another) before each
  save, at the last step, before an alert that may escalate, and before
  handling a fault of the next step.
* **straggler mitigation** — ``StepTimer`` keeps a ring buffer of step times;
  a step slower than ``threshold × median`` raises a StragglerAlert (the
  outlier sample stays OUT of the window, so one slow step cannot inflate
  the median and mask the next). In a synchronous SPMD job the remedy at
  scale is checkpoint-and-remesh around the slow host: after
  ``straggler_patience`` consecutive alerts the loop checkpoints and raises
  ``SliceLost(cause="straggler")`` for runtime/elastic.py to handle.
* **elastic re-mesh** — ``remesh_state`` re-shards a state pytree from ANY
  source placement onto a target (mesh, Rules) pair — plan-to-plan: leaves
  round-trip through the host, so arbitrary source→target mesh shapes and
  any strategy pair the Rules tables cover work, bit-exactly (pinned by
  tests/test_remesh_properties.py). ``SliceLost`` is the event that drives
  it: runtime/elastic.py derives the surviving ClusterSpec, re-runs the
  tuner, and resumes from the checkpoint under the new plan's shardings.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from ..checkpoint.checkpointing import Checkpointer
from ..nn.module import Rules, tree_shardings


class StragglerAlert(RuntimeError):
    def __init__(self, step: int, step_s: float, median_s: float):
        self.step, self.step_s, self.median_s = step, step_s, median_s
        super().__init__(
            f"step {step} took {step_s:.3f}s vs median {median_s:.3f}s")


class SliceLost(RuntimeError):
    """A device slice is gone — the surviving machine is a *different*
    ClusterSpec, so recovery is a planning problem, not just a restart.

    Raised by fault injection (standing in for the device watchdog) on
    slice death, and by ``run_with_recovery`` itself when stragglers exceed
    the patience budget (``cause="straggler"`` — graceful: the state was
    checkpointed first). ``dim``/``count`` name the torus dimension that
    lost ``count`` hyperplanes, feeding ``ClusterSpec.degraded``.
    """

    def __init__(self, step: int, *, dim: int = 0, count: int = 1,
                 cause: str = "failure", reason: str | None = None):
        self.step, self.dim, self.count, self.cause = step, dim, count, cause
        self.reason = reason or f"slice lost (torus dim {dim})"
        super().__init__(f"step {step}: {self.reason}")


@dataclass
class StepTimer:
    window: int = 32
    threshold: float = 3.0
    _times: deque = None

    def __post_init__(self):
        self._times = deque(maxlen=self.window)

    def observe(self, step: int, step_s: float):
        if len(self._times) >= 8:
            med = float(np.median(self._times))
            if step_s > self.threshold * med:
                # the straggler sample must NOT enter the window: appended,
                # a run of slow steps would drag the median up until the
                # detector stops firing on the very condition it watches
                raise StragglerAlert(step, step_s, med)
        self._times.append(step_s)

    def reset(self):
        """Drop the baseline — after an elastic re-mesh the plan (and its
        step time, including a fresh compile) has nothing in common with
        the old window."""
        self._times.clear()

    @property
    def median(self) -> float:
        return float(np.median(self._times)) if self._times else 0.0


def remesh_state(state, spec_tree=None, new_mesh=None, rules: Rules | None = None,
                 *, shardings=None):
    """Re-shard a state pytree plan-to-plan: any source placement (sharded
    on some mesh, single-device, or host numpy) → a target described either
    by ``(spec_tree, new_mesh, rules)`` or by a precomputed per-leaf
    ``shardings`` tree (e.g. the split params/opt/step shardings of
    runtime/elastic.py, where ZeRO-1 optimizer state rides its own rules).

    Arbitrary source→target mesh pairs work because every leaf round-trips
    through the host: ``device_get`` reassembles the full array from
    whatever sharding it had, ``device_put`` lays it out under the new one.
    Pure data movement — bit-exact per leaf (tests/test_remesh_properties).
    """
    if shardings is None:
        shardings = tree_shardings(spec_tree, new_mesh, rules)
    return jax.tree.map(
        lambda x, s: jax.device_put(np.asarray(jax.device_get(x)), s),
        state, shardings)


def run_with_recovery(step_fn, state, loader, ckpt: Checkpointer, *,
                      n_steps: int, start_step: int = 0,
                      ckpt_every: int = 50, async_ckpt: bool = True,
                      max_restarts: int = 3, timer: StepTimer | None = None,
                      inject_failure_at=None, inject=None,
                      straggler_patience: int | None = None,
                      skeleton=None, restore_shardings=None,
                      on_metrics=None):
    """Fault-tolerant train loop: checkpoint, detect, restore, replay.

    ``inject_failure_at`` simulates node failures (an int or an iterable of
    steps; each fires once) — the restart path end-to-end. ``inject``, when
    given, is called with the step index before it executes and may raise
    (``SliceLost`` propagates to the elastic controller, anything else
    takes the restart path) or return a simulated step duration in seconds
    for the straggler timer (tests/helpers/fault_plan.py builds these).

    ``straggler_patience``: after that many consecutive StragglerAlerts the
    loop checkpoints the (healthy, just slow) state and raises
    ``SliceLost(cause="straggler")`` — the checkpoint-and-remesh-around-
    the-slow-host escalation runtime/elastic.py drives. None (default):
    log-and-continue, the single-host behavior.

    ``skeleton``/``restore_shardings`` shape the restore: elastic restarts
    restore onto a NEW mesh, so they pass the state spec tree and the
    re-tuned plan's shardings; by default the live state is the skeleton
    and leaves land wherever ``device_put`` defaults.

    The loop keeps one step in flight: in steady state it fetches step
    s+1's batch and dispatches s+1 on step s's output state, then waits on
    step s's loss and runs the timer and ``on_metrics`` for step s, so the
    device runs s+1 while the host does its per-step work. It drains —
    waits on the step in flight and finishes it before dispatching another
    — where step s's output state is needed or no step s+1 follows: before
    each save (the state is donated into the next step), at the last step,
    and when step s's alert may escalate (``strikes`` one short of
    ``straggler_patience``). A fault of step s+1 (``inject``, an injected
    failure or an error while dispatching it) is handled once step s has
    finished; an error in step s's own wait discards s+1 and takes the
    restart path. The timer's step time runs from the end of the previous
    step's wait to the end of this one's, or from the step's batch where
    the loop drained before it.

    Each step opens profiler spans, in order: ``train.batch`` (the
    loader), ``train.dispatch`` (the call into ``step_fn``), ``train.wait``
    (until the loss is ready), ``train.metrics`` (``on_metrics``), and
    ``train.checkpoint`` around each save and restore; step s+1's batch and
    dispatch come before step s's wait. They cost about a microsecond
    without an active ``jax.profiler`` session, and share the device
    trace's clock with one.
    """
    timer = timer or StepTimer()
    step = start_step
    restarts = 0
    seen_failure = False
    budget_anchor = None     # ckpt.latest_step() at the previous failure
    fail_steps = ({int(inject_failure_at)}
                  if isinstance(inject_failure_at, int)
                  else set(int(s) for s in inject_failure_at or ()))
    fired: set[int] = set()
    strikes = 0

    def dispatch(s, state):
        """Step ``s``'s hook, batch and dispatch: the output state and the
        step in flight, ``(metrics, t0, fake_dt)``."""
        fake_dt = inject(s) if inject is not None else None
        t0 = time.perf_counter()
        with TraceAnnotation("train.batch"):
            batch = loader.batch_at(s)
        if s in fail_steps and s not in fired:
            fired.add(s)
            raise RuntimeError(f"injected node failure at step {s}")
        with TraceAnnotation("train.dispatch"):
            state, metrics = step_fn(state, batch)
        return state, (metrics, t0, fake_dt)

    inflight = None          # step `step`, dispatched, not yet waited on
    while step < n_steps:
        try:
            if inflight is None:
                state, inflight = dispatch(step, state)
            # drain where step `step`'s output state is saved or no step
            # follows it; otherwise dispatch the next step on that state
            nxt = step + 1
            drain = (nxt >= n_steps or nxt % ckpt_every == 0
                     or (straggler_patience is not None
                         and strikes >= straggler_patience - 1))
            ahead = fault = None
            if not drain:
                try:
                    state, ahead = dispatch(nxt, state)
                except Exception as e:  # noqa: BLE001 — raised after `step`
                    fault = e
            metrics, t0, fake_dt = inflight
            with TraceAnnotation("train.wait"):
                jax.block_until_ready(metrics["loss"])
            waited = time.perf_counter()
            dt = fake_dt if fake_dt is not None else waited - t0
            if ahead is not None:
                ahead = (ahead[0], waited, ahead[2])
            escalate = None
            try:
                timer.observe(step, dt)
                strikes = 0
            except StragglerAlert as e:
                # synchronous SPMD: log-and-continue; repeated alerts
                # escalate to checkpoint-and-remesh around the slow host
                print(f"[straggler] {e}")
                strikes += 1
                if straggler_patience is not None \
                        and strikes >= straggler_patience:
                    escalate = e
            if on_metrics:
                with TraceAnnotation("train.metrics"):
                    on_metrics(step, metrics)
            step += 1
            inflight = ahead
            if escalate is not None:
                # graceful: the state is intact, persist it before leaving
                with TraceAnnotation("train.checkpoint"):
                    ckpt.wait()
                    ckpt.save(state, step)
                raise SliceLost(
                    step, cause="straggler",
                    reason=f"{strikes} consecutive stragglers "
                           f"(last: {escalate})")
            if step % ckpt_every == 0:
                with TraceAnnotation("train.checkpoint"):
                    ckpt.save(state, step, blocking=not async_ckpt)
            if fault is not None:
                raise fault
        except (StragglerAlert, SliceLost):
            raise
        except Exception as e:  # noqa: BLE001 — restart path
            inflight = None      # a step dispatched ahead is discarded
            ckpt.wait()          # an in-flight async save may still commit
            latest = ckpt.latest_step()
            key = -1 if latest is None else latest
            if seen_failure and key > (budget_anchor
                                       if budget_anchor is not None else -1):
                restarts = 0     # forward progress since the last failure
            seen_failure, budget_anchor = True, latest
            restarts += 1
            if restarts > max_restarts:
                raise
            print(f"[recovery] {e!r} → restoring from "
                  f"{'step ' + str(latest) if latest is not None else 'init'}")
            if latest is not None:
                with TraceAnnotation("train.checkpoint"):
                    state, step = ckpt.restore(
                        skeleton if skeleton is not None else state,
                        shardings=restore_shardings)
            else:
                step = start_step
    with TraceAnnotation("train.checkpoint"):
        ckpt.wait()
        ckpt.save(state, step)
    return state, step
