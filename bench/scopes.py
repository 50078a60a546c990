"""Read the program's own names in a profiler trace: the scope path of each
device op, and the ``train.*`` spans of the training loop.

The program scopes its step with ``jax.named_scope`` (``optimizer``,
``batchnorm``, ``stem``, ``stage{s}/block{b}``, ``head``, ``loss``): the
names reach the compiled HLO as each instruction's ``op_name`` metadata, and
a fusion keeps its root's. ``runtime.fault_tolerance.run_with_recovery``
opens ``train.batch``, ``train.dispatch``, ``train.wait``, ``train.metrics``
and ``train.checkpoint`` spans on the host, on the device trace's clock.

This extends ``bench/trace.py`` and changes none of its numbers: the same
ops, window, steps and per-op seconds, to which it adds

- device seconds per scope path, and the three readings of the layers the
  scopes and spans mark: ``optimizer_ms``, ``bn_ms`` and ``loop_host_ms``;
- each idle gap named by the ``train.*`` span that covers most of it, else
  as ``trace.host_span_over`` names it;
- a breakdown whose ops carry their scope path.

    python3 -m bench.scopes TRACE.xplane.pb STEP_HLO.txt[.gz]
"""
from __future__ import annotations

import argparse
import gzip
import json
import re
import sys
from dataclasses import dataclass, field

from bench import trace
from bench.trace import TRAIN_PREFIX, Op, Span

OP_NAME = re.compile(r'%([\w.\-]+) = .*\bmetadata=\{[^}]*?op_name="([^"]*)"')
WRAPPER = re.compile(r"^([\w\-]+)\((.*)\)$")


def _segments(path: str) -> list[str]:
    """``path`` split at each ``/`` outside parentheses."""
    out, depth, cur = [], 0, ""
    for ch in path:
        depth += (ch == "(") - (ch == ")")
        if ch == "/" and depth == 0:
            out.append(cur)
            cur = ""
        else:
            cur += ch
    return out + [cur]


def _scopes_of(segment: str) -> list[str]:
    """The named scopes in one segment of an ``op_name``: a transform's
    wrapper (``jvp(stage1)``, ``transpose(jvp(stem))``) gives what it
    wraps; a ``jit(...)``, the name of a jitted function, gives none."""
    m = WRAPPER.match(segment)
    if m is None:
        return [segment] if segment else []
    if m.group(1) in ("jit", "pjit"):
        return []
    return [s for part in _segments(m.group(2)) for s in _scopes_of(part)]


def scope_path(op_name: str) -> str:
    """The named scopes of an HLO instruction's ``op_name``, outermost
    first: ``jit(train_step)/transpose(jvp(stage1))/block0/batchnorm/
    jit(_var)/mul`` gives ``stage1/block0/batchnorm``. The last segment,
    the primitive, is dropped; an instruction outside every scope gives
    ``""``."""
    segs = _segments(op_name)[:-1]
    return "/".join(s for seg in segs for s in _scopes_of(seg))


def hlo_op_names(hlo_text: str) -> dict:
    """Instruction name -> its ``op_name`` metadata, for every instruction
    of a compiled program's HLO text that has one."""
    return {m.group(1): m.group(2) for m in OP_NAME.finditer(hlo_text)}


def hlo_scopes(hlo_text: str) -> dict:
    """Instruction name -> scope path (``scope_path``) for the
    instructions of a compiled program's HLO text; the trace names its ops
    by these instructions, as ``trace.hlo_kinds`` uses them."""
    return {n: scope_path(o) for n, o in hlo_op_names(hlo_text).items()}


def under(path: str, scope: str) -> bool:
    """Whether a scope path lies inside the named scope ``scope``."""
    return scope in path.split("/")


def gap_name(spans: list[Span], t0: float, t1: float) -> str:
    """What the host was doing through an idle gap (t0, t1): the
    ``train.*`` span that covers most of it; where none covers any of it,
    what ``trace.host_span_over`` makes of the ``bench.*`` spans."""
    cover: dict[str, list] = {}
    for s in spans:
        if s.name.startswith(TRAIN_PREFIX) and s.end > t0 and s.start < t1:
            cover.setdefault(s.name, []).append(
                (max(s.start, t0), min(s.end, t1)))
    if cover:
        return max(cover, key=lambda n: trace.length(trace.union(cover[n])))
    return trace.host_span_over(
        [s for s in spans if s.name.startswith(trace.SPAN_PREFIX)], t0, t1)


def idle_gaps(ops: list[Op], spans: list[Span], red: trace.Reduction
              ) -> list[tuple[str, float]]:
    """The idle gaps of ``trace.reduce_ops``, on the same devices and
    window and in the same order, each named by ``gap_name``."""
    t0, t1 = red.window
    gaps = []
    for d in red.devices:
        busy = trace.union(trace.clip(
            [(o.start, o.end) for o in ops if o.device == d], t0, t1))
        edges = [(t0, t0)] + busy + [(t1, t1)]
        for (_, e), (s, _) in zip(edges, edges[1:]):
            if s > e:
                gaps.append((gap_name(spans, e, s), s - e))
    return gaps


def loop_host_s(spans: list[Span]) -> float | None:
    """Mean over consecutive steps of (start of step s+1's ``train.wait`` -
    end of step s's): the host's own work per step, whatever order the loop
    opens its spans in. A loop that blocks on each step's loss puts it on
    the chip's idle time; one that dispatches ahead overlaps it with the
    step in flight. None without two waits."""
    waits = sorted((s for s in spans if s.name == "train.wait"),
                   key=lambda s: s.start)
    turns = [b.start - a.end for a, b in zip(waits, waits[1:])]
    return sum(turns) / len(turns) if turns else None


@dataclass
class Layers:
    """A trace reduced by ``trace.reduce_ops``, with the program's names."""

    red: trace.Reduction
    scopes: dict                      # op name -> scope path
    kinds: dict                       # op name -> kind (``trace.hlo_kinds``)
    spans: list = field(default_factory=list)   # train.* spans of the trace
    gaps: list = field(default_factory=list)    # (gap_name, seconds)

    def by_scope(self) -> dict:
        """Scope path -> device seconds per device over the window; ``""``
        holds the ops that no scope covers."""
        out: dict[str, float] = {}
        for n, s in self.red.by_op.items():
            p = self.scopes.get(n, "")
            out[p] = out.get(p, 0.0) + s
        return out

    def seconds_under(self, scope: str, convolutions: bool = True) -> float:
        """Device seconds per device of the ops inside ``scope``; with
        ``convolutions`` False, less those that are or fuse a convolution."""
        return sum(s for n, s in self.red.by_op.items()
                   if under(self.scopes.get(n, ""), scope)
                   and (convolutions
                        or trace.category(n, self.kinds) != "convolution"))

    @property
    def unscoped_share(self) -> float:
        """Share of the window's device op time that no scope covers."""
        total = sum(self.red.by_op.values())
        return self.by_scope().get("", 0.0) / total if total else 0.0

    def readings(self) -> dict:
        """Per step of the window and per chip, in ms: ``optimizer_ms``,
        the ops under ``optimizer``; ``bn_ms``, the ops under ``batchnorm``
        that neither are nor fuse a convolution (those count with the
        convolutions); ``loop_host_ms`` (``loop_host_s``). A reading with
        nothing to read, as on a program without the scopes or spans, is
        left out."""
        out = {"optimizer_ms": self.seconds_under("optimizer"),
               "bn_ms": self.seconds_under("batchnorm", convolutions=False)}
        out = {k: 1e3 * v / self.red.steps for k, v in out.items() if v > 0}
        host = loop_host_s(self.spans)
        if host is not None:
            out["loop_host_ms"] = 1e3 * host
        return out

    def breakdown(self, top: int = 10) -> dict:
        """``Reduction.breakdown`` with each op's scope path after its name
        (``multiply_reduce_fusion.4 @ stage3/block2/batchnorm``) and the
        gaps named by ``gap_name``; seconds and order unchanged."""
        b = self.red.breakdown(top)
        for row in b["device_ops"]:
            if self.scopes.get(row[0]):
                row[0] = f"{row[0]} @ {self.scopes[row[0]]}"
        b["idle_gaps"] = [[n, s] for n, s in
                          sorted(self.gaps, key=lambda g: -g[1])[:top]]
        return b


def reduce(path: str, hlo_text: str, n_devices: int | None = None) -> Layers:
    """``trace.reduce`` of a trace and its step's compiled HLO text, with
    the program's scopes and spans."""
    kinds = trace.hlo_kinds(hlo_text)
    ops, spans, runs = trace.load(path, kinds)
    red = trace.reduce_ops(ops, spans, runs, trace.step_module(hlo_text),
                           n_devices)
    return Layers(red, hlo_scopes(hlo_text), kinds,
                  [s for s in spans if s.name.startswith(TRAIN_PREFIX)],
                  idle_gaps(ops, spans, red))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="the .xplane.pb of a traced run")
    ap.add_argument("hlo", help="the traced step's compiled HLO text "
                                "(compiled.as_text()), optionally gzipped")
    args = ap.parse_args(argv)
    opener = gzip.open if args.hlo.endswith(".gz") else open
    with opener(args.hlo, "rt") as f:
        hlo_text = f.read()
    lay = reduce(args.trace, hlo_text)
    steps = lay.red.steps
    print(json.dumps({"steps": steps, "window_s": lay.red.window_s,
                      "readings": lay.readings(),
                      "unscoped_share": lay.unscoped_share,
                      "by_scope_ms": {k: 1e3 * v / steps for k, v in
                                      sorted(lay.by_scope().items())},
                      "breakdown": lay.breakdown()}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
