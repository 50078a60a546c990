"""Reduce a profiler trace to device busy and idle time, op time by kind,
exposed collective time, and idle gaps attributed to the host's spans.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX's ``ProfileData``. Device operations are the events of the ``XLA Ops``
line of each ``/device:TPU:<n>`` plane, and runs of compiled programs those
of its ``XLA Modules`` line; host spans are the benchmark's ``bench.*`` and
the program's ``train.*`` annotations on the host plane.

The window is bounded by the runs of the traced step's program (the module
that its compiled HLO names) on the first device: from the start of its
first whole run in the trace to the start of its last. The steps in the
window are the runs that start inside it, each with the idle time that
follows it, so the window holds whole steps however far ahead of the
device the host dispatches; a run that was under way when the trace
started is left out. Host spans only name the idle gaps.
"""
from __future__ import annotations

import bisect
import glob
import re
from dataclasses import dataclass, field

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
TRAIN_PREFIX = "train."
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|send|recv")
CONVOLUTION = re.compile(r"convolution")


@dataclass
class Op:
    device: int
    name: str
    category: str
    start: float          # seconds on the trace's clock
    end: float


@dataclass
class Span:
    name: str
    start: float
    end: float


@dataclass
class Run:
    """One run of a compiled program on a device."""

    device: int
    module: str           # ``jit_train_step``: the HLO module's name
    start: float
    end: float


def find_xplane(directory: str) -> str:
    found = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {directory}, "
                                f"found {found}")
    return found[0]


def hlo_kinds(hlo_text: str) -> dict:
    """Op name -> ``convolution`` or ``collective`` for the instructions of
    a compiled program's HLO text: a convolution, a collective, or a fusion
    whose fused computation holds one."""
    comps: dict[str, str] = {}
    current = None
    for line in hlo_text.splitlines():
        head = re.match(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", line)
        if head and "=" not in line.split("{")[0]:
            current = head.group(1)
            comps[current] = ""
        elif current is not None:
            comps[current] += line + "\n"
    kinds = {}
    for body in comps.values():
        for m in re.finditer(r"%([\w.\-]+) = [^\n]*?\b([a-z\-]+)\(([^\n]*)",
                             body):
            name, opcode, rest = m.groups()
            if opcode == "convolution":
                kinds[name] = "convolution"
            elif COLLECTIVE.match(opcode):
                kinds[name] = "collective"
            elif opcode == "fusion":
                callee = re.search(r"calls=%?([\w.\-]+)", rest)
                inner = comps.get(callee.group(1), "") if callee else ""
                if " convolution(" in inner:
                    kinds[name] = "convolution"
                elif COLLECTIVE.search(inner):
                    kinds[name] = "collective"
    return kinds


def step_module(hlo_text: str) -> str:
    """The module name of a compiled program's HLO text (``HloModule
    jit_train_step, ...``), which names its runs in the trace."""
    m = re.match(r"\s*HloModule\s+([\w.\-]+)", hlo_text)
    if m is None:
        raise ValueError("the HLO text names no module")
    return m.group(1)


def op_name(event_name: str) -> str:
    """``fusion.12`` of a TPU op event named by its whole HLO instruction,
    ``%fusion.12 = f32[...] fusion(...), ...``; other names as they are."""
    m = re.match(r"^%?([\w.\-]+)\s*=", event_name)
    return m.group(1) if m else event_name


def category(name: str, kinds: dict | None = None) -> str:
    """The op's kind: what the compiled program's HLO says of the op, else
    ``collective``, ``convolution`` or ``other`` from the op's name. (A TPU
    trace names each op by its HLO instruction and gives no category.)"""
    if kinds and name in kinds:
        return kinds[name]
    if COLLECTIVE.search(name):
        return "collective"
    if CONVOLUTION.search(name):
        return "convolution"
    return "other"


def is_collective(op: Op) -> bool:
    return op.category == "collective"


def is_convolution(op: Op) -> bool:
    return op.category == "convolution"


def load(path: str, kinds: dict | None = None
         ) -> tuple[list[Op], list[Span], list[Run]]:
    """The device ops, the ``bench.*`` and ``train.*`` host spans in order
    of their start, and the program runs of a trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans, runs = [], [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    t0 = ev.start_ns * 1e-9
                    t1 = t0 + ev.duration_ns * 1e-9
                    if line.name == MODULES_LINE:
                        # ``jit_train_step(<fingerprint>)``
                        runs.append(Run(dev, ev.name.split("(")[0], t0, t1))
                    else:
                        name = op_name(ev.name)
                        ops.append(Op(dev, name, category(name, kinds),
                                      t0, t1))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith((SPAN_PREFIX, TRAIN_PREFIX)):
                        t0 = ev.start_ns * 1e-9
                        spans.append(Span(ev.name, t0,
                                          t0 + ev.duration_ns * 1e-9))
    return ops, sorted(spans, key=lambda s: s.start), runs


def union(intervals) -> list[tuple[float, float]]:
    """Merge overlapping (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def clip(intervals, t0: float, t1: float) -> list[tuple[float, float]]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of the merged intervals ``a`` that no interval of ``b`` covers."""
    out, b = [], union(b)
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclass
class Reduction:
    """Per-device totals over the traced window."""

    window: tuple[float, float]
    steps: int                                        # runs in the window
    devices: list[int]
    busy: dict = field(default_factory=dict)          # device -> seconds
    by_op: dict = field(default_factory=dict)         # op name -> s/device
    conv_s: float = 0.0                               # per device
    collective_s: float = 0.0                         # per device
    collective_exposed_s: float = 0.0                 # per device
    gaps: list = field(default_factory=list)          # (span, seconds)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        return float(np.mean([self.busy[d] for d in self.devices]))

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.by_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def whole_runs(runs: list[Run], ops: list[Op], module: str, device: int
               ) -> list[Run]:
    """The runs of ``module`` on ``device`` that the trace holds whole, in
    order. A run already under way when the trace started is recorded from
    that moment: it begins with another op than the runs that started
    inside the trace, the last one among them, and is left out."""
    mine = sorted((r for r in runs
                   if r.module == module and r.device == device),
                  key=lambda r: r.start)
    starts = sorted((o.start, o.name) for o in ops if o.device == device)

    def first_op(run: Run) -> str | None:
        i = bisect.bisect_left(starts, (run.start,))
        if i < len(starts) and starts[i][0] <= run.end:
            return starts[i][1]
        return None

    if len(mine) > 1 and first_op(mine[0]) != first_op(mine[-1]):
        return mine[1:]
    return mine


def window_of(runs: list[Run], ops: list[Op], module: str, device: int
              ) -> tuple[tuple[float, float], int]:
    """The window of the traced steps and the number of steps in it: from
    the start of the first whole run of ``module`` on ``device``
    (``whole_runs``) to the start of its last; the steps are the runs that
    start inside."""
    starts = [r.start for r in whole_runs(runs, ops, module, device)]
    if len(starts) < 2:
        raise ValueError(f"the trace holds {len(starts)} whole runs of "
                         f"{module} on device {device}; its window needs two")
    return (starts[0], starts[-1]), len(starts) - 1


def host_span_over(spans: list[Span], t0: float, t1: float) -> str:
    """What the host was doing through most of (t0, t1): the ``bench.*``
    span that covers most of it, or ``loop``, the program's loop outside
    the benchmark's spans, where that covers more."""
    cover: dict[str, list] = {}
    for s in spans:
        if s.end > t0 and s.start < t1:
            cover.setdefault(s.name, []).append(
                (max(s.start, t0), min(s.end, t1)))
    share = {n: length(union(iv)) for n, iv in cover.items()}
    share["loop"] = (t1 - t0) - length(union(
        iv for ivs in cover.values() for iv in ivs))
    return max(share, key=share.get)


def reduce_ops(ops: list[Op], spans: list[Span], runs: list[Run],
               module: str, n_devices: int | None = None) -> Reduction:
    """Busy and idle time, op time and gaps over the window of ``module``'s
    runs (``window_of``), per device; gaps named by the ``bench.*`` spans
    (``host_span_over``)."""
    devices = sorted({o.device for o in ops})
    if n_devices is not None:
        devices = devices[:n_devices]
    if not devices:
        raise ValueError("the trace holds no device operations")
    (t0, t1), steps = window_of(runs, ops, module, devices[0])
    spans = [s for s in spans if s.name.startswith(SPAN_PREFIX)]
    red = Reduction((t0, t1), steps, devices)
    nd = len(devices)
    for d in devices:
        mine = [o for o in ops if o.device == d and o.end > t0 and o.start < t1]
        busy = union(clip([(o.start, o.end) for o in mine], t0, t1))
        red.busy[d] = length(busy)
        for o in mine:
            s = min(o.end, t1) - max(o.start, t0)
            red.by_op[o.name] = red.by_op.get(o.name, 0.0) + s / nd
        red.conv_s += length(union(clip(
            [(o.start, o.end) for o in mine if is_convolution(o)], t0, t1))) / nd
        coll = union(clip([(o.start, o.end) for o in mine if is_collective(o)],
                          t0, t1))
        compute = [(o.start, o.end) for o in mine if not is_collective(o)]
        red.collective_s += length(coll) / nd
        red.collective_exposed_s += length(subtract(coll, compute)) / nd
        # idle gaps on this device, named by what the host was doing
        edges = [(t0, t0)] + busy + [(t1, t1)]
        for (_, e), (s, _) in zip(edges, edges[1:]):
            if s > e:
                red.gaps.append((host_span_over(spans, e, s), s - e))
    return red


def reduce(path: str, hlo_text: str,
           n_devices: int | None = None) -> Reduction:
    """``reduce_ops`` of a trace, over the runs of the step whose compiled
    HLO text is ``hlo_text``."""
    ops, spans, runs = load(path, hlo_kinds(hlo_text))
    return reduce_ops(ops, spans, runs, step_module(hlo_text), n_devices)
