"""Compiled-HLO analysis: collective inventory, cost extraction, extrapolation.

XLA's ``cost_analysis()`` counts a ``while`` (scan) body once, not
× trip-count (established empirically — EXPERIMENTS.md §Dry-run). The
dry-run therefore compiles each cell twice more with 1 and 2 unrolled layer
groups under identical shardings; the delta is the exact per-group HLO cost
and  ``total = full_scan + (n_groups - 1) × delta``.

Collectives are parsed from the compiled HLO text with their shapes and
replica groups; per-chip wire bytes follow the ring model the paper uses
(§4.3): all-reduce 2m(g−1)/g, all-gather/reduce-scatter/all-to-all m(g−1)/g,
collective-permute m.
"""
from __future__ import annotations

import re
from collections import Counter
from dataclasses import dataclass


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"(?P<kind>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?"
    r"(?:\.\d+)?\s*\(")

_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?P<otype>\([^=]*?\)|[\w\[\],{}\s]+?)\s*"
    r"(?P<kind>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?(?:\.\d+)?\(",
    re.M)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\]")
_SRC_TGT_RE = re.compile(r"source_target_pairs=\{\{(\d+),(\d+)\}")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class Collective:
    kind: str
    out_bytes: int
    group_size: int
    axis: str  # inferred mesh axis ("model"/"data"/"pod"/"mixed")
    count: int = 1
    f32: bool = False  # True when the payload is fp32 (see adjusted accounting)

    @property
    def wire_bytes_per_chip(self) -> float:
        g, m = self.group_size, self.out_bytes
        if g <= 1:
            return 0.0
        if self.kind == "all-reduce":
            return 2 * m * (g - 1) / g
        if self.kind == "all-gather":
            return m * (g - 1) / g          # m = gathered (output) size
        if self.kind == "reduce-scatter":
            return m * (g - 1)              # m = output (scattered) shard
        if self.kind == "all-to-all":
            return m * (g - 1) / g
        if self.kind == "collective-permute":
            return m
        return 0.0


def _infer_axis(first_group: list[int], mesh_shape: dict[str, int]) -> str:
    """Infer which mesh axis a replica group spans from its id stride."""
    if len(first_group) < 2:
        return "none"
    stride = first_group[1] - first_group[0]
    # mesh is laid out row-major over (pod, data, model)
    axes = list(mesh_shape.items())  # ordered
    sizes = [s for _, s in axes]
    strides = {}
    acc = 1
    for name, size in reversed(axes):
        strides[name] = acc
        acc *= size
    for name, size in axes:
        if stride == strides[name] and len(first_group) <= size:
            return name
    return "mixed"


def parse_collectives(hlo_text: str, mesh_shape: dict[str, int]) -> list[Collective]:
    """Inventory of collectives with byte sizes and inferred mesh axes."""
    out: dict[tuple, Collective] = {}
    for m in _OP_RE.finditer(hlo_text):
        kind = m.group("kind")
        otype = m.group("otype")
        nbytes = _shape_bytes(otype)
        is_f32 = "f32[" in otype and "bf16[" not in otype
        # find replica groups within this op's line
        line_end = hlo_text.find("\n", m.end())
        line = hlo_text[m.start():line_end if line_end > 0 else None]
        gsize, axis = 1, "none"
        gm = _GROUPS_RE.search(line)
        if gm:
            ids = [int(x) for x in gm.group(1).split(",")]
            gsize = len(ids)
            axis = _infer_axis(ids, mesh_shape)
        else:
            im = _GROUPS_IOTA_RE.search(line)
            if im:
                n_groups, gsize = int(im.group(1)), int(im.group(2))
                # iota groups: contiguous by construction along the last dims
                axis = "model" if gsize <= mesh_shape.get("model", 0) else "mixed"
        if kind == "collective-permute":
            gsize = max(gsize, 2)
            axis = axis if axis != "none" else "model"
        key = (kind, nbytes, gsize, axis, is_f32)
        if key in out:
            out[key].count += 1
        else:
            out[key] = Collective(kind, nbytes, gsize, axis, f32=is_f32)
    return list(out.values())


@dataclass
class CellCost:
    """Per-device HLO-derived cost of one compiled cell."""

    flops: float
    bytes_accessed: float
    collectives: list[Collective]
    temp_bytes: int = 0
    arg_bytes: int = 0
    out_bytes: int = 0

    def wire_bytes(self, axis: str | None = None,
                   native_dtype: bool = False) -> float:
        """native_dtype=True halves fp32 collectives: the CPU backend
        promotes every bf16 dot to f32 and drags the converts into the
        gathers/reduces; on the TPU target those payloads are bf16
        (EXPERIMENTS.md §Dry-run, artifact note)."""
        total = 0.0
        for c in self.collectives:
            if axis is not None and c.axis != axis:
                continue
            w = c.wire_bytes_per_chip * c.count
            if native_dtype and c.f32:
                w *= 0.5
            total += w
        return total

    def to_json(self) -> dict:
        return {
            "flops": self.flops, "bytes_accessed": self.bytes_accessed,
            "temp_bytes": self.temp_bytes, "arg_bytes": self.arg_bytes,
            "out_bytes": self.out_bytes,
            "wire_bytes_total": self.wire_bytes(),
            "wire_bytes_native_dtype": self.wire_bytes(native_dtype=True),
            "wire_bytes_by_axis": {
                ax: self.wire_bytes(ax)
                for ax in ("pod", "data", "model", "mixed")},
            "collectives": [
                {"kind": c.kind, "bytes": c.out_bytes, "group": c.group_size,
                 "axis": c.axis, "count": c.count, "f32": c.f32}
                for c in sorted(self.collectives,
                                key=lambda c: -c.wire_bytes_per_chip * c.count)],
        }


def cost_of(compiled, mesh_shape: dict[str, int]) -> CellCost:
    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    colls = parse_collectives(compiled.as_text(), mesh_shape)
    return CellCost(
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        collectives=colls,
        temp_bytes=ma.temp_size_in_bytes,
        arg_bytes=ma.argument_size_in_bytes,
        out_bytes=ma.output_size_in_bytes)


def combine(full: CellCost, g1: CellCost, g2: CellCost,
            n_groups: int) -> CellCost:
    """total = full_scan + (n_groups − 1) × (g2 − g1)."""
    extra = max(n_groups - 1, 0)
    d_flops = max(g2.flops - g1.flops, 0.0)
    d_bytes = max(g2.bytes_accessed - g1.bytes_accessed, 0.0)
    # collective deltas bucketed by (kind, bytes, group, axis)
    def bucket(colls):
        d = Counter()
        for c in colls:
            d[(c.kind, c.out_bytes, c.group_size, c.axis, c.f32)] += c.count
        return d

    b_full, b1, b2 = bucket(full.collectives), bucket(g1.collectives), \
        bucket(g2.collectives)
    total = Counter(b_full)
    for key in set(b2) | set(b1):
        delta = b2.get(key, 0) - b1.get(key, 0)
        if delta > 0:
            total[key] += delta * extra
    colls = [Collective(k, nb, g, ax, cnt, f32=f32)
             for (k, nb, g, ax, f32), cnt in total.items() if cnt > 0]
    return CellCost(
        flops=full.flops + extra * d_flops,
        bytes_accessed=full.bytes_accessed + extra * d_bytes,
        collectives=colls,
        temp_bytes=full.temp_bytes, arg_bytes=full.arg_bytes,
        out_bytes=full.out_bytes)
