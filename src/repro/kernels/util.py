"""Block-size resolution shared by the Pallas kernels and their autotuner.

Every kernel grids over fixed-size blocks, so a requested block must be
reconciled with the actual extent. The old per-kernel idiom
(``while N % b: b -= 1``) is O(N) and collapses to b=1 for prime extents —
a 4099-row ragged microbatch would silently serialize the rmsnorm grid.
These helpers do it right once: largest divisor in O(√N), plus a
pad-to-block escape hatch for extents whose divisors are all pathological.
The autotuner (kernels/autotune/space.py) calls the same functions so its
candidate tilings are exactly what the kernels will deploy.
"""
from __future__ import annotations

import math

# a divisor smaller than this serializes the grid badly enough that padding
# to the requested block (and wasting the pad rows) is cheaper
MIN_BLOCK_ROWS = 16

# VMEM one Pallas program may use on a TPU v5e: the compiler's default scoped
# limit, not the chip's 128 MiB (a kernel whose double-buffered blocks need
# more is refused with RESOURCE_EXHAUSTED at compile time)
SCOPED_VMEM_BYTES = 16 * 2**20


def _tile(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def conv_vmem_bytes(bh: int, *, Wp: int, Wo: int, C: int, kh: int, kw: int,
                    sh: int, bf: int, e: int) -> int:
    """VMEM an implicit-GEMM conv program holds for ``bh`` output rows:
    double-buffered input window, filter block and output block, plus the
    fp32 accumulator and one shifted patch. Minor dims pad to the (8, 128)
    vreg tile, so a 3-channel stem row costs as much as a 128-channel one."""
    rows_in = (kh - 1) + sh * bh
    x = rows_in * _tile(Wp, 8) * _tile(C, 128) * e
    w = kh * kw * _tile(C, 8) * _tile(bf, 128) * e
    out = bh * _tile(Wo, 8) * _tile(bf, 128) * e
    acc = bh * _tile(Wo, 8) * _tile(bf, 128) * 4
    patch = sh * bh * _tile(Wp, 8) * _tile(C, 128) * 4
    return 2 * (x + w + out) + acc + patch


def conv_row_block(Ho: int, budget: int = SCOPED_VMEM_BYTES // 2,
                   **dims) -> int:
    """Output rows per conv program: the largest divisor of ``Ho`` whose
    working set (``conv_vmem_bytes``) fits ``budget``. Half the scoped limit
    by default, leaving the rest to the compiler's own scratch."""
    for bh in range(Ho, 0, -1):
        if Ho % bh == 0 and conv_vmem_bytes(bh, **dims) <= budget:
            return bh
    return 1


def largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is ≤ ``cap`` (O(√n); cap clamped to
    [1, n])."""
    n = int(n)
    cap = max(1, min(int(cap), n))
    if n % cap == 0:
        return cap
    best = 1
    for d in range(2, math.isqrt(n) + 1):
        if n % d == 0:
            if d <= cap and d > best:
                best = d
            q = n // d
            if q <= cap and q > best:
                best = q
    return best


def resolve_block_rows(rows: int, block: int,
                       min_block: int = MIN_BLOCK_ROWS) -> tuple[int, int]:
    """Resolve a row-block request against ``rows`` independent rows.

    Returns ``(block_rows, padded_rows)``: the block to grid over and the
    extent to pad the rows to (== ``rows`` when no padding is needed).
    Preference order:

      1. the largest divisor of ``rows`` ≤ ``block`` — exact grid, no waste;
      2. when that divisor is pathologically small (< ``min_block``, e.g.
         a prime row count from a ragged last microbatch), pad up to a
         multiple of the requested block instead: the pad rows are wasted
         bandwidth, but the grid stays parallel instead of serializing
         to ``rows`` single-row programs.

    Only valid for row-independent kernels (rmsnorm): padded rows compute
    garbage that the caller slices off.
    """
    cap = max(1, min(int(block), int(rows)))
    br = largest_divisor(rows, cap)
    if br == cap or br >= min_block:
        return br, rows
    return cap, -(-rows // cap) * cap
