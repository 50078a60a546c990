"""Search space + analytic pruner for Pallas block sizes (DESIGN.md §13).

The discipline follows the paper's oracle: enumerate the candidate tilings,
reject the ones arithmetic alone can kill, and only measure what survives.
Per kernel the knobs are the block sizes its wrapper already exposes:

    conv2d_gemm      block_f       (filter-block width of the implicit GEMM)
    flash_attention  block_q/block_k
    rmsnorm          block_rows
    ssd_scan         chunk         (intra-chunk quadratic extent)

Two analytic filters, both read off ``HardwareSpec.from_cluster``:

* **VMEM capacity** — a candidate whose per-program working set (blocks
  double-buffered, as the Pallas pipeline holds them) exceeds
  ``VMEM_FRACTION`` of ``hw.vmem_bytes`` — the scoped limit the compiler
  enforces per kernel, not the chip's whole VMEM — cannot be compiled;
  reject.
* **Roofline knee** — predicted time is
  ``max(compute_s, memory_s) + programs · DISPATCH_S`` with MXU utilization
  ``min(block, hw.mxu)/hw.mxu`` scaling the compute term; candidates worse
  than ``slack ×`` the best prediction are off the knee and not worth
  measuring.

This module is pure arithmetic (numpy-free, jax-free) so it is unit-testable
without an accelerator and importable before XLA_FLAGS are set.
"""
from __future__ import annotations

from dataclasses import dataclass

from ..util import (conv_row_block, conv_vmem_bytes, largest_divisor,
                    resolve_block_rows)

DISPATCH_S = 2e-6          # per-program launch overhead charged to the grid
VMEM_FRACTION = 0.9        # usable fraction of hw.vmem_bytes per program

KERNELS = ("conv2d_gemm", "flash_attention", "rmsnorm", "ssd_scan")

#: the literals the kernel wrappers default to — always kept as candidates so
#: the measure loop records a default row to compare the winner against.
DEFAULT_BLOCKS = {
    "conv2d_gemm": {"block_f": 128},
    "flash_attention": {"block_q": 128, "block_k": 128},
    "rmsnorm": {"block_rows": 256},
    "ssd_scan": {"chunk": 128},
}

_BLOCK_CHOICES = {
    "conv2d_gemm": (16, 32, 64, 128, 256, 512),
    "flash_attention": (32, 64, 128, 256, 512),
    "rmsnorm": (32, 64, 128, 256, 512, 1024),
    "ssd_scan": (16, 32, 64, 128, 256),
}

# dims whose magnitude (not structure) drives the tiling choice: bucketed to
# the nearest power of two so nearby shapes share a cache entry.  Everything
# else (channels, heads, head_dim, kernel extent, strides, itemsize) changes
# the kernel structurally and stays exact.  Note "H" is spatial for conv but
# heads for flash/ssd — hence per-kernel sets.
_SIZE_DIMS = {
    "conv2d_gemm": ("B", "H", "W"),
    "flash_attention": ("B", "S"),
    "rmsnorm": ("R",),
    "ssd_scan": ("B", "S"),
}


def _nearest_pow2(n: int) -> int:
    n = max(1, int(n))
    lo = 1 << (n.bit_length() - 1)
    hi = lo << 1
    return lo if n * n <= lo * hi else hi    # geometric midpoint


def bucket(kernel: str, dims: dict) -> str:
    """Stable shape-bucket string: size dims → nearest power of two,
    structural dims exact. Nearest (not ceil) so a halo tile carrying its
    kh−1 boundary rows (e.g. H=34) lands in the bucket of its base shape."""
    size = _SIZE_DIMS[kernel]
    parts = []
    for k in sorted(dims):
        v = dims[k]
        if k in size:
            v = _nearest_pow2(v)
        parts.append(f"{k}{v}")
    return ",".join(parts)


@dataclass(frozen=True)
class Candidate:
    """One point of the search space, priced by the analytic model."""
    kernel: str
    blocks: tuple                 # sorted ((name, value), ...) — resolved
    predicted_s: float
    vmem_bytes: int
    programs: int
    is_default: bool = False
    rejected: str = ""            # "" = survives; else the pruning reason

    @property
    def blocks_dict(self) -> dict:
        return dict(self.blocks)


def _mk(kernel, blocks, compute_s, memory_s, vmem, programs, default):
    return Candidate(
        kernel=kernel, blocks=tuple(sorted(blocks.items())),
        predicted_s=max(compute_s, memory_s) + programs * DISPATCH_S,
        vmem_bytes=int(vmem), programs=int(programs), is_default=default)


def _util(block: int, mxu: int) -> float:
    return min(block, mxu) / mxu


# ---------------------------------------------------------------------------
# per-kernel models: enumerate resolved candidates and price each one
# ---------------------------------------------------------------------------

def _conv_candidates(dims, hw):
    B, H, W, C, F = (dims[k] for k in "BHWCF")
    kh, kw, sh, sw, e = dims["kh"], dims["kw"], dims["sh"], dims["sw"], dims["e"]
    Ho, Wo = -(-H // sh), -(-W // sw)
    Wp = (kw - 1) + sw * Wo
    flops = 2.0 * B * Ho * Wo * kh * kw * C * F
    out = []
    dbf = largest_divisor(F, DEFAULT_BLOCKS["conv2d_gemm"]["block_f"])
    for bf in _resolved(_BLOCK_CHOICES["conv2d_gemm"], F, dbf):
        # the kernel's own row block: the most output rows that fit
        tile = dict(Wp=Wp, Wo=Wo, C=C, kh=kh, kw=kw, sh=sh, bf=bf, e=e)
        bh = conv_row_block(Ho, **tile)
        programs = B * (Ho // bh) * (F // bf)
        # each row window is re-read once per filter block; weights/output
        # are read once regardless of bf — larger bf ⇒ less x traffic.
        rows_in = (kh - 1) + sh * bh
        traffic = (programs * rows_in * Wp * C * e     # x windows
                   + B * kh * kw * C * F * e           # weight blocks
                   + B * Ho * Wo * F * e)              # output
        vmem = conv_vmem_bytes(bh, **tile)
        compute = flops / (hw.peak_bf16 * _util(bf, hw.mxu))
        out.append(_mk("conv2d_gemm", {"block_f": bf}, compute,
                       traffic / hw.hbm_bw, vmem, programs, bf == dbf))
    return out


def _flash_candidates(dims, hw):
    B, Hh, S, D, e = dims["B"], dims["H"], dims["S"], dims["D"], dims["e"]
    causal = bool(dims.get("causal", 1))
    kv_frac = 0.5 if causal else 1.0       # causal programs skip ~half the KV
    flops = 4.0 * B * Hh * S * S * D * kv_frac          # QKᵀ + PV
    dq = largest_divisor(S, DEFAULT_BLOCKS["flash_attention"]["block_q"])
    dk = largest_divisor(S, DEFAULT_BLOCKS["flash_attention"]["block_k"])
    out, seen = [], set()
    for rq in _resolved(_BLOCK_CHOICES["flash_attention"], S, dq):
        for rk in _resolved(_BLOCK_CHOICES["flash_attention"], S, dk):
            if (rq, rk) in seen:
                continue
            seen.add((rq, rk))
            programs = B * Hh * (S // rq)
            # each program streams the (causal-truncated) KV; q/out once
            traffic = (programs * kv_frac * 2 * S * D * e
                       + 2 * B * Hh * S * D * e)
            vmem = (2 * (2 * rq * D * e + 2 * S * D * e)  # q, out, K, V ×2
                    + rq * rk * 4 + rq * D * 4)         # logits + fp32 acc
            compute = flops / (hw.peak_bf16
                               * _util(min(rq, rk), hw.mxu) * _util(D, hw.mxu))
            out.append(_mk("flash_attention", {"block_q": rq, "block_k": rk},
                           compute, traffic / hw.hbm_bw, vmem, programs,
                           (rq, rk) == (dq, dk)))
    return out


def _rmsnorm_candidates(dims, hw):
    R, D, e = dims["R"], dims["D"], dims["e"]
    out, seen = [], set()
    dbr, _ = resolve_block_rows(R, DEFAULT_BLOCKS["rmsnorm"]["block_rows"])
    for req in _BLOCK_CHOICES["rmsnorm"]:
        br, Rp = resolve_block_rows(R, req)
        if (br, Rp) in seen:
            continue
        seen.add((br, Rp))
        programs = Rp // br
        # memory-bound VPU op: rows in + rows out (+ per-program scale
        # re-read); padding waste shows up as Rp > R traffic.
        traffic = 2 * Rp * D * e + programs * D * e
        vmem = 2 * (2 * br * D * e) + br * D * 4     # in, out ×2 + fp32
        compute = 3.0 * Rp * D / hw.peak_bf16           # negligible by design
        out.append(_mk("rmsnorm", {"block_rows": br}, compute,
                       traffic / hw.hbm_bw, vmem, programs, br == dbr))
    return out


def _ssd_candidates(dims, hw):
    B, S, Hh, P, N, e = (dims[k] for k in ("B", "S", "H", "P", "N", "e"))
    out = []
    for Q in _resolved(_BLOCK_CHOICES["ssd_scan"], S,
                       largest_divisor(S, DEFAULT_BLOCKS["ssd_scan"]["chunk"])):
        programs = B * (S // Q)
        # intra-chunk quadratic term grows with Q — a genuine knee, unlike
        # the monotone kernels above: scores/L are O(Q²) per chunk.
        flops = 2.0 * B * Hh * S * (Q * (N + P) + P * N)
        traffic = (B * S * (Hh * P + Hh + 2 * Hh * N) * e   # x, dt, B, C in
                   + B * S * Hh * P * 4                      # y out (fp32)
                   + programs * Hh * (P * N + 1) * 4)        # states + decays
        vmem = (Q * Hh * P * e + 2 * Q * Hh * N * e
                + 3 * Hh * Q * Q * 4                         # scores, L, w
                + Q * Hh * P * 4 + Hh * P * N * 4)
        compute = flops / (hw.peak_bf16 * _util(min(Q, N), hw.mxu))
        out.append(_mk("ssd_scan", {"chunk": Q}, compute,
                       traffic / hw.hbm_bw, vmem, programs,
                       Q == largest_divisor(S, 128)))
    return out


def _resolved(choices, n, default_resolved):
    """Resolve each requested block against n (largest divisor ≤ request),
    dedup, and make sure the wrapper's resolved default is present."""
    vals = {largest_divisor(n, c) for c in choices if c <= max(n, min(choices))}
    vals.add(default_resolved)
    return sorted(vals)


_ENUM = {"conv2d_gemm": _conv_candidates, "flash_attention": _flash_candidates,
         "rmsnorm": _rmsnorm_candidates, "ssd_scan": _ssd_candidates}


def enumerate_candidates(kernel: str, dims: dict, hw) -> list:
    """All resolved candidates for (kernel, dims), priced — none rejected."""
    return _ENUM[kernel](dims, hw)


def prune(kernel: str, dims: dict, hw, *, slack: float = 2.0,
          top_k: int = 4) -> list:
    """Survivors worth measuring, best-predicted first.

    Rejects candidates whose per-program working set exceeds the VMEM budget,
    then keeps the ``top_k`` best-predicted within ``slack ×`` the best; the
    resolved default always survives (the measure loop needs its row)."""
    cands = enumerate_candidates(kernel, dims, hw)
    budget = VMEM_FRACTION * hw.vmem_bytes
    fit = [c for c in cands if c.vmem_bytes <= budget]
    if not fit:                      # degenerate budget: keep the smallest
        fit = [min(cands, key=lambda c: c.vmem_bytes)]
    fit.sort(key=lambda c: c.predicted_s)
    best = fit[0].predicted_s
    keep = [c for c in fit if c.predicted_s <= slack * best][:top_k]
    if not any(c.is_default for c in keep):
        keep += [c for c in fit if c.is_default][:1]
    return keep
