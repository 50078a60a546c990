"""Implicit-GEMM conv2d Pallas kernel (the paper's CNN compute hot spot).

Hardware adaptation (DESIGN.md §6): cuDNN's implicit GEMM tiles for SMs/shared
memory; on TPU the conv is re-expressed as kh·kw shifted (rows·Wo, C) × (C, F)
matmuls accumulated in fp32 — each contraction feeds the 128×128 MXU, the
image window + filter block live in VMEM. Grid: (batch, output-row blocks,
F/BF). Each program reads the kh−1 + sh·bh padded input rows its bh output
rows need (overlapping element-offset windows), with bh the largest row block
whose working set fits half the scoped VMEM limit — so a whole 224² stem image
never has to sit in one program. Input is pre-padded in the wrapper so the
kernel body is branch-free.

Strided convolutions (ResNet's stride-2 bottlenecks) decimate each shifted
patch with a slice-then-reshape — `(sh·Ho, …) → (Ho, sh, …)[:, 0]` — static
shapes only, no gather, so the same body serves every stride.

The halo-aware entry (``pad_h=False``) consumes a tile whose leading spatial
dim ALREADY carries its kh−1 boundary rows (the spatial-parallel halo
exchange delivered them — parallel/halo.py); only the W dim is padded here,
so the sharded path pays no second `jnp.pad` round-trip over H.

Backward: the kernel is forward-only; its custom VJP differentiates the
equivalent VALID XLA convolution of the padded input, so a train step can
run the kernel in its forward pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..util import conv_row_block, largest_divisor


def _decimate(patch, Ho: int, Wo: int, sh: int, sw: int, c: int):
    """Keep every (sh, sw)-th pixel of a (sh·Ho, sw·Wo, C) patch."""
    if sh > 1:
        patch = patch.reshape(Ho, sh, patch.shape[1], c)[:, 0]
    if sw > 1:
        patch = patch.reshape(Ho, Wo, sw, c)[:, :, 0]
    return patch


def _conv_kernel(x_ref, w_ref, o_ref, *, bh: int, Wo: int, kh: int, kw: int,
                 sh: int, sw: int):
    c, bf = x_ref.shape[-1], o_ref.shape[-1]
    acc = jnp.zeros((bh * Wo, bf), jnp.float32)
    for di in range(kh):
        for dj in range(kw):
            # read each shifted patch straight from the ref: a dynamic_slice
            # of a loaded value has no TPU lowering
            patch = x_ref[pl.ds(di, sh * bh), pl.ds(dj, sw * Wo), :]
            mat = _decimate(patch, bh, Wo, sh, sw, c).reshape(bh * Wo, c)
            acc += jax.lax.dot(mat, w_ref[di, dj],
                               preferred_element_type=jnp.float32)
    o_ref[...] = acc.reshape(bh, Wo, bf).astype(o_ref.dtype)


def _valid_conv(xp, w, strides):
    """The XLA convolution the kernel computes: VALID over the padded input."""
    dn = jax.lax.conv_dimension_numbers(xp.shape, w.shape,
                                        ("NHWC", "HWIO", "NHWC"))
    return jax.lax.conv_general_dilated(xp, w, strides, "VALID",
                                        dimension_numbers=dn)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _gemm(xp, w, strides, block_f, interpret):
    B, Hp, Wp, C = xp.shape
    kh, kw, _, F = w.shape
    sh, sw = strides
    Ho, Wo = (Hp - kh) // sh + 1, (Wp - kw) // sw + 1
    bf = largest_divisor(F, block_f)
    bh = conv_row_block(Ho, Wp=Wp, Wo=Wo, C=C, kh=kh, kw=kw, sh=sh, bf=bf,
                        e=xp.dtype.itemsize)
    rows = (kh - 1) + sh * bh
    kernel = functools.partial(_conv_kernel, bh=bh, Wo=Wo, kh=kh, kw=kw,
                               sh=sh, sw=sw)
    return pl.pallas_call(
        kernel,
        grid=(B, Ho // bh, F // bf),
        in_specs=[
            # overlapping row windows: element offsets, not block indices
            pl.BlockSpec((None, pl.Element(rows), pl.Element(Wp),
                          pl.Element(C)),
                         lambda b, r, f: (b, r * sh * bh, 0, 0)),
            pl.BlockSpec((kh, kw, C, bf), lambda b, r, f: (0, 0, 0, f)),
        ],
        out_specs=pl.BlockSpec((None, bh, Wo, bf),
                               lambda b, r, f: (b, r, 0, f)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wo, F), xp.dtype),
        interpret=interpret,
    )(xp, w)


def _gemm_fwd(xp, w, strides, block_f, interpret):
    return _gemm(xp, w, strides, block_f, interpret), (xp, w)


def _gemm_bwd(strides, block_f, interpret, res, g):
    _, vjp = jax.vjp(lambda a, b: _valid_conv(a, b, strides), *res)
    return vjp(g)


_gemm.defvjp(_gemm_fwd, _gemm_bwd)


def conv2d_gemm(x, w, *, strides=(1, 1), block_f: int = 128,
                pad_h: bool = True, interpret: bool = False):
    """SAME conv with arbitrary strides. x: (B,H,W,C); w: (kh,kw,C,F).

    ``pad_h=False`` is the halo-aware variant: H is treated as pre-padded —
    the tile already holds its kh−1 boundary rows (stride 1 only; the
    spatial executor never strides a halo conv) and the output has
    H − kh + 1 rows (VALID over H, SAME over W).
    """
    B, H, W, C = x.shape
    kh, kw, _, F = w.shape
    sh, sw = (strides, strides) if isinstance(strides, int) else strides
    if not pad_h and (sh, sw) != (1, 1):
        raise ValueError(f"halo-aware conv2d_gemm is stride-1 only, "
                         f"got strides={(sh, sw)}")
    Ho = H - kh + 1 if not pad_h else -(-H // sh)
    Wo = -(-W // sw)
    # padded extents cover the largest shifted patch, di + sh·Ho ≤ Hp
    Hp = (kh - 1) + sh * Ho
    Wp = (kw - 1) + sw * Wo
    if pad_h:
        lo_h = max((Ho - 1) * sh + kh - H, 0) // 2   # XLA SAME convention
        pads_h = (lo_h, Hp - H - lo_h)
    else:
        pads_h = (0, Hp - H)                          # Hp == H: no-op
    lo_w = max((Wo - 1) * sw + kw - W, 0) // 2
    xp = jnp.pad(x, ((0, 0), pads_h, (lo_w, Wp - W - lo_w), (0, 0)))
    return _gemm(xp, w, (sh, sw), block_f, interpret)
