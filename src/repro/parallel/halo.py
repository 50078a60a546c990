"""Spatial parallelism with overlapped halo exchange — paper §3.2 / [13].

Convolutions whose input is sharded along a spatial dim need boundary rows
from logically-neighbouring PEs. The paper's FB-Halo transfers cost ~60% of
an allreduce on its MPI cluster and its oracle charges them SERIALLY; Dryden
et al. show they can be almost fully hidden under interior compute. This
module implements that overlap:

  ``spatial_conv2d`` launches the ``ppermute`` halo transfers FIRST, computes
  the interior VALID convolution — the output rows whose windows touch only
  local data — while the exchange is in flight, then computes just the
  2·(K−1) boundary rows from the received halos and stitches. Every output
  row is the same reduction over the same window as the unsharded SAME conv,
  so the result is bit-exact (asserted by the ``halo_overlap`` multidevice
  check), and the interior conv carries no data dependency on the permutes,
  so XLA is free to run the DMA under it.

``HaloConv`` deploys this through the strategy rules tables: a drop-in
``nn.layers.Conv`` whose apply routes to the overlapped sharded path when
the ctx's rules shard the leading spatial dim (the ``spatial``/``ds``
tables), and to the plain conv otherwise. With ``ctx.use_pallas`` the local
convolutions run on the implicit-GEMM Pallas kernel — the boundary/interior
tiles feed its halo-aware ``pad_h=False`` entry directly, no second
``jnp.pad`` round-trip (DESIGN.md §6).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..launch.compat import shard_map
from ..nn.layers import Conv
from ..nn.module import NULL_CTX, ShardingCtx, spec_to_pspec


def _halo_sizes(kh: int) -> tuple[int, int]:
    """(rows needed from the upper neighbour, rows from the lower) for a
    SAME conv of width kh — XLA's SAME convention: pad_lo = (kh−1)//2,
    pad_hi = kh//2, so even widths split asymmetrically."""
    return (kh - 1) // 2, kh // 2


def halo_exchange(x, halo: int | tuple[int, int], axis: str):
    """Exchange halo rows (dim 1) with ring neighbours inside shard_map.

    ``halo`` is (lo, hi) — rows fetched from the upper / lower neighbour —
    or a single int for a symmetric exchange. x: (B, H_local, ..., C);
    returns (B, lo + H_local + hi, ..., C) with zeros at the global
    boundary (= the unsharded op's SAME zero padding).
    """
    lo, hi = (halo, halo) if isinstance(halo, int) else halo
    if lo == 0 and hi == 0:
        return x
    if x.shape[1] < max(lo, hi):
        raise ValueError(
            f"shard too thin for the halo: H_local={x.shape[1]} < "
            f"halo={max(lo, hi)} (p={jax.lax.axis_size(axis)}) — one-hop neighbour "
            f"exchange cannot serve this kernel; use fewer spatial shards")
    from_up, from_down = _exchange(x, lo, hi, axis)
    return jnp.concatenate([from_up, x, from_down], axis=1)


def _exchange(x, lo: int, hi: int, axis: str):
    """The two ppermute transfers: returns (rows from up, rows from down),
    zero-filled at the global edges. Issued before any compute that uses
    them so the DMA can overlap the interior convolution."""
    p = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    empty = x[:, :0]
    from_up = from_down = empty
    if lo:
        bot = x[:, -lo:]           # rows this shard sends DOWN (to idx+1)
        from_up = jax.lax.ppermute(bot, axis,
                                   [(i, i + 1) for i in range(p - 1)])
        from_up = jnp.where(idx == 0, jnp.zeros_like(from_up), from_up)
    if hi:
        top = x[:, :hi]            # rows this shard sends UP (to idx-1)
        from_down = jax.lax.ppermute(top, axis,
                                     [(i + 1, i) for i in range(p - 1)])
        from_down = jnp.where(idx == p - 1, jnp.zeros_like(from_down),
                              from_down)
    return from_up, from_down


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _tuned_block_f(ctx, x_shape, w_shape, strides=(1, 1), p: int = 1) -> int:
    """Tuned conv2d_gemm filter block for this site, or the 128 default.

    ``p`` divides the leading spatial dim for the sharded path so the lookup
    uses the per-shard tile height the kernel actually sees (the bucket's
    nearest-pow2 rounding absorbs the kh−1 halo rows)."""
    tiles = getattr(ctx, "kernel_tiles", None)
    if tiles is None:
        return 128
    B, H, W, C = x_shape
    kh, kw, _, F = w_shape
    return tiles.conv_block_f(B=B, H=H // p, W=W, C=C, F=F, kh=kh, kw=kw,
                              sh=strides[0], sw=strides[1], e=4)


def _local_conv(xl, wl, trail_pads, *, use_pallas: bool, interpret: bool,
                block_f: int = 128):
    """VALID-over-dim-1 conv of a local tile (trailing spatial dims SAME).

    The Pallas path is 2-D only and consumes the tile through the
    halo-aware kernel entry (H pre-padded by the exchange)."""
    nd = xl.ndim - 2
    if use_pallas and nd == 2:
        from ..kernels import conv2d_gemm
        return conv2d_gemm(xl, wl, pad_h=False, interpret=interpret,
                           block_f=block_f)
    spatial = "DHW"[-nd:]
    dn = jax.lax.conv_dimension_numbers(
        xl.shape, wl.shape, (f"N{spatial}C", f"{spatial}IO", f"N{spatial}C"))
    return jax.lax.conv_general_dilated(
        xl, wl, (1,) * nd, ((0, 0),) + trail_pads, dimension_numbers=dn)


def spatial_conv2d(x, w, mesh: Mesh, axis: str = "model", bias=None, *,
                   strides: Sequence[int] | None = None, overlap: bool = True,
                   batch_axes=None, use_pallas: bool = False,
                   interpret: bool | None = None, block_f: int = 128):
    """N-D conv (stride 1, SAME) with the leading spatial dim sharded.

    x: (B, H, *spatial, C) with H sharded over ``axis``; w: (kh, *k, C, F).
    Matches the unsharded SAME conv bit-exactly — including even kernel
    widths (asymmetric halos) and p = 1 (degenerates to the serial conv).

    ``overlap=True`` (default) computes the interior rows while the halo
    transfers are in flight; ``overlap=False`` keeps the serial
    exchange-then-conv pipeline (same values, reference for parity checks).
    ``batch_axes`` names the mesh axes the batch dim is sharded over (the
    DP axes under ``ds``) so the wrapped region preserves data parallelism.
    Spatial parallelism cannot stride the sharded dim (shard boundaries
    would fall between stride phases), so any stride ≠ 1 raises.
    """
    nd = x.ndim - 2
    if strides is not None and tuple(strides) != (1,) * nd:
        raise ValueError(
            f"spatial_conv2d is stride-1 only (got strides={tuple(strides)});"
            f" strided convs cannot split the sharded spatial dim — keep "
            f"them on the unsharded path (HaloConv falls back automatically)")
    kh = w.shape[0]
    lo, hi = _halo_sizes(kh)
    trail_pads = tuple(_halo_sizes(k) for k in w.shape[1:nd])
    interpret = not _on_tpu() if interpret is None else interpret

    def local(xl, wl, bl):
        # (shards thinner than the halo raise inside halo_exchange — every
        # too-thin case takes the serial branch below)
        H = xl.shape[1]
        conv = lambda t: _local_conv(t, wl, trail_pads,       # noqa: E731
                                     use_pallas=use_pallas,
                                     interpret=interpret, block_f=block_f)
        if not overlap or H <= lo + hi:
            # serial reference path (also the thin-shard fallback where the
            # interior would be empty — H == lo+hi included: a zero-row
            # interior is illegal for the Pallas call): full exchange, one
            # conv
            y = conv(halo_exchange(xl, (lo, hi), axis))
        else:
            # 1. launch the halo transfers
            from_up, from_down = _exchange(xl, lo, hi, axis)
            # 2. interior rows [lo, H−hi) depend only on local data — this
            #    conv overlaps the exchange
            interior = conv(xl)
            # 3. boundary rows from the received halos, then stitch. An
            #    even kernel has lo = 0 (XLA SAME pads below only): that
            #    side contributes no rows and must not reach the conv —
            #    a zero-row tile is illegal for the Pallas path.
            pieces = [interior]
            if lo:
                pieces.insert(0, conv(jnp.concatenate(
                    [from_up, xl[:, :lo + hi]], axis=1)))
            if hi:
                pieces.append(conv(jnp.concatenate(
                    [xl[:, H - (lo + hi):], from_down], axis=1)))
            y = jnp.concatenate(pieces, axis=1) if len(pieces) > 1 \
                else interior
        if bl is not None:
            y = y + bl
        return y

    spec = [None] * (nd + 2)
    spec[0], spec[1] = batch_axes, axis
    io_spec = P(*spec)
    if bias is None:     # no dead all-replicated bias arg: two real arities
        fn = shard_map(lambda xl, wl: local(xl, wl, None), mesh=mesh,
                       in_specs=(io_spec, P()), out_specs=io_spec,
                       check_vma=False)
        return fn(x, w)
    fn = shard_map(local, mesh=mesh, in_specs=(io_spec, P(), P()),
                   out_specs=io_spec, check_vma=False)
    return fn(x, w, bias)


# ---------------------------------------------------------------------------
# HaloConv: the deployable layer (models/cnn.py uses it for its K>1 convs)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class HaloConv(Conv):
    """``Conv`` that executes as the overlapped halo pipeline when sharded.

    Same ``params_spec``; ``apply`` inspects the ctx: when the rules shard
    the model's "spatial" logical axis onto ONE mesh axis that evenly
    divides the input's leading spatial dim (the ``spatial``/``ds`` tables),
    the conv runs inside ``spatial_conv2d``'s shard_map with the halo
    transfers overlapped under the interior compute. Anything the explicit
    path cannot take — strides, grouped convs, non-SAME padding, thin
    shards, a multi-axis or non-dividing sharding — falls back to the plain
    (GSPMD-partitioned) conv, so the layer is always safe to deploy.
    """

    overlap: bool = True

    def _spatial_sharding(self, ctx: ShardingCtx, x):
        """(mesh axis name, batch axes) when the explicit halo path applies,
        else None."""
        if ctx.mesh is None:
            return None
        nd = len(self.kernel)
        if nd < 2 or self.feature_group_count != 1 or self.kernel[0] <= 1:
            return None
        if self.padding != "SAME":   # the halo exchange IS the SAME padding
            return None
        if self.strides is not None and tuple(self.strides) != (1,) * nd:
            return None
        axes = ("batch", "spatial") + (None,) * (nd - 1) + ("conv_out",)
        pspec = spec_to_pspec(axes, ctx.rules, ctx.mesh, x.shape)
        sp = pspec[1]
        if sp is None or isinstance(sp, tuple):
            return None
        p = ctx.mesh.shape[sp]
        lo, hi = _halo_sizes(self.kernel[0])
        if p <= 1 or x.shape[1] % p or x.shape[1] // p < max(lo, hi):
            return None
        return sp, pspec[0]

    def apply(self, params, x, ctx: ShardingCtx = NULL_CTX):
        sharded = self._spatial_sharding(ctx, x)
        if sharded is None:
            if ctx.use_pallas and len(self.kernel) == 2 \
                    and self.feature_group_count == 1 \
                    and self.padding == "SAME":
                from ..kernels import conv2d_gemm
                strides = tuple(self.strides or (1, 1))
                y = conv2d_gemm(x, params["w"], strides=strides,
                                interpret=not _on_tpu(),
                                block_f=_tuned_block_f(
                                    ctx, x.shape, params["w"].shape, strides))
                if self.use_bias:
                    y = y + params["b"]
                return y
            return super().apply(params, x, ctx)
        axis, batch_axes = sharded
        return spatial_conv2d(
            x, params["w"], ctx.mesh, axis,
            bias=params["b"] if self.use_bias else None,
            overlap=self.overlap, batch_axes=batch_axes,
            use_pallas=ctx.use_pallas and len(self.kernel) == 2,
            block_f=_tuned_block_f(ctx, x.shape, params["w"].shape,
                                   p=ctx.mesh.shape[axis]))
