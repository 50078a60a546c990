"""Core layers: Dense, Embedding, norms, convolutions (1/2/3-D), pooling.

Every layer follows the module.py contract:
  * ``params_spec()`` — declarative ParamSpec tree with logical axes,
  * ``apply(params, x, ctx)`` — pure function; ``ctx: ShardingCtx`` carries the
    mesh + parallel-strategy rules for activation sharding constraints.

Convolutions use ``jax.lax.conv_general_dilated`` with channels-last layout
(TPU-native). The CNN stack (ResNet/VGG/CosmoFlow) builds on these and is what
the paper's six parallel strategies were originally defined over.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .module import (NULL_CTX, ShardingCtx, fan_in_init, ones_init, param, zeros_init)


# ---------------------------------------------------------------------------
# Dense
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Dense:
    """y = x @ w (+ b). Logical axes configurable for column/row parallel."""

    in_dim: int
    out_dim: int
    use_bias: bool = False
    in_axis: str | None = "embed"
    out_axis: str | None = "mlp"
    dtype: Any = None

    def params_spec(self):
        spec = {
            "w": param((self.in_dim, self.out_dim), (self.in_axis, self.out_axis),
                       init=fan_in_init((0,)), dtype=self.dtype)
        }
        if self.use_bias:
            spec["b"] = param((self.out_dim,), (self.out_axis,), init=zeros_init(),
                              dtype=self.dtype)
        return spec

    def apply(self, params, x, ctx: ShardingCtx = NULL_CTX):
        y = x @ params["w"]
        if self.use_bias:
            y = y + params["b"]
        return y


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Embedding:
    vocab_size: int
    features: int
    dtype: Any = None

    def params_spec(self):
        return {"table": param((self.vocab_size, self.features), ("vocab", "embed"),
                               init=fan_in_init((1,)), dtype=self.dtype)}

    def apply(self, params, ids, ctx: ShardingCtx = NULL_CTX):
        return jnp.take(params["table"], ids, axis=0)

    def attend(self, params, x):
        """Tied-weight logits: x @ table.T"""
        return x @ params["table"].T


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class RMSNorm:
    dim: int
    eps: float = 1e-6
    axis_name: str | None = "embed"

    def params_spec(self):
        return {"scale": param((self.dim,), (self.axis_name,), init=ones_init())}

    def apply(self, params, x, ctx: ShardingCtx = NULL_CTX):
        dtype = x.dtype
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + self.eps)
        return (y * params["scale"].astype(jnp.float32)).astype(dtype)


@dataclass(frozen=True)
class LayerNorm:
    dim: int
    eps: float = 1e-5
    use_bias: bool = True
    axis_name: str | None = "embed"

    def params_spec(self):
        spec = {"scale": param((self.dim,), (self.axis_name,), init=ones_init())}
        if self.use_bias:
            spec["bias"] = param((self.dim,), (self.axis_name,), init=zeros_init())
        return spec

    def apply(self, params, x, ctx: ShardingCtx = NULL_CTX):
        dtype = x.dtype
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + self.eps)
        y = y * params["scale"].astype(jnp.float32)
        if self.use_bias:
            y = y + params["bias"].astype(jnp.float32)
        return y.astype(dtype)


@dataclass(frozen=True)
class BatchNorm:
    """Inference-style BN (running stats folded) + train-mode batch stats.

    Paper §4.5.2: under data parallelism BN is local (unsynchronized) by
    default; under filter/channel parallelism each PE recomputes BN
    redundantly after the Allgather (no communication); under spatial
    parallelism BN is computed on the local spatial shard. ``sync`` enables
    cross-device mean/var via psum when a mesh axis name is given (used for
    tiny local batches, cf. [55] in the paper).
    """

    dim: int
    eps: float = 1e-5
    momentum: float = 0.9
    sync_axis: str | None = None  # physical mesh axis for sync-BN

    def params_spec(self):
        return {
            "scale": param((self.dim,), ("conv_out",), init=ones_init()),
            "bias": param((self.dim,), ("conv_out",), init=zeros_init()),
        }

    def apply(self, params, x, ctx: ShardingCtx = NULL_CTX, train: bool = True):
        with jax.named_scope("batchnorm"):
            dtype = x.dtype
            xf = x.astype(jnp.float32)
            axes = tuple(range(x.ndim - 1))
            mu = jnp.mean(xf, axis=axes)
            var = jnp.mean(xf * xf, axis=axes) - mu * mu
            if self.sync_axis is not None:
                mu = jax.lax.pmean(mu, self.sync_axis)
                var = jax.lax.pmean(var, self.sync_axis)
            y = (xf - mu) * jax.lax.rsqrt(var + self.eps)
            y = (y * params["scale"].astype(jnp.float32)
                 + params["bias"].astype(jnp.float32))
            return y.astype(dtype)


# ---------------------------------------------------------------------------
# Convolutions (channels-last, any spatial rank 1..3)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Conv:
    """N-D convolution, channels-last: x[N, *spatial, C] -> y[N, *spatial', F].

    The paper's notation: weight w[C, F, K^d]; here stored as [*K^d, C, F]
    (HWIO — TPU native).
    """

    in_channels: int
    out_channels: int
    kernel: tuple[int, ...]
    strides: tuple[int, ...] | None = None
    padding: str | Sequence[tuple[int, int]] = "SAME"
    use_bias: bool = True
    feature_group_count: int = 1
    dtype: Any = None

    def params_spec(self):
        k = tuple(self.kernel)
        spec = {
            "w": param(k + (self.in_channels // self.feature_group_count,
                            self.out_channels),
                       tuple(["conv_k"] + [None] * (len(k) - 1)) + ("conv_in", "conv_out"),
                       init=fan_in_init(tuple(range(len(k) + 1))), dtype=self.dtype)
        }
        if self.use_bias:
            spec["b"] = param((self.out_channels,), ("conv_out",), init=zeros_init(),
                              dtype=self.dtype)
        return spec

    def apply(self, params, x, ctx: ShardingCtx = NULL_CTX):
        nd = len(self.kernel)
        strides = self.strides or (1,) * nd
        spatial = "DHW"[-nd:]
        dn = jax.lax.conv_dimension_numbers(
            x.shape, params["w"].shape,
            (f"N{spatial}C", f"{spatial}IO", f"N{spatial}C"))
        y = jax.lax.conv_general_dilated(
            x, params["w"], window_strides=strides, padding=self.padding,
            dimension_numbers=dn, feature_group_count=self.feature_group_count)
        if self.use_bias:
            y = y + params["b"]
        return y


def max_pool(x, window: tuple[int, ...], strides: tuple[int, ...] | None = None,
             padding: str = "SAME"):
    nd = len(window)
    strides = strides or window
    dims = (1,) + window + (1,)
    strd = (1,) + strides + (1,)
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, dims, strd, padding)


def avg_pool(x, window: tuple[int, ...], strides: tuple[int, ...] | None = None,
             padding: str = "VALID"):
    nd = len(window)
    strides = strides or window
    dims = (1,) + window + (1,)
    strd = (1,) + strides + (1,)
    summed = jax.lax.reduce_window(x.astype(jnp.float32), 0.0, jax.lax.add, dims,
                                   strd, padding)
    return (summed / float(np.prod(window))).astype(x.dtype)


def global_avg_pool(x):
    axes = tuple(range(1, x.ndim - 1))
    return jnp.mean(x, axis=axes)
