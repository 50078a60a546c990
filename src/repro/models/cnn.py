"""The paper's evaluation CNNs in JAX: ResNet-50/152, VGG16, CosmoFlow (3-D).

These are the models the ParaDL oracle was validated on (paper Table 5) and
the substrate for the spatial/filter/channel parallel strategies. Layouts are
channels-last. BatchNorm follows paper §4.5.2 (local per-PE by default).

Each model exposes ``params_spec()``, ``apply(params, x, ctx, train)`` and
``loss_fn`` (softmax CE for classification, MSE for CosmoFlow regression),
plus ``layer_table()`` — the per-layer tensor-shape table (|x|,|y|,|w|,FLOPs)
that feeds the oracle's analytical model (paper Table 2 notation).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ..nn.layers import BatchNorm, Conv, Dense, global_avg_pool, max_pool
from ..nn.module import NULL_CTX, ShardingCtx, tree_num_params
from ..parallel.halo import HaloConv


# ---------------------------------------------------------------------------
# ResNet
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ResNetConfig:
    name: str
    stage_sizes: tuple[int, ...]      # (3,4,6,3) → ResNet-50; (3,8,36,3) → 152
    n_classes: int = 1000
    width: int = 64
    dtype: Any = jnp.float32


RESNET50 = ResNetConfig("resnet50", (3, 4, 6, 3))
RESNET152 = ResNetConfig("resnet152", (3, 8, 36, 3))


@dataclass(frozen=True)
class Bottleneck:
    in_ch: int
    mid_ch: int
    stride: int
    dtype: Any

    @property
    def out_ch(self):
        return self.mid_ch * 4

    def convs(self):
        # the 3×3 is the spatial hot spot: HaloConv runs it as the
        # overlapped halo pipeline under spatial/ds sharding (stride-2
        # bottleneck entries fall back to the plain path automatically)
        return {
            "conv1": Conv(self.in_ch, self.mid_ch, (1, 1), use_bias=False,
                          dtype=self.dtype),
            "conv2": HaloConv(self.mid_ch, self.mid_ch, (3, 3),
                              strides=(self.stride, self.stride),
                              use_bias=False, dtype=self.dtype),
            "conv3": Conv(self.mid_ch, self.out_ch, (1, 1), use_bias=False,
                          dtype=self.dtype),
        }

    def params_spec(self):
        spec = {k: c.params_spec() for k, c in self.convs().items()}
        spec["bn1"] = BatchNorm(self.mid_ch).params_spec()
        spec["bn2"] = BatchNorm(self.mid_ch).params_spec()
        spec["bn3"] = BatchNorm(self.out_ch).params_spec()
        if self.stride != 1 or self.in_ch != self.out_ch:
            spec["proj"] = Conv(self.in_ch, self.out_ch, (1, 1),
                                strides=(self.stride, self.stride),
                                use_bias=False, dtype=self.dtype).params_spec()
            spec["bn_proj"] = BatchNorm(self.out_ch).params_spec()
        return spec

    def apply(self, params, x, ctx: ShardingCtx = NULL_CTX, train=True):
        convs = self.convs()
        y = convs["conv1"].apply(params["conv1"], x, ctx)
        y = jax.nn.relu(BatchNorm(self.mid_ch).apply(params["bn1"], y, ctx, train))
        y = ctx.constrain(y, ("batch", "spatial", None, "conv_out"))
        y = convs["conv2"].apply(params["conv2"], y, ctx)
        y = jax.nn.relu(BatchNorm(self.mid_ch).apply(params["bn2"], y, ctx, train))
        y = convs["conv3"].apply(params["conv3"], y, ctx)
        y = BatchNorm(self.out_ch).apply(params["bn3"], y, ctx, train)
        if "proj" in params:
            sc = Conv(self.in_ch, self.out_ch, (1, 1),
                      strides=(self.stride, self.stride), use_bias=False,
                      dtype=self.dtype).apply(params["proj"], x, ctx)
            sc = BatchNorm(self.out_ch).apply(params["bn_proj"], sc, ctx, train)
        else:
            sc = x
        y = jax.nn.relu(y + sc)
        return ctx.constrain(y, ("batch", "spatial", None, "conv_out"))


@dataclass(frozen=True)
class ResNet:
    cfg: ResNetConfig

    def _blocks(self):
        c = self.cfg
        blocks = []
        in_ch = c.width
        for stage, n in enumerate(c.stage_sizes):
            mid = c.width * (2 ** stage)
            for b in range(n):
                stride = 2 if (b == 0 and stage > 0) else 1
                blocks.append(Bottleneck(in_ch, mid, stride, c.dtype))
                in_ch = mid * 4
        return blocks

    def _head(self):
        # the last stage's output width: 2048 at width 64 over four stages
        c = self.cfg
        return Dense(c.width * 2 ** (len(c.stage_sizes) - 1) * 4, c.n_classes,
                     use_bias=True, in_axis="mlp", out_axis="vocab",
                     dtype=c.dtype)

    def params_spec(self):
        c = self.cfg
        spec = {
            "stem": HaloConv(3, c.width, (7, 7), strides=(2, 2),
                             use_bias=False, dtype=c.dtype).params_spec(),
            "bn_stem": BatchNorm(c.width).params_spec(),
            "blocks": [b.params_spec() for b in self._blocks()],
            "head": self._head().params_spec(),
        }
        return spec

    def apply(self, params, x, ctx: ShardingCtx = NULL_CTX, train=True):
        """Scoped for the profiler: ``stem``, ``stage{s}/block{b}`` for each
        bottleneck (both counted from 0), ``head``."""
        c = self.cfg
        with jax.named_scope("stem"):
            h = HaloConv(3, c.width, (7, 7), strides=(2, 2), use_bias=False,
                         dtype=c.dtype).apply(params["stem"], x, ctx)
            h = jax.nn.relu(BatchNorm(c.width).apply(params["bn_stem"], h,
                                                     ctx, train))
            h = max_pool(h, (3, 3), (2, 2), "SAME")
        where = [(s, b) for s, n in enumerate(c.stage_sizes) for b in range(n)]
        for (s, b), block, p in zip(where, self._blocks(), params["blocks"]):
            with jax.named_scope(f"stage{s}"), jax.named_scope(f"block{b}"):
                h = block.apply(p, h, ctx, train)
        with jax.named_scope("head"):
            return self._head().apply(params["head"], global_avg_pool(h), ctx)

    def loss_fn(self, params, batch, ctx: ShardingCtx = NULL_CTX, train=True):
        logits = self.apply(params, batch["images"], ctx, train)
        ce = _softmax_xent(logits, batch["labels"])
        return ce, {"ce": ce}

    def num_params(self):
        return tree_num_params(self.params_spec())


# ---------------------------------------------------------------------------
# VGG16
# ---------------------------------------------------------------------------
_VGG16_LAYOUT = (64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
                 512, 512, 512, "M", 512, 512, 512, "M")


@dataclass(frozen=True)
class VGGConfig:
    name: str = "vgg16"
    n_classes: int = 1000
    img: int = 224
    dtype: Any = jnp.float32


@dataclass(frozen=True)
class VGG:
    cfg: VGGConfig

    def _convs(self):
        convs, in_ch = [], 3
        for v in _VGG16_LAYOUT:
            if v == "M":
                convs.append("M")
            else:
                convs.append(HaloConv(in_ch, v, (3, 3), dtype=self.cfg.dtype))
                in_ch = v
        return convs

    def params_spec(self):
        c = self.cfg
        feat = c.img // 32
        spec = {"convs": [x.params_spec() for x in self._convs() if x != "M"]}
        spec["fc1"] = Dense(512 * feat * feat, 4096, use_bias=True,
                            in_axis="mlp", out_axis="embed",
                            dtype=c.dtype).params_spec()
        spec["fc2"] = Dense(4096, 4096, use_bias=True, in_axis="embed",
                            out_axis="mlp", dtype=c.dtype).params_spec()
        spec["fc3"] = Dense(4096, c.n_classes, use_bias=True, in_axis="mlp",
                            out_axis="vocab", dtype=c.dtype).params_spec()
        return spec

    def apply(self, params, x, ctx: ShardingCtx = NULL_CTX, train=True):
        c = self.cfg
        h, i = x, 0
        for layer in self._convs():
            if layer == "M":
                h = max_pool(h, (2, 2), (2, 2), "VALID")
            else:
                h = jax.nn.relu(layer.apply(params["convs"][i], h, ctx))
                h = ctx.constrain(h, ("batch", "spatial", None, "conv_out"))
                i += 1
        h = h.reshape(h.shape[0], -1)
        feat = c.img // 32
        h = jax.nn.relu(Dense(512 * feat * feat, 4096, use_bias=True,
                              in_axis="mlp", out_axis="embed",
                              dtype=c.dtype).apply(params["fc1"], h, ctx))
        h = jax.nn.relu(Dense(4096, 4096, use_bias=True, in_axis="embed",
                              out_axis="mlp", dtype=c.dtype).apply(
                                  params["fc2"], h, ctx))
        return Dense(4096, c.n_classes, use_bias=True, in_axis="mlp",
                     out_axis="vocab", dtype=c.dtype).apply(params["fc3"], h, ctx)

    def loss_fn(self, params, batch, ctx: ShardingCtx = NULL_CTX, train=True):
        logits = self.apply(params, batch["images"], ctx, train)
        ce = _softmax_xent(logits, batch["labels"])
        return ce, {"ce": ce}

    def num_params(self):
        return tree_num_params(self.params_spec())


# ---------------------------------------------------------------------------
# CosmoFlow (3-D CNN, regression) — the paper's ds-hybrid flagship
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class CosmoFlowConfig:
    name: str = "cosmoflow"
    img: int = 128               # cube edge (paper uses 256³/512³; smoke uses less)
    in_ch: int = 4
    n_targets: int = 4
    width: int = 16
    n_conv: int = 5
    dtype: Any = jnp.float32


@dataclass(frozen=True)
class CosmoFlow:
    cfg: CosmoFlowConfig

    def _convs(self):
        c = self.cfg
        convs, in_ch = [], c.in_ch
        for i in range(c.n_conv):
            out = c.width * (2 ** i)
            convs.append(HaloConv(in_ch, out, (3, 3, 3), dtype=c.dtype))
            in_ch = out
        return convs

    def _flat_dim(self):
        c = self.cfg
        edge = c.img // (2 ** c.n_conv)
        return (c.width * 2 ** (c.n_conv - 1)) * edge ** 3

    def params_spec(self):
        spec = {"convs": [x.params_spec() for x in self._convs()]}
        spec["fc1"] = Dense(self._flat_dim(), 128, use_bias=True, in_axis="mlp",
                            out_axis="embed", dtype=self.cfg.dtype).params_spec()
        spec["fc2"] = Dense(128, 64, use_bias=True, in_axis="embed",
                            out_axis="mlp", dtype=self.cfg.dtype).params_spec()
        spec["out"] = Dense(64, self.cfg.n_targets, use_bias=True, in_axis="mlp",
                            out_axis=None, dtype=self.cfg.dtype).params_spec()
        return spec

    def apply(self, params, x, ctx: ShardingCtx = NULL_CTX, train=True):
        c = self.cfg
        h = x
        for i, conv in enumerate(self._convs()):
            h = jax.nn.leaky_relu(conv.apply(params["convs"][i], h, ctx))
            h = ctx.constrain(h, ("batch", "spatial", None, None, "conv_out"))
            h = max_pool(h, (2, 2, 2), (2, 2, 2), "VALID")
        h = h.reshape(h.shape[0], -1)
        h = jax.nn.leaky_relu(Dense(self._flat_dim(), 128, use_bias=True,
                                    in_axis="mlp", out_axis="embed",
                                    dtype=c.dtype).apply(params["fc1"], h, ctx))
        h = jax.nn.leaky_relu(Dense(128, 64, use_bias=True, in_axis="embed",
                                    out_axis="mlp", dtype=c.dtype).apply(
                                        params["fc2"], h, ctx))
        return Dense(64, c.n_targets, use_bias=True, in_axis="mlp",
                     out_axis=None, dtype=c.dtype).apply(params["out"], h, ctx)

    def loss_fn(self, params, batch, ctx: ShardingCtx = NULL_CTX, train=True):
        pred = self.apply(params, batch["images"], ctx, train)
        mse = jnp.mean((pred - batch["targets"]) ** 2)
        return mse, {"mse": mse}

    def num_params(self):
        return tree_num_params(self.params_spec())


def _softmax_xent(logits, labels):
    with jax.named_scope("loss"):
        logits = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - picked)
