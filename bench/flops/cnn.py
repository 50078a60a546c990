"""Operations and bytes of a CNN training step, counted from shapes.

The benchmark's own arithmetic: it reads the sizes in a configuration file
and nothing of the program. A multiply-add is two operations. For each
convolution the forward pass, the input gradient and the weight gradient do
the same multiply-adds, so each costs the forward's operations; the first
layer's input gradient is not needed and is not counted. Nothing that a
program recomputes is counted. Bytes are the least a pass must move: read
its two operands once and write its result once, in the dtype of the
configuration.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

PASSES = ("fwd", "dgrad", "wgrad")


@dataclass(frozen=True)
class Conv:
    """One convolution of one sample: spatial extents, kernel, channels."""

    name: str
    spatial_in: tuple[int, ...]
    spatial_out: tuple[int, ...]
    kernel: tuple[int, ...]
    cin: int
    cout: int

    @property
    def macs(self) -> int:
        return (math.prod(self.spatial_out) * math.prod(self.kernel)
                * self.cin * self.cout)

    def flops(self, batch: int, pass_: str = "fwd") -> float:
        assert pass_ in PASSES
        return 2.0 * batch * self.macs

    def bytes(self, batch: int, pass_: str, itemsize: int = 4) -> float:
        x = batch * math.prod(self.spatial_in) * self.cin
        y = batch * math.prod(self.spatial_out) * self.cout
        w = math.prod(self.kernel) * self.cin * self.cout
        # fwd: x, w -> y; dgrad: dy, w -> dx; wgrad: x, dy -> dw
        return float(itemsize * (x + w + y))


@dataclass(frozen=True)
class Dense:
    name: str
    fan_in: int
    fan_out: int

    @property
    def macs(self) -> int:
        return self.fan_in * self.fan_out

    def flops(self, batch: int, pass_: str = "fwd") -> float:
        assert pass_ in PASSES
        return 2.0 * batch * self.macs

    def bytes(self, batch: int, pass_: str, itemsize: int = 4) -> float:
        return float(itemsize * (batch * (self.fan_in + self.fan_out)
                                 + self.macs))


def _same_out(spatial, stride):
    return tuple(-(-s // stride) for s in spatial)


def resnet_layers(m: dict) -> tuple[list[Conv], list[Dense]]:
    """ResNet v1.5 (stride on the 3x3 of a bottleneck), as ``m`` sizes it."""
    width, img = m["width"], m["img"]
    sp = (img, img)
    out = _same_out(sp, 2)
    convs = [Conv("stem", sp, out, (7, 7), m["in_ch"], width)]
    sp = _same_out(out, 2)                      # 3x3/2 max pool
    cin = width
    for stage, n in enumerate(m["stage_sizes"]):
        mid = width * 2 ** stage
        for b in range(n):
            stride = 2 if (b == 0 and stage > 0) else 1
            sp2 = _same_out(sp, stride)
            pre = f"s{stage}b{b}"
            convs += [Conv(f"{pre}c1", sp, sp, (1, 1), cin, mid),
                      Conv(f"{pre}c2", sp, sp2, (3, 3), mid, mid),
                      Conv(f"{pre}c3", sp2, sp2, (1, 1), mid, 4 * mid)]
            if stride != 1 or cin != 4 * mid:
                convs.append(Conv(f"{pre}proj", sp, sp2, (1, 1), cin, 4 * mid))
            sp, cin = sp2, 4 * mid
    return convs, [Dense("head", cin, m["n_classes"])]


def cosmoflow_layers(m: dict) -> tuple[list[Conv], list[Dense]]:
    """CosmoFlow: 3x3x3 SAME convs, each followed by a 2x2x2 max pool."""
    sp = (m["img"],) * 3
    cin, convs = m["in_ch"], []
    for i in range(m["n_conv"]):
        cout = m["width"] * 2 ** i
        convs.append(Conv(f"conv{i}", sp, sp, (3, 3, 3), cin, cout))
        sp, cin = tuple(s // 2 for s in sp), cout
    dims = [cin * math.prod(sp), *m["dense"], m["n_targets"]]
    dense = [Dense(f"fc{i}", a, b) for i, (a, b) in enumerate(zip(dims, dims[1:]))]
    return convs, dense


LAYERS = {"resnet": resnet_layers, "cosmoflow": cosmoflow_layers}


def layers(m: dict) -> tuple[list[Conv], list[Dense]]:
    return LAYERS[m["kind"]](m)


def forward_macs(m: dict) -> int:
    """Multiply-adds of one sample's forward pass (convolutions and dense)."""
    convs, dense = layers(m)
    return sum(c.macs for c in convs) + sum(d.macs for d in dense)


def train_flops_per_sample(m: dict) -> float:
    """Forward, weight gradient and input gradient of every layer, less the
    first layer's input gradient, which no one needs."""
    convs, dense = layers(m)
    return 2.0 * (3 * forward_macs(m) - convs[0].macs)


def roofline_s(layer_list, batch: int, peak_flops: float, hbm_bw: float,
               itemsize: int = 4, first: bool = True) -> float:
    """Least time the chip could spend on these layers' passes in one step:
    the sum over passes of the larger of operations over peak and bytes
    over bandwidth. ``first``: the list starts at the model's first layer,
    whose input gradient is not needed."""
    total = 0.0
    for i, layer in enumerate(layer_list):
        for p in PASSES:
            if first and i == 0 and p == "dgrad":
                continue
            total += max(layer.flops(batch, p) / peak_flops,
                         layer.bytes(batch, p, itemsize) / hbm_bw)
    return total


def conv_roofline_s(m: dict, batch: int, peak_flops: float, hbm_bw: float,
                    itemsize: int = 4) -> float:
    """Least time for every convolution pass of one step at this batch."""
    return roofline_s(layers(m)[0], batch, peak_flops, hbm_bw, itemsize)


def step_roofline_s(m: dict, batch: int, peak_flops: float, hbm_bw: float,
                    itemsize: int = 4) -> float:
    """Least time for every convolution and dense pass of one step."""
    convs, dense = layers(m)
    return (roofline_s(convs, batch, peak_flops, hbm_bw, itemsize)
            + roofline_s(dense, batch, peak_flops, hbm_bw, itemsize,
                         first=False))
