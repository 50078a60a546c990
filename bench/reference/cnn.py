"""Plain reference of the ResNet training step, and the resident batches.

Straightforward ``jax.numpy``: the model's forward pass, its loss, the
gradient and the Adam update with global-norm clipping, sized from the
configuration file alone. It imports nothing of the program. Its parameter
tree is laid out as the program's checkpoint is (``stem``, ``blocks``,
``head``, ...), so the benchmark can hand the same weights to both.

``dtype`` is the type the forward and backward passes hold their
activations in: float32 for the reference, bfloat16 for the control.
``precision`` is the matmul precision the configuration states, for every
convolution and product of both. The parameters are updated in the type
they are held in, which is the caller's: float32 for the reference, and
bfloat16 for the control that holds them as a model built in bfloat16
would. The optimizer's state is float32 in all of them.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: the configuration's ``matmul_precision`` -> the products' precision
PRECISION = {"default": jax.lax.Precision.DEFAULT,
             "high": jax.lax.Precision.HIGH,
             "highest": jax.lax.Precision.HIGHEST}


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _conv_w(k, cin, cout):
    return {"w": (tuple(k) + (cin, cout), math.prod(k) * cin)}


def _bn(c):
    return {"scale": ((c,), None), "bias": ((c,), 0)}


def _dense(a, b):
    return {"w": ((a, b), a), "b": ((b,), 0)}


def resnet_shapes(m: dict) -> dict:
    """Leaf -> (shape, fan-in); fan-in None means ones, 0 means zeros."""
    w = m["width"]
    tree = {"stem": _conv_w((7, 7), m["in_ch"], w), "bn_stem": _bn(w),
            "blocks": []}
    cin = w
    for stage, n in enumerate(m["stage_sizes"]):
        mid = w * 2 ** stage
        for b in range(n):
            stride = 2 if (b == 0 and stage > 0) else 1
            blk = {"conv1": _conv_w((1, 1), cin, mid),
                   "conv2": _conv_w((3, 3), mid, mid),
                   "conv3": _conv_w((1, 1), mid, 4 * mid),
                   "bn1": _bn(mid), "bn2": _bn(mid), "bn3": _bn(4 * mid)}
            if stride != 1 or cin != 4 * mid:
                blk["proj"] = _conv_w((1, 1), cin, 4 * mid)
                blk["bn_proj"] = _bn(4 * mid)
            tree["blocks"].append(blk)
            cin = 4 * mid
    tree["head"] = _dense(cin, m["n_classes"])
    return tree


SHAPES = {"resnet": resnet_shapes}


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def init_params(key, m: dict):
    """Seeded float32 weights: normal with 1/sqrt(fan-in) deviation,
    zero biases, unit BatchNorm scales. One call makes every leaf."""
    shapes = SHAPES[m["kind"]](m)
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=_is_leaf)
    out = []
    for i, (shape, fan) in enumerate(leaves):
        if fan is None:
            out.append(jnp.ones(shape, jnp.float32))
        elif fan == 0:
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            k = jax.random.fold_in(key, i)
            out.append(jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan))
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# resident batches
# ---------------------------------------------------------------------------

def make_batches(key, m: dict, batch: int, count: int) -> list[dict]:
    """``count`` distinct batches: standard normal images, labels uniform
    over the classes."""
    out = []
    for i in range(count):
        kx, ky = jax.random.split(jax.random.fold_in(key, i))
        shape = (batch, m["img"], m["img"], m["in_ch"])
        out.append({
            "images": jax.random.normal(kx, shape, jnp.float32),
            "labels": jax.random.randint(ky, (batch,), 0, m["n_classes"],
                                         jnp.int32)})
    return out


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------

def _conv(x, w, stride, precision):
    """SAME convolution, NHWC."""
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _batchnorm(x, p, eps=1e-5):
    """Training-mode BatchNorm over every axis but channels; statistics in
    float32 whatever the activations' dtype."""
    xf = x.astype(jnp.float32)
    axes = tuple(range(x.ndim - 1))
    mu = xf.mean(axes)
    var = ((xf - mu) ** 2).mean(axes)
    y = (xf - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


def _max_pool(x, k, s):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, k, k, 1),
                                 (1, s, s, 1), "SAME")


def resnet_forward(params, x, m: dict, precision):
    relu = jax.nn.relu

    def conv(h, w, stride=1):
        return _conv(h, w, stride, precision)

    h = relu(_batchnorm(conv(x, params["stem"]["w"], 2), params["bn_stem"]))
    h = _max_pool(h, 3, 2)
    i = 0
    for stage, n in enumerate(m["stage_sizes"]):
        for b in range(n):
            stride = 2 if (b == 0 and stage > 0) else 1
            p = params["blocks"][i]
            y = relu(_batchnorm(conv(h, p["conv1"]["w"]), p["bn1"]))
            y = relu(_batchnorm(conv(y, p["conv2"]["w"], stride), p["bn2"]))
            y = _batchnorm(conv(y, p["conv3"]["w"]), p["bn3"])
            sc = h
            if "proj" in p:
                sc = _batchnorm(conv(h, p["proj"]["w"], stride), p["bn_proj"])
            h = relu(y + sc)
            i += 1
    h = h.mean(axis=(1, 2))
    return jnp.dot(h, params["head"]["w"].astype(h.dtype),
                   precision=precision) + params["head"]["b"].astype(h.dtype)


def loss(params, batch, m: dict, dtype=jnp.float32, precision="default"):
    """Mean softmax cross-entropy, the forward pass holding its activations
    in ``dtype``."""
    x = batch["images"].astype(dtype)
    logits = resnet_forward(params, x, m, PRECISION[precision])
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, batch["labels"][:, None], -1)[:, 0]
    return jnp.mean(lse - picked)


# ---------------------------------------------------------------------------
# one training step
# ---------------------------------------------------------------------------

def train_step(params, m_state, v_state, count, batch, m: dict, opt: dict,
               lr: float, dtype=jnp.float32, precision="default"):
    """Adam with global-norm clipping, as ``opt`` states it. ``count`` is
    the 1-based step. The update is computed in float32 and stored in the
    parameters' dtype. Returns the new state, the loss, the clipped gradient
    the update used and the gradient's norm before clipping."""
    def f(p):
        return loss(p, batch, m, dtype, precision)

    val, g = jax.value_and_grad(f)(params)
    g = jax.tree.map(lambda a: a.astype(jnp.float32), g)
    norm = jnp.sqrt(sum(jnp.sum(a * a) for a in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(norm, 1e-9))
    g = jax.tree.map(lambda a: a * scale, g)
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    m_new = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m_state, g)
    v_new = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v_state, g)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    p_new = jax.tree.map(
        lambda p, mm, vv: (p.astype(jnp.float32) - lr * (
            (mm / c1) / (jnp.sqrt(vv / c2) + eps)
            + opt["weight_decay"] * p.astype(jnp.float32))).astype(p.dtype),
        params, m_new, v_new)
    return p_new, m_new, v_new, val, g, norm
