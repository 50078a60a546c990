"""Optimizers: SGD(+momentum) and AdamW, with ZeRO-1 sharded states.

States are declared as ParamSpec trees (same logical axes as their params) so
they ride the same rules tables. Under ZeRO-1 the states claim the *data*
axis on their first free dimension: XLA then reduce-scatters gradients into
the state sharding, updates locally, and all-gathers fresh params — the
paper's §5.3.3 "shard the weight update among GPUs" ([52] Xu et al.)
realized through shardings alone.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..nn.module import ParamSpec, Rules, param, tree_map_spec


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"            # "adamw" | "sgd"
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    momentum: float = 0.9          # sgd
    grad_clip: float = 1.0
    zero1: bool = True


def state_spec(opt: OptimizerConfig, params_spec):
    """ParamSpec tree(s) for optimizer state, fp32, same logical axes."""

    def clone(s: ParamSpec) -> ParamSpec:
        return param(s.shape, s.axes, init=lambda k, sh, d: jnp.zeros(sh, d),
                     dtype=jnp.float32)

    if opt.name == "adamw":
        return {"m": tree_map_spec(clone, params_spec),
                "v": tree_map_spec(clone, params_spec)}
    if opt.name == "sgd":
        return {"mom": tree_map_spec(clone, params_spec)}
    raise ValueError(opt.name)


def zero1_rules(rules: Rules) -> Rules:
    """Extend strategy rules so optimizer states shard over the data axis.

    State tensors reuse the parameter logical axes; mapping the axes that are
    free under the base strategy onto "data" shards the states p-ways (ZeRO-1).
    """
    extra = {}
    for ax in ("embed", "vocab", "mlp", "heads", "conv_in", "conv_k", "layers"):
        if rules.get(ax) is None:
            extra[ax] = "data"
    return rules.merged(extra)


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale), grads), norm


def apply_update(opt: OptimizerConfig, params, grads, state, step):
    """Pure update: returns (new_params, new_state, metrics). Its operations,
    the clip included, carry the ``optimizer`` scope in the compiled HLO."""
    with jax.named_scope("optimizer"):
        return _update(opt, params, grads, state, step)


def _update(opt: OptimizerConfig, params, grads, state, step):
    grads, gnorm = clip_by_global_norm(grads, opt.grad_clip)
    count = step.astype(jnp.float32) + 1.0

    if opt.name == "adamw":
        b1, b2 = opt.b1, opt.b2

        def upd(p, g, m, v):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mhat = m / (1 - b1 ** count)
            vhat = v / (1 - b2 ** count)
            step_ = opt.lr * (mhat / (jnp.sqrt(vhat) + opt.eps)
                              + opt.weight_decay * p.astype(jnp.float32))
            return (p.astype(jnp.float32) - step_).astype(p.dtype), m, v

        out = jax.tree.map(upd, params, grads, state["m"], state["v"])
        new_params = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
        new_m = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
        new_v = jax.tree.map(lambda t: t[2], out, is_leaf=lambda x: isinstance(x, tuple))
        return new_params, {"m": new_m, "v": new_v}, {"grad_norm": gnorm}

    if opt.name == "sgd":
        def upd(p, g, mom):
            mom = opt.momentum * mom + g
            return (p.astype(jnp.float32) - opt.lr * mom).astype(p.dtype), mom

        out = jax.tree.map(upd, params, grads, state["mom"])
        new_params = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
        new_mom = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
        return new_params, {"mom": new_mom}, {"grad_norm": gnorm}

    raise ValueError(opt.name)
