"""Per-stage program specialization for heterogeneous pipeline trunks.

Uniform TransformerLM stacks pipeline by sharding their stacked block params
over the stage axis (stages.py). CNN trunks and mixed LM patterns cannot:
blocks differ in parameter structure AND activation shape (spatial
downsampling), so there is no stacked-leaf layout to shard. Instead each
model decomposes into an ordered list of :class:`PipeBlock` closures over
the *full* (replicated) parameter tree, activations travel the pipe as a
flat padded buffer sized to the largest stage boundary, and every rank runs
a ``lax.switch`` on its axis index that selects its specialized stage
program — SPMD-valid (one program), while each branch unflattens its own
input shape, applies its contiguous block slice, and reflattens.

Gradients are exact: ``lax.switch`` routes cotangents only through the
selected branch, and the shard_map transpose psums the per-rank (zero
except own-stage) parameter cotangents into the full gradient.

The trade against the stacked path: parameters are replicated across ranks
(each rank touches only its slice, but holds all of them) — the right
realization for the host executor; a memory-sharded variant would gather
per-stage subsets instead.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class PipeBlock:
    """One schedulable unit of a heterogeneous trunk.

    ``apply(full_params, x) -> y`` maps a batched activation through the
    block; ``cost`` is the fw+bw FLOP weight the DP partitioner cuts on.
    """
    name: str
    apply: Callable
    cost: float = 1.0


def model_pipe_blocks(model, stats=None, **fwd_kw) -> list[PipeBlock]:
    """Decompose a model into pipeline blocks (full forward for CNNs —
    stem through head; trunk layers only for LMs, whose embed/head run
    replicated outside the pipe).

    ``stats`` (the oracle's per-layer table) supplies per-block fw+bw costs
    — exact backward FLOPs when the extractor recorded them
    (``flops_bwd_exact``), else the 2×fw approximation; uniform costs
    without stats.
    """
    from ...models.cnn import CosmoFlow, ResNet, VGG
    from ...models.transformer import TransformerLM
    if isinstance(model, ResNet):
        return _resnet_blocks(model, stats)
    if isinstance(model, VGG):
        return _vgg_blocks(model, stats)
    if isinstance(model, CosmoFlow):
        return _cosmoflow_blocks(model, stats)
    if isinstance(model, TransformerLM):
        return _lm_layer_blocks(model, stats, **fwd_kw)
    raise NotImplementedError(
        f"{type(model).__name__}: no pipeline block decomposition")


def pipeline_block_count(cfg) -> int | None:
    """Schedulable block count for a model config (the executor's stage
    ceiling — distinct from the oracle's stat-layer count G), or None when
    the model cannot pipeline."""
    from ...models.cnn import CosmoFlowConfig, ResNetConfig, VGGConfig
    from ...models.transformer import LMConfig
    if isinstance(cfg, ResNetConfig):
        return 2 + sum(cfg.stage_sizes)          # stem + bottlenecks + head
    if isinstance(cfg, VGGConfig):
        from ...models.cnn import _VGG16_LAYOUT
        return sum(1 for x in _VGG16_LAYOUT if x != "M") + 1   # convs + head
    if isinstance(cfg, CosmoFlowConfig):
        return cfg.n_conv + 1                    # conv blocks + head
    if isinstance(cfg, LMConfig):
        return cfg.n_layers                      # embed/head stay outside
    return None


def pipeline_block_costs(model, stats=None, **fwd_kw):
    """Per-block fw+bw cost vector for the DP stage partitioner — the
    model's pipeline decomposition weighted by the oracle's layer stats
    (exact backward FLOPs when recorded)."""
    import numpy as np
    return np.asarray(
        [b.cost for b in model_pipe_blocks(model, stats, **fwd_kw)])


def _stat_cost(st) -> float:
    return st.flops_fwd + (st.flops_bwd_exact or 2.0 * st.flops_fwd)


def _grouped_costs(names: list[str], stats) -> list[float]:
    """Sum stat costs onto blocks by longest-prefix name match; blocks with
    no matching stats (or no stats at all) get uniform weight 1."""
    if stats is None:
        return [1.0] * len(names)
    costs = [0.0] * len(names)
    for st in stats:
        best = None
        for i, nm in enumerate(names):
            if st.name == nm or st.name.startswith(nm):
                if best is None or len(names[best]) < len(nm):
                    best = i
        if best is not None:
            costs[best] += _stat_cost(st)
    return costs if any(costs) else [1.0] * len(names)


def _resnet_blocks(model, stats) -> list[PipeBlock]:
    from ...models.cnn import BatchNorm, HaloConv, global_avg_pool, max_pool
    from ...nn.module import NULL_CTX
    c = model.cfg

    def stem(params, x):
        h = HaloConv(3, c.width, (7, 7), strides=(2, 2), use_bias=False,
                     dtype=c.dtype).apply(params["stem"], x, NULL_CTX)
        h = jax.nn.relu(
            BatchNorm(c.width).apply(params["bn_stem"], h, NULL_CTX, True))
        return max_pool(h, (3, 3), (2, 2), "SAME")

    def head(params, x):
        return model._head().apply(params["head"], global_avg_pool(x),
                                   NULL_CTX)

    names, applies = ["stem"], [stem]
    bottlenecks = model._blocks()
    i = 0
    for stage, n in enumerate(c.stage_sizes):
        for bb in range(n):
            blk = bottlenecks[i]
            applies.append(lambda params, x, blk=blk, i=i: blk.apply(
                params["blocks"][i], x, NULL_CTX, True))
            names.append(f"s{stage}b{bb}")
            i += 1
    names.append("head")
    applies.append(head)
    costs = _grouped_costs(names, stats)
    return [PipeBlock(nm, ap, ct)
            for nm, ap, ct in zip(names, applies, costs)]


def _vgg_blocks(model, stats) -> list[PipeBlock]:
    from ...models.cnn import _VGG16_LAYOUT, Dense, max_pool
    from ...nn.module import NULL_CTX
    c = model.cfg
    convs = [x for x in model._convs() if x != "M"]
    pool_after = []
    ci = -1
    for x in _VGG16_LAYOUT:
        if x == "M":
            pool_after[ci] = True
        else:
            ci += 1
            pool_after.append(False)

    names, applies = [], []
    for i, conv in enumerate(convs):
        def conv_block(params, x, conv=conv, i=i, pool=pool_after[i]):
            h = jax.nn.relu(conv.apply(params["convs"][i], x, NULL_CTX))
            return max_pool(h, (2, 2), (2, 2), "VALID") if pool else h
        names.append(f"conv{i}")
        applies.append(conv_block)

    feat = c.img // 32

    def head(params, x):
        h = x.reshape(x.shape[0], -1)
        h = jax.nn.relu(Dense(512 * feat * feat, 4096, use_bias=True,
                              in_axis="mlp", out_axis="embed",
                              dtype=c.dtype).apply(params["fc1"], h, NULL_CTX))
        h = jax.nn.relu(Dense(4096, 4096, use_bias=True, in_axis="embed",
                              out_axis="mlp", dtype=c.dtype).apply(
                                  params["fc2"], h, NULL_CTX))
        return Dense(4096, c.n_classes, use_bias=True, in_axis="mlp",
                     out_axis="vocab", dtype=c.dtype).apply(
                         params["fc3"], h, NULL_CTX)

    names.append("fc")
    applies.append(head)
    costs = _grouped_costs(names, stats)
    return [PipeBlock(nm, ap, ct)
            for nm, ap, ct in zip(names, applies, costs)]


def _cosmoflow_blocks(model, stats) -> list[PipeBlock]:
    from ...models.cnn import Dense, max_pool
    from ...nn.module import NULL_CTX
    c = model.cfg
    names, applies = [], []
    for i, conv in enumerate(model._convs()):
        def conv_block(params, x, conv=conv, i=i):
            h = jax.nn.leaky_relu(conv.apply(params["convs"][i], x, NULL_CTX))
            return max_pool(h, (2, 2, 2), (2, 2, 2), "VALID")
        names.append(f"conv{i}")
        applies.append(conv_block)

    def head(params, x):
        h = x.reshape(x.shape[0], -1)
        h = jax.nn.leaky_relu(
            Dense(model._flat_dim(), 128, use_bias=True, in_axis="mlp",
                  out_axis="embed", dtype=c.dtype).apply(
                      params["fc1"], h, NULL_CTX))
        h = jax.nn.leaky_relu(
            Dense(128, 64, use_bias=True, in_axis="embed", out_axis="mlp",
                  dtype=c.dtype).apply(params["fc2"], h, NULL_CTX))
        return Dense(64, c.n_targets, use_bias=True, in_axis="mlp",
                     out_axis=None, dtype=c.dtype).apply(
                         params["out"], h, NULL_CTX)

    names.append("fc")
    applies.append(head)
    costs = _grouped_costs(names, stats)
    return [PipeBlock(nm, ap, ct)
            for nm, ap, ct in zip(names, applies, costs)]


def _lm_layer_blocks(model, stats, **fwd_kw) -> list[PipeBlock]:
    """Mixed-pattern trunks: one PipeBlock per layer, each closing over the
    layer's position in the lead/stacks/tail parameter layout."""
    from ...models.transformer import Block
    from ...nn.module import NULL_CTX
    from .stages import block_costs_from_stats
    c = model.cfg
    period, n_groups, rem = model._groups()
    kw = {k: v for k, v in fwd_kw.items()
          if k in ("attn_impl", "q_chunk", "kv_chunk")}

    def layer_block(j: int) -> PipeBlock:
        if j < c.first_k_dense:
            kind, get = "attn", (lambda p, j=j: p["lead"][j])
        else:
            i = j - c.first_k_dense
            g, pos = divmod(i, period)
            if g < n_groups:
                kind = c.pattern[pos]
                get = lambda p, g=g, pos=pos: jax.tree.map(  # noqa: E731
                    lambda x: x[g], p["stacks"][pos])
            else:
                r = i - n_groups * period
                kind, get = rem[r], (lambda p, r=r: p["tail"][r])
        blk = Block(c, kind)

        def run(params, h):
            y, _aux = blk.apply(get(params), h, NULL_CTX, **kw)
            return y

        return PipeBlock(f"L{j}.{kind}", run, 1.0)

    blocks = [layer_block(j) for j in range(c.n_layers)]
    if stats is not None:
        costs = block_costs_from_stats(stats, c.n_layers)
        blocks = [PipeBlock(b.name, b.apply, float(ct))
                  for b, ct in zip(blocks, costs)]
    return blocks


# ---------------------------------------------------------------------------
# Flat activation buffer + switch-specialized stage programs
# ---------------------------------------------------------------------------

def boundary_shapes(blocks: list[PipeBlock], params, x0) -> list[tuple]:
    """Per-sample activation shape entering each block, plus the final
    output shape (len(blocks)+1 entries). Shape-only evaluation — works on
    tracers and concrete params alike."""
    aparams = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    x = jax.ShapeDtypeStruct(x0.shape, x0.dtype)
    shapes = [tuple(x.shape[1:])]
    for blk in blocks:
        x = jax.eval_shape(blk.apply, aparams, x)
        shapes.append(tuple(x.shape[1:]))
    return shapes


def make_switch_stage_fns(blocks: list[PipeBlock], bounds, shapes,
                          axis: str, n_stages: int):
    """Specialized stage programs for a heterogeneous trunk.

    Returns (stage_fn, virtual_stage_fn, K): ``stage_fn(params, buf)``
    switches on the rank index (gpipe / 1F1B over p = len(bounds)−1
    stages); ``virtual_stage_fn(params, buf, q)`` switches on the global
    chunk index q·p + rank (interleaved). K is the flat buffer width — the
    largest per-sample boundary activation, zero-padded so one ppermute
    carrier shape serves every stage boundary.
    """
    bounds = tuple(int(b) for b in bounds)
    sizes = [int(math.prod(s)) for s in shapes]
    K = max(sizes[b] for b in bounds) if bounds else max(sizes)
    K = max(K, sizes[-1])

    def branch(b0: int, b1: int):
        ishape, isize = shapes[b0], sizes[b0]

        def run(params, buf):
            mb = buf.shape[0]
            x = buf[:, :isize].reshape(mb, *ishape)
            for blk in blocks[b0:b1]:
                x = blk.apply(params, x)
            y = x.reshape(mb, -1)
            if y.shape[1] < K:
                y = jnp.pad(y, ((0, 0), (0, K - y.shape[1])))
            return y.astype(buf.dtype)

        return run

    branches = [branch(bounds[j], bounds[j + 1])
                for j in range(len(bounds) - 1)]

    def stage_fn(params, buf):
        idx = jax.lax.axis_index(axis)
        return jax.lax.switch(idx, branches, params, buf)

    def virtual_stage_fn(params, buf, q):
        idx = jax.lax.axis_index(axis)
        return jax.lax.switch(q * n_stages + idx, branches, params, buf)

    return stage_fn, virtual_stage_fn, K


def to_buffer(x, K: int):
    """Batched activation → (B, K) zero-padded flat buffer."""
    flat = x.reshape(x.shape[0], -1)
    if flat.shape[1] < K:
        flat = jnp.pad(flat, ((0, 0), (0, K - flat.shape[1])))
    return flat


def from_buffer(buf, shape: tuple, dtype=None):
    """(B, K) flat buffer → batched activation of per-sample ``shape``."""
    n = int(math.prod(shape))
    out = buf[:, :n].reshape(buf.shape[0], *shape)
    return out.astype(dtype) if dtype is not None else out
