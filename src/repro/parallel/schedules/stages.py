"""Stage parameter layouts for the schedule executors.

Uniform TransformerLM stacks shard their (L, ...) stacked block params over
the stage axis: ``stack_stages`` (equal cuts), ``stack_stage_bounds`` (the DP
partitioner's non-uniform cuts, padded + masked) and
``stack_virtual_stage_bounds`` (v·p round-robin chunks for the interleaved
schedule). ``make_stage_fn`` / ``make_masked_stage_fn`` /
``make_virtual_stage_fn`` turn a per-block apply into the matching stage
program. Heterogeneous trunks (CNNs, mixed LM patterns) use per-stage
program specialization instead — see ``hetero.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def stack_stages(layer_params_stacked, n_stages: int):
    """(L, ...) stacked layer params → (n_stages, L/n_stages, ...)."""

    def reshape(x):
        L = x.shape[0]
        if L % n_stages:
            raise ValueError(f"{L} layers do not divide {n_stages} stages")
        return x.reshape(n_stages, L // n_stages, *x.shape[1:])

    return jax.tree.map(reshape, layer_params_stacked)


def make_stage_fn(block_apply):
    """Stage = scan over the layers owned by this stage.

    block_apply(one_layer_params, x) -> y
    """

    def stage_fn(stage_params, x):
        def body(h, lp):
            return block_apply(lp, h), None

        y, _ = jax.lax.scan(body, x, stage_params)
        return y

    return stage_fn


# ---------------------------------------------------------------------------
# Non-uniform stages (DP partitioner cuts)
# ---------------------------------------------------------------------------

def stack_stage_bounds(layer_params_stacked, bounds):
    """(L, ...) stacked layer params + partition bounds → the SPMD stage
    layout: ((n_stages, m, ...) padded stacks, (n_stages, m) validity mask),
    m = max stage length.

    Stages may own unequal layer counts (core/partition.py DP cuts); padded
    slots repeat the stage's last layer so every rank scans identical shapes,
    and the mask turns padded slots into identity in the stage scan (their
    parameters receive exactly-zero gradients through the ``where``).
    """
    bounds = tuple(int(b) for b in bounds)
    k = len(bounds) - 1
    counts = [bounds[i + 1] - bounds[i] for i in range(k)]
    if min(counts) < 1:
        raise ValueError(f"empty stage in bounds {bounds}")
    m = max(counts)
    # one gather per leaf, NOT concat-of-slices: under jit, XLA's SPMD
    # partitioner has miscompiled a concat/stack of slices feeding a
    # shard_map with P(stage) in_specs (values silently wrong); a single
    # take lowers to a clean gather that reshards correctly. Padded slots
    # clamp to the stage's last layer; the mask keeps their cotangents at
    # exactly zero, so the duplicated layer sees no spurious gradient.
    idx = jnp.asarray([min(bounds[i] + j, bounds[i + 1] - 1)
                       for i in range(k) for j in range(m)])
    mask = jnp.array([[j < c for j in range(m)] for c in counts])
    restack = lambda x: jnp.take(x, idx, axis=0).reshape(k, m, *x.shape[1:])
    return jax.tree.map(restack, layer_params_stacked), mask


def stack_virtual_stage_bounds(layer_params_stacked, bounds,
                               n_stages: int, virtual_stages: int):
    """(L, ...) stacked layer params + v·p chunk bounds → the interleaved
    SPMD layout: ((p, v, m, ...) padded stacks, (p, v, m) validity mask).

    Chunk j = q·p + r of the contiguous DP partition goes to rank r,
    virtual slot q — the round-robin assignment the interleaved schedule's
    ring permute expects. Same single-gather restack (and the same
    concat-of-slices caveat) as ``stack_stage_bounds``.
    """
    bounds = tuple(int(b) for b in bounds)
    p, v = int(n_stages), int(virtual_stages)
    k = len(bounds) - 1
    if k != p * v:
        raise ValueError(f"{k} chunks in bounds for p={p}, v={v}")
    counts = [bounds[i + 1] - bounds[i] for i in range(k)]
    if min(counts) < 1:
        raise ValueError(f"empty chunk in bounds {bounds}")
    m = max(counts)
    idx = jnp.asarray([min(bounds[q * p + r] + j, bounds[q * p + r + 1] - 1)
                       for r in range(p) for q in range(v) for j in range(m)])
    mask = jnp.array([[[j < counts[q * p + r] for j in range(m)]
                       for q in range(v)] for r in range(p)])
    restack = lambda x: jnp.take(x, idx, axis=0).reshape(
        p, v, m, *x.shape[1:])
    return jax.tree.map(restack, layer_params_stacked), mask


def make_masked_stage_fn(block_apply):
    """Stage = masked scan over the (padded) layer slots this stage owns;
    stage params are the ``stack_stage_bounds`` layout:
    {"layers": (m, ...) pytree, "mask": (m,) bool}."""

    def stage_fn(stage_params, x):
        def body(h, slot):
            lp, valid = slot
            return jnp.where(valid, block_apply(lp, h), h), None

        y, _ = jax.lax.scan(body, x,
                            (stage_params["layers"], stage_params["mask"]))
        return y

    return stage_fn


def make_virtual_stage_fn(block_apply):
    """Interleaved stage program over the ``stack_virtual_stage_bounds``
    layout: select virtual chunk q (a traced index) out of the rank's
    (v, m, ...) slots, then run the masked stage scan over it."""
    inner = make_masked_stage_fn(block_apply)

    def stage_fn(rank_params, x, q):
        chunk = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, q, axis=0,
                                                   keepdims=False),
            rank_params)
        return inner(chunk, x)

    return stage_fn


def block_costs_from_stats(stats, n_layers: int):
    """Per-BLOCK fw+bw FLOP cost vector from oracle layer stats.

    ``lm_stats`` names per-layer entries ``L{i}.<part>`` (attn/ffn/...);
    each block's cost is the sum over its parts. Backward FLOPs come from
    the stat's exact per-layer value when the extractor recorded one
    (``LayerStat.flops_bwd_exact`` — CNN stride/pool layers break the
    bw ≈ 2×fw rule), falling back to the 2×fw approximation (3×fw total)
    only when absent. Embed and head entries carry no ``L{i}.`` prefix and
    are excluded — they run replicated outside the stage schedule. Falls
    back to uniform costs if the stats carry no per-block entries.
    """
    import re
    import numpy as np
    costs = np.zeros(n_layers)
    for st in stats:
        m = re.match(r"L(\d+)\.", st.name)
        if m and int(m.group(1)) < n_layers:
            bwd = st.flops_bwd_exact or 2.0 * st.flops_fwd
            costs[int(m.group(1))] += st.flops_fwd + bwd
    return costs if costs.any() else np.ones(n_layers)
