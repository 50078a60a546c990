"""Pallas TPU flash attention (forward), FlashAttention-2 style.

Grid: (B·H, S/BQ). Each program streams KV blocks from HBM-resident refs
while q stays in VMEM; running max / sum / output accumulator live in VMEM
scratch. Block shapes are MXU-aligned (BQ×D, BK×D with D a multiple of 128
for full MXU utilization on the TARGET TPU; interpret=True validates the
same body on CPU).

Hardware adaptation note (DESIGN.md §6): the CUDA flash kernel tiles for SRAM +
warps; here tiling is VMEM-sized (BQ·D + 2·BK·D + BQ·BK fp32 ≪ ~128 MiB)
and the contraction shapes feed the 128×128 MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..util import largest_divisor

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int,
                      seq_len: int, causal: bool, scale: float):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale            # (BQ, D)

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    s0 = jnp.zeros((bq,), jnp.float32)
    o0 = jnp.zeros((bq, q.shape[-1]), jnp.float32)
    nk = seq_len // bk
    # causal: skip KV blocks strictly past this q block
    nk_eff = jnp.minimum(nk, (qi + 1) * bq // bk + (1 if bq % bk else 0)) \
        if causal else nk

    def body(ki, carry):
        m, s, o = carry
        k = k_ref[pl.ds(ki * bk, bk), :].astype(jnp.float32)  # (BK, D)
        v = v_ref[pl.ds(ki * bk, bk), :].astype(jnp.float32)
        logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (BQ,BK)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            logits = jnp.where(qpos >= kpos, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        s_new = s * alpha + jnp.sum(p, axis=-1)
        o_new = o * alpha[:, None] + jax.lax.dot(p.astype(v.dtype), v)
        return m_new, s_new, o_new

    m, s, o = jax.lax.fori_loop(0, nk_eff, body, (m0, s0, o0))
    o_ref[...] = (o / jnp.maximum(s, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, block_q: int = 128,
                        block_k: int = 128, interpret: bool = False):
    """q,k,v: (B, H, S, D) → (B, H, S, D).

    Block sizes that do not divide S fall back to the largest divisor ≤ the
    request (as rmsnorm does), so odd sequence lengths run instead of
    crashing — the grid and the KV loop both need exact tiling.
    """
    B, H, S, D = q.shape
    bq = largest_divisor(S, block_q)
    bk = largest_divisor(S, block_k)
    scale = 1.0 / np.sqrt(D)
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, D)

    kernel = functools.partial(_flash_fwd_kernel, bq=bq, bk=bk, seq_len=S,
                               causal=causal, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, S // bq),
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, S, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, D)
