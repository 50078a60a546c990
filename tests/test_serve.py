"""Serving subsystem: paged KV cache, continuous batching, serving oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import LMConfig, TransformerLM
from repro.nn import AttentionConfig, FFNConfig
from repro.nn.module import NULL_CTX, tree_init
from repro.serve import (BlockAllocator, Engine, Request, ServeConfig,
                         TrafficModel, cache_geometry, gather_view,
                         max_abs_diff, pool_spec, price_serving,
                         scatter_blocks, serve_tune)

V, D = 64, 32


def mk_lm(n_layers=2):
    cfg = LMConfig(
        name="tiny", vocab=V, d_model=D, n_layers=n_layers,
        attn=AttentionConfig(D, 4, 2, 8, qk_norm=True, dtype=jnp.float32),
        ffn=FFNConfig(D, 64, dtype=jnp.float32), dtype=jnp.float32)
    return TransformerLM(cfg)


def solo_greedy(lm, params, key, prompt, max_new, max_len):
    """Dense-cache single-sequence greedy decode (the engine's reference)."""
    cache = jax.tree.map(jnp.zeros_like,
                         tree_init(lm.cache_spec(1, max_len,
                                                 dtype=jnp.float32), key))
    lg, cache = lm.prefill(params, jnp.asarray(prompt[None]), cache,
                           attn_impl="plain")
    toks = [int(np.argmax(np.asarray(lg[0, 0])))]
    for i in range(max_new - 1):
        lg, cache = lm.decode_step(params, jnp.asarray([[toks[-1]]]), cache,
                                   len(prompt) + i)
        toks.append(int(np.argmax(np.asarray(lg[0, 0]))))
    return toks


def test_paged_vs_dense_exact(key):
    """Chunked prefill through the paged pool is bit-exact vs the dense
    cache — logits AND cache contents, every chunk."""
    lm = mk_lm()
    params = tree_init(lm.params_spec(), key)
    S, max_len, C = 16, 32, 8
    toks = jax.random.randint(key, (1, S), 0, V)
    full, _ = lm.apply(params, toks, attn_impl="plain")
    geo = cache_geometry(lm, max_len, block_tokens=8, dtype=jnp.float32)
    pool = tree_init(pool_spec(lm, geo, 9, jnp.float32), key)
    tables = jnp.asarray(np.array([[1, 2, 3, 4]], np.int32))
    dense = jax.tree.map(jnp.zeros_like,
                         tree_init(lm.cache_spec(1, max_len,
                                                 dtype=jnp.float32), key))
    for k in range(S // C):
        p0 = jnp.asarray([k * C], jnp.int32)
        chunk = toks[:, k * C:(k + 1) * C]
        lgr, dense = lm.decode_step(params, chunk, dense, p0)
        view = gather_view(pool, tables)
        lgp, view = lm.decode_step(params, chunk, view, p0)
        jidx = ((p0 % geo.span) // geo.bspan)[:, None] \
            + jnp.arange(C // geo.bspan)[None]
        pool = scatter_blocks(pool, tables, view, jidx)
        assert float(jnp.max(jnp.abs(lgp - lgr))) == 0.0
        # decode vs the full forward: two different programs, which XLA may
        # fuse and reassociate differently — float32 rounding, not a bug
        assert float(jnp.max(jnp.abs(lgr - full[:, k * C:(k + 1) * C]))) \
            <= 1e-5
        assert max_abs_diff(pool, tables, dense, geo, (k + 1) * C) == 0.0


def test_block_allocator():
    a = BlockAllocator(5)                    # block 0 reserved
    assert a.capacity == 4
    got = a.alloc(3)
    assert got == [1, 2, 3]
    assert a.alloc(2) is None                # OOM: only 1 block left
    assert a.alloc(1) == [4]
    a.free([2, 3])
    assert sorted(a.alloc(2)) == [2, 3]      # freed blocks are reused
    with pytest.raises(ValueError):
        a.free([2, 2])                       # double free
    with pytest.raises(ValueError):
        a.free([0])                          # the null block is never freed
    with pytest.raises(ValueError):
        BlockAllocator(1)


def test_engine_admission_control(key):
    lm = mk_lm()
    params = tree_init(lm.params_spec(), key)
    cfg = ServeConfig(max_len=32, max_batch=3, block_tokens=8,
                      prefill_chunk=8, num_blocks=9, dtype=jnp.float32)
    eng = Engine(lm, params, NULL_CTX, cfg)
    with pytest.raises(ValueError):          # can never fit: 40+8 > 32 slots
        eng.submit(Request(0, np.ones(33, np.int32), 8))
    # r0 (2 blocks) + r1 (4 blocks) leave only 2 of the pool's 8 blocks
    # free; r2 needs 4, so despite a free decode slot its admission waits
    # until r0 finishes — FIFO back-off instead of deadlock
    r0 = Request(0, np.arange(1, 9, dtype=np.int32), 4)
    r1 = Request(1, np.arange(1, 25, dtype=np.int32), 8)
    r2 = Request(2, np.arange(1, 25, dtype=np.int32), 8)
    for r in (r0, r1, r2):
        eng.submit(r)
    rep = eng.run([], honor_arrivals=False)
    assert [r.rid for r in rep.requests] == [0, 1, 2]
    assert [len(r.tokens) for r in rep.requests] == [4, 8, 8]
    assert eng.alloc.free_blocks == eng.alloc.capacity  # all blocks freed


def test_continuous_batching_matches_solo(key):
    """Sequences joining/leaving the shared batch emit exactly the tokens
    they emit when decoded alone."""
    lm = mk_lm()
    params = tree_init(lm.params_spec(), key)
    max_len = 40
    cfg = ServeConfig(max_len=max_len, max_batch=3, block_tokens=8,
                      prefill_chunk=8, dtype=jnp.float32)
    eng = Engine(lm, params, NULL_CTX, cfg)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(1, V, size=L, dtype=np.int32),
                    max_new=6)
            for i, L in enumerate([5, 11, 3, 16])]   # multi-chunk prompts too
    rep = eng.run(reqs, honor_arrivals=False)
    assert len(rep.requests) == 4
    for s in rep.requests:
        ref = solo_greedy(lm, params, key, reqs[s.rid].prompt, 6, max_len)
        assert s.tokens == ref, (s.rid, s.tokens, ref)


def test_serve_project_monotone_in_rate():
    lm = mk_lm()
    from repro.core.hardware import cpu_host_model
    sysm = cpu_host_model()
    traffic = [TrafficModel(r, 64, 16) for r in (0.5, 1, 2, 4, 8, 16)]
    rows = [price_serving(lm.cfg, sysm, "serve_tp", 1, 1, 1, 4, t,
                          max_len=128, dtype_bytes=4) for t in traffic]
    assert all(r.rho <= s.rho for r, s in zip(rows, rows[1:]))
    feas = [r for r in rows if r.feasible]
    assert feas, "every rate overloaded the host model"
    for a, b in zip(feas, feas[1:]):
        assert b.latency_p99 >= a.latency_p99      # queueing only grows
        assert b.ttft_p99 >= a.ttft_p99
    # overload is reported, not hidden
    overloaded = price_serving(lm.cfg, sysm, "serve_tp", 1, 1, 1, 1,
                               TrafficModel(1e9, 64, 16), max_len=128,
                               dtype_bytes=4)
    assert not overloaded.feasible and overloaded.rho >= 1.0


def test_serve_tune_ranks_and_meets_slo():
    lm = mk_lm()
    from repro.core.hardware import cpu_host_model
    sysm = cpu_host_model()
    traffic = TrafficModel(2.0, 64, 16)
    plan = serve_tune(lm.cfg, sysm, 4, traffic, slo_p99=1e3,
                      max_len=128, dtype_bytes=4)
    assert plan.meets_slo and plan.winner.latency_p99 <= 1e3
    # the winner dominates every other row it was ranked against
    assert all(plan.winner.tok_per_s >= r.tok_per_s for r in plan.rows)
    # an impossible SLO still yields a deployable least-bad plan
    miss = serve_tune(lm.cfg, sysm, 4, traffic, slo_p99=1e-9,
                      max_len=128, dtype_bytes=4)
    assert not miss.meets_slo and miss.winner.feasible


def test_serve_tune_cli_smoke(capsys):
    from repro.api import main
    rc = main(["--serve-tune", "--arch", "qwen3-32b", "--p", "8",
               "--rate", "4", "--prompt", "256", "--gen", "64",
               "--slo-ms", "60000"])
    out = capsys.readouterr().out
    assert rc == 0 and "OK" in out and "serve_tp" in out
