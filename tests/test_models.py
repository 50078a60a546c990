"""Model-level parity: scan==unrolled; prefill+decode == full forward; a
narrow ResNet trains, and its step carries the profiler's scopes."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.nn import AttentionConfig, FFNConfig, MoEConfig, RGLRUConfig
from repro.nn.module import tree_init
from repro.models import (EncDecConfig, EncDecLM, LMConfig, TransformerLM,
                          VLM, VLMConfig)

B, S, V, D = 2, 32, 64, 32


def mk_dense(n_layers=4, **kw):
    return LMConfig(
        name="tiny", vocab=V, d_model=D, n_layers=n_layers,
        attn=AttentionConfig(D, 4, 2, 8, qk_norm=True, dtype=jnp.float32),
        ffn=FFNConfig(D, 64, dtype=jnp.float32), dtype=jnp.float32, **kw)


def test_dense_scan_equals_unrolled(key):
    lm = TransformerLM(mk_dense())
    p = tree_init(lm.params_spec(), key)
    toks = jax.random.randint(key, (B, S), 0, V)
    a, _ = lm.apply(p, toks, scan_layers=True, attn_impl="plain")
    b, _ = lm.apply(p, toks, scan_layers=False, attn_impl="plain")
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_dense_prefill_decode(key):
    lm = TransformerLM(mk_dense())
    p = tree_init(lm.params_spec(), key)
    toks = jax.random.randint(key, (B, S), 0, V)
    full, _ = lm.apply(p, toks, attn_impl="plain")
    cache = jax.tree.map(jnp.zeros_like,
                         tree_init(lm.cache_spec(B, S, dtype=jnp.float32), key))
    lg, cache = lm.prefill(p, toks[:, :S // 2], cache, attn_impl="plain")
    np.testing.assert_allclose(lg[:, 0], full[:, S // 2 - 1], rtol=2e-3,
                               atol=2e-3)
    lg, cache = lm.decode_step(p, toks[:, S // 2:S // 2 + 1], cache, S // 2)
    np.testing.assert_allclose(lg[:, 0], full[:, S // 2], rtol=2e-3, atol=2e-3)


def test_moe_lm_with_lead_and_mtp(key):
    cfg = LMConfig(
        name="tinymoe", vocab=V, d_model=D, n_layers=4, pattern=("moe",),
        attn=AttentionConfig(D, 4, 2, 8, dtype=jnp.float32),
        ffn=FFNConfig(D, 64, dtype=jnp.float32),
        moe=MoEConfig(D, 32, n_experts=4, top_k=2, n_shared=1,
                      capacity_factor=2.0, dtype=jnp.float32),
        first_k_dense=1, mtp_heads=1, dtype=jnp.float32)
    lm = TransformerLM(cfg)
    p = tree_init(lm.params_spec(), key)
    toks = jax.random.randint(key, (B, S), 0, V)
    loss, m = lm.loss_fn(p, {"tokens": toks}, attn_impl="plain")
    assert np.isfinite(loss) and "mtp_ce" in m
    full, _ = lm.apply(p, toks, attn_impl="plain")
    cache = jax.tree.map(jnp.zeros_like,
                         tree_init(lm.cache_spec(B, S, dtype=jnp.float32), key))
    _, cache = lm.prefill(p, toks[:, :16], cache, attn_impl="plain")
    lg, _ = lm.decode_step(p, toks[:, 16:17], cache, 16)
    np.testing.assert_allclose(lg[:, 0], full[:, 16], rtol=3e-3, atol=3e-3)


def test_hybrid_pattern_with_remainder(key):
    cfg = LMConfig(
        name="tinyhy", vocab=V, d_model=D, n_layers=8,
        pattern=("rec", "rec", "local_attn"),
        local_attn=AttentionConfig(D, 4, 1, 8, window=8, dtype=jnp.float32),
        rglru=RGLRUConfig(D, 64, n_blocks=4),
        ffn=FFNConfig(D, 64, activation="gelu", dtype=jnp.float32),
        dtype=jnp.float32)
    lm = TransformerLM(cfg)
    p = tree_init(lm.params_spec(), key)
    toks = jax.random.randint(key, (B, S), 0, V)
    full, _ = lm.apply(p, toks, attn_impl="plain")
    cache = jax.tree.map(jnp.zeros_like,
                         tree_init(lm.cache_spec(B, S, dtype=jnp.float32), key))
    _, cache = lm.prefill(p, toks[:, :16], cache, attn_impl="plain")
    lg, _ = lm.decode_step(p, toks[:, 16:17], cache, 16)
    np.testing.assert_allclose(lg[:, 0], full[:, 16], rtol=5e-3, atol=5e-3)


def test_encdec_parity(key):
    cfg = EncDecConfig("tinyed", vocab=V, d_model=D, n_enc_layers=2,
                       n_dec_layers=2, n_heads=4, d_ff=64,
                       max_source_positions=16, max_target_positions=S,
                       dtype=jnp.float32)
    ed = EncDecLM(cfg)
    p = tree_init(ed.params_spec(), key)
    frames = jax.random.normal(key, (B, 16, D))
    toks = jax.random.randint(key, (B, S), 0, V)
    enc = ed.encode(p, frames, attn_impl="plain")
    full = ed.decode_train(p, toks, enc, attn_impl="plain")
    cache = jax.tree.map(jnp.zeros_like,
                         tree_init(ed.cache_spec(B, S, dtype=jnp.float32), key))
    _, cache = ed.prefill(p, frames, cache)
    outs = []
    for t in range(4):
        lg, cache = ed.decode_step(p, toks[:, t:t + 1], cache, t)
        outs.append(lg)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), full[:, :4],
                               rtol=3e-3, atol=3e-3)


def test_vlm_loss_and_masking(key):
    cfg = VLMConfig(lm=mk_dense(n_layers=2, tie_embeddings=True,
                                embed_scale=True), d_vision=24, n_patches=8)
    vlm = VLM(cfg)
    p = tree_init(vlm.params_spec(), key)
    toks = jax.random.randint(key, (B, S), 0, V)
    patches = jax.random.normal(key, (B, 8, 24))
    loss, _ = vlm.loss_fn(p, {"patches": patches, "tokens": toks},
                          attn_impl="plain")
    assert np.isfinite(loss)


def test_logit_softcap_bounds(key):
    cfg = mk_dense(n_layers=1)
    import dataclasses
    cfg = dataclasses.replace(cfg, final_logit_softcap=5.0)
    lm = TransformerLM(cfg)
    p = tree_init(lm.params_spec(), key)
    toks = jax.random.randint(key, (B, S), 0, V)
    logits, _ = lm.apply(p, toks, attn_impl="plain")
    assert np.all(np.abs(np.asarray(logits)) <= 5.0 + 1e-4)


@pytest.fixture(scope="module")
def narrow_resnet_step():
    """A ResNet of width 8, one bottleneck a stage, on 32x32 images: its
    AdamW train step, a state and a batch."""
    from repro.models.cnn import ResNet, ResNetConfig
    from repro.nn.module import NULL_CTX
    from repro.optim.optimizers import OptimizerConfig
    from repro.training.steps import make_train_step, train_state_spec
    model = ResNet(ResNetConfig("narrow", (1, 1, 1, 1), n_classes=10, width=8))
    opt = OptimizerConfig(lr=1e-3)
    state = tree_init(train_state_spec(model, opt), jax.random.PRNGKey(0))
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    batch = {"images": jax.random.normal(k1, (4, 32, 32, 3)),
             "labels": jax.random.randint(k2, (4,), 0, 10)}
    return jax.jit(make_train_step(model, opt, NULL_CTX)), state, batch


def test_narrow_resnet_trains_one_step(narrow_resnet_step):
    """The head takes the last stage's width (8 * 2**3 * 4), not 2048."""
    step, state, batch = narrow_resnet_step
    assert state["params"]["head"]["w"].shape == (256, 10)
    new, m = step(state, batch)
    assert np.isfinite(float(m["loss"])) and int(new["step"]) == 1
    assert not np.allclose(new["params"]["head"]["w"],
                           state["params"]["head"]["w"])


def test_train_step_carries_the_profiler_scopes(narrow_resnet_step):
    """The compiled step names its parts in each instruction's op_name, for
    a profiler trace to group device time by; fusions keep them."""
    step, state, batch = narrow_resnet_step
    text = step.lower(state, batch).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for part in ["/optimizer/", "/batchnorm/", "stem", "stage3", "block0",
                 "head", "loss"]:
        assert any(part in n for n in names), part
    assert any(n.startswith("jit(train_step)/transpose(jvp(stage")
               and "/batchnorm/" in n for n in names)
    fusions = re.findall(r'%\S*fusion\S* = .*op_name="([^"]*)"', text)
    assert fusions and any("/batchnorm/" in n for n in fusions)
