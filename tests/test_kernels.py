"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (attention_ref, conv2d_gemm, conv2d_ref,
                           flash_attention, rmsnorm, rmsnorm_ref, ssd_chunk,
                           ssd_ref)


@pytest.mark.parametrize("S,D,bq,bk", [(128, 32, 32, 32), (256, 64, 64, 128),
                                       (64, 16, 64, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(key, S, D, bq, bk, dtype, causal):
    B, H = 2, 2
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, H, S, D)
                                 ).astype(dtype) for i in range(3))
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("S,H,P,N,chunk", [(64, 4, 8, 16, 16),
                                           (128, 2, 16, 8, 32)])
def test_ssd_chunk_sweep(key, S, H, P, N, chunk):
    B = 2
    x = jax.random.normal(key, (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (B, S, H)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (H,)) * 0.3)
    Bm = jax.random.normal(jax.random.fold_in(key, 3), (B, S, H, N)) * 0.5
    Cm = jax.random.normal(jax.random.fold_in(key, 4), (B, S, H, N)) * 0.5
    y, st = ssd_chunk(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, sr = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(y, yr, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(st, sr, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("HW,C,F,k", [((16, 12), 32, 64, 3), ((8, 8), 16, 16, 1),
                                      ((12, 16), 8, 128, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv2d_gemm_sweep(key, HW, C, F, k, dtype):
    H, W = HW
    x = jax.random.normal(key, (2, H, W, C)).astype(dtype)
    w = (jax.random.normal(jax.random.fold_in(key, 1), (k, k, C, F)) * 0.1
         ).astype(dtype)
    out = conv2d_gemm(x, w, interpret=True)
    ref = conv2d_ref(x, w)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), rtol=tol, atol=tol)


# ResNet-50's strided conv shapes: the 3×3 stride-2 bottleneck entries of
# stages 1–3 and the 1×1 stride-2 projection (ISSUE-4 acceptance: ≤ 1e-5)
RESNET50_STRIDE2 = [((56, 56), 64, 64, 3), ((28, 28), 128, 128, 3),
                    ((14, 14), 256, 256, 3), ((56, 56), 256, 512, 1)]


@pytest.mark.parametrize("HW,C,F,k", RESNET50_STRIDE2)
def test_conv2d_gemm_stride2_resnet50_shapes(key, HW, C, F, k):
    H, W = HW
    x = jax.random.normal(key, (2, H, W, C))
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, k, C, F)) * 0.1
    out = conv2d_gemm(x, w, strides=(2, 2), interpret=True)
    ref = conv2d_ref(x, w, strides=(2, 2))
    assert out.shape == ref.shape == (2, H // 2, W // 2, F)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("HW,C,F,k,s", [((15, 13), 8, 16, 5, 2),
                                        ((16, 12), 8, 16, 2, 2),
                                        ((32, 32), 16, 32, 3, 4)])
def test_conv2d_gemm_strided_odd_shapes(key, HW, C, F, k, s):
    """Non-dividing extents and even kernels keep the XLA SAME semantics."""
    H, W = HW
    x = jax.random.normal(key, (2, H, W, C))
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, k, C, F)) * 0.1
    out = conv2d_gemm(x, w, strides=(s, s), interpret=True)
    ref = conv2d_ref(x, w, strides=(s, s))
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_conv2d_gemm_halo_aware_consumes_padded_tile(key):
    """pad_h=False: the tile already carries its kh−1 boundary rows (the
    halo exchange delivered them) — VALID over H, SAME over W."""
    H, W, C, F, k = 12, 16, 8, 16, 3
    x = jax.random.normal(key, (2, H + k - 1, W, C))
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, k, C, F)) * 0.1
    out = conv2d_gemm(x, w, pad_h=False, interpret=True)
    assert out.shape == (2, H, W, F)
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NHWC", "HWIO", "NHWC"))
    ref = jax.lax.conv_general_dilated(
        x, w, (1, 1), ((0, 0), (k // 2, k // 2)), dimension_numbers=dn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_conv2d_gemm_halo_aware_rejects_strides(key):
    x = jax.random.normal(key, (1, 10, 8, 4))
    w = jax.random.normal(key, (3, 3, 4, 8))
    with pytest.raises(ValueError, match="stride-1 only"):
        conv2d_gemm(x, w, strides=(2, 2), pad_h=False, interpret=True)


@pytest.mark.parametrize("shape", [(4, 37, 128), (2, 256), (1, 8, 8, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(key, shape, dtype):
    x = jax.random.normal(key, shape).astype(dtype)
    sc = jax.random.normal(jax.random.fold_in(key, 1), (shape[-1],))
    out = rmsnorm(x, sc, interpret=True)
    ref = rmsnorm_ref(x, sc)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("S", [100, 37, 96])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_non_dividing_seq(key, S, causal):
    """Block sizes that do not divide S fall back to the largest divisor
    (S=37 is prime → single-block grid) instead of raising."""
    B, H, D = 2, 2, 32
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, H, S, D))
               for i in range(3))
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("R", [37, 149, 257])
def test_rmsnorm_prime_rows_pad_to_block(key, R):
    """Prime row counts (ragged last microbatch) pad up to the block and
    slice back instead of degrading to R single-row programs."""
    D = 128
    x = jax.random.normal(key, (R, D))
    sc = jax.random.normal(jax.random.fold_in(key, 1), (D,))
    out = rmsnorm(x, sc, block_rows=64, interpret=True)
    assert out.shape == (R, D)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(rmsnorm_ref(x, sc)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("strides,pad_h", [((1, 1), True), ((2, 2), True),
                                           ((1, 1), False)])
def test_conv2d_gemm_grad_matches_xla(key, strides, pad_h):
    """The kernel's custom VJP (the equivalent XLA conv's VJP) gives the
    gradients of the plain convolution, so a train step can run it."""
    x = jax.random.normal(key, (2, 12, 10, 8))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 8, 16)) * 0.1

    def ref(a, b):
        if pad_h:
            return conv2d_ref(a, b, strides=strides)
        dn = jax.lax.conv_dimension_numbers(a.shape, b.shape,
                                            ("NHWC", "HWIO", "NHWC"))
        return jax.lax.conv_general_dilated(a, b, (1, 1), ((0, 0), (1, 1)),
                                            dimension_numbers=dn)

    def loss(f):
        return lambda a, b: jnp.sum(f(a, b) ** 2)

    got = jax.grad(loss(lambda a, b: conv2d_gemm(
        a, b, strides=strides, pad_h=pad_h, interpret=True)), (0, 1))(x, w)
    want = jax.grad(loss(ref), (0, 1))(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)


# (Ho, Wp, Wo, C, kh, sh, F) of ResNet-50's kernel convs at 224²
RESNET50_KERNEL_CONVS = [(112, 229, 112, 3, 7, 2, 64), (56, 58, 56, 64, 3, 1, 64),
                         (28, 57, 28, 128, 3, 2, 128),
                         (14, 16, 14, 256, 3, 1, 256), (7, 9, 7, 512, 3, 1, 512)]


@pytest.mark.parametrize("Ho,Wp,Wo,C,kh,sh,F", RESNET50_KERNEL_CONVS)
def test_conv_row_block_fits_scoped_vmem(Ho, Wp, Wo, C, kh, sh, F):
    """Each program's working set stays inside the compiler's scoped VMEM
    limit; the 3-channel stem, whose rows pad to 128 lanes, is row-blocked
    instead of holding the whole 229² image."""
    from repro.kernels.util import (SCOPED_VMEM_BYTES, conv_row_block,
                                    conv_vmem_bytes)
    dims = dict(Wp=Wp, Wo=Wo, C=C, kh=kh, kw=kh, sh=sh, bf=min(F, 128), e=4)
    bh = conv_row_block(Ho, **dims)
    assert Ho % bh == 0
    assert conv_vmem_bytes(bh, **dims) <= SCOPED_VMEM_BYTES // 2
    if C == 3:
        assert bh < Ho


def test_resnet_pallas_sites(key):
    """With use_pallas, the stem and every bottleneck's 3×3 conv (strided
    or not) run on the kernel; the 1×1 convs stay on XLA."""
    from repro.models.cnn import ResNet, ResNetConfig
    from repro.nn.module import ShardingCtx, tree_abstract
    from repro.parallel.strategies import make_rules
    cfg = ResNetConfig("tiny", (2, 1, 1, 1), n_classes=10)
    model = ResNet(cfg)
    ctx = ShardingCtx(None, make_rules("data"), use_pallas=True)
    jaxpr = jax.make_jaxpr(lambda p, v: model.apply(p, v, ctx))(
        tree_abstract(model.params_spec()),
        jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32))
    assert str(jaxpr).count("pallas_call") == 1 + sum(cfg.stage_sizes)
