"""The Pallas kernels compile for a TPU v5e that is described, not attached.

Interpret mode accepts kernels the chip's compiler refuses (a dynamic_slice
of a loaded value, a block larger than the scoped VMEM limit), so each kernel
on the model path is compiled here at the ResNet-50 shapes it serves, and the
compiled text must hold the kernel as a ``tpu_custom_call``. Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import conv2d_gemm, flash_attention


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache without the chip: keep it out of any cache that is on
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# (x, w, strides, pad_h): every conv2d_gemm shape of a ResNet-50 step
# (batch cut to 2: each program sees one image)
RESNET50_CONVS = {
    "stem_7x7s2_224x3": ((2, 224, 224, 3), (7, 7, 3, 64), (2, 2), True),
    "3x3_56x64": ((2, 56, 56, 64), (3, 3, 64, 64), (1, 1), True),
    "3x3_14x256": ((2, 14, 14, 256), (3, 3, 256, 256), (1, 1), True),
    "3x3_7x512": ((2, 7, 7, 512), (3, 3, 512, 512), (1, 1), True),
    "3x3s2_56x128": ((2, 56, 56, 128), (3, 3, 128, 128), (2, 2), True),
    # halo-aware entry: one half of a two-way split of 56 rows, with the
    # two halo rows the exchange delivered
    "halo_3x3_28+2x56x64": ((2, 30, 56, 64), (3, 3, 64, 64), (1, 1), False),
}


@pytest.mark.parametrize("name", sorted(RESNET50_CONVS))
def test_conv2d_gemm_compiles_for_v5e(one_chip, name):
    x, w, strides, pad_h = RESNET50_CONVS[name]
    text = _compiled_text(
        lambda a, b: conv2d_gemm(a, b, strides=strides, pad_h=pad_h),
        one_chip, x, w)
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_for_v5e(one_chip):
    shape = (1, 2, 2048, 128)
    text = _compiled_text(lambda q, k, v: flash_attention(q, k, v),
                          one_chip, shape, shape, shape)
    assert "tpu_custom_call" in text
