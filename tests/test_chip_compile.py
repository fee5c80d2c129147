"""Compile the served path for a described TPU v5e, with no chip attached.

The TPU compiler is installed here and compiles for a topology that is
described rather than attached, so what the chip's compiler would refuse
(block shapes off the (8, 128) tiling, unsupported in-kernel reshapes,
fast memory over the scoped limit, kernels that cannot be partitioned)
fails here at no chip time.  Each Pallas kernel of the served path
compiles with ``interpret=False`` at ResNet-50 and VGG-16 512x512
shapes, and one whole f32 engine step compiles with the Pallas CC tail.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around these compiles (a
TPU entry written here could not be read back without a chip).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# ResNet-50 / VGG-16 3x3 stride-1 convs at 512x512, batch 1 and 8:
# (batch, H, W, Cin, Cout); the last four are VGG-16's batch-8 convs at
# the full plane and at 256 (conv1_1 with its 3-channel input, conv1_2,
# conv2_1, conv2_2)
WINOGRAD = [(1, 128, 128, 64, 64), (8, 64, 64, 128, 128),
            (8, 32, 32, 256, 256), (8, 16, 16, 512, 512),
            (1, 512, 512, 64, 64),
            (8, 512, 512, 3, 64), (8, 512, 512, 64, 64),
            (8, 256, 256, 64, 128), (8, 256, 256, 128, 128)]
# 1x1 convs as matmuls through the public bfp_matmul, (M = batch*H*W,
# K = Cin, N = Cout); K=288 is a U-merge concat of no 128-lane multiple
BFP = [(4096, 1024, 256), (131072, 64, 256), (8192, 2048, 512),
       (131072, 288, 32), (131072, 32, 9)]
# the fused 1x1 kernel as the engine calls it: f16 activation (M, K),
# load-time f32 weights (K, N), bias and ReLU in the flush
FUSED_1X1 = [(131072, 64, 256), (32768, 1152, 128), (8192, 2048, 9),
             (131072, 288, 32), (2048, 512, 2048)]
# the one-pass activation round trip before the convs off the matmul
# kernel, (batch, H, W, Cin) as the engine reshapes them to (M, Cin) rows:
# VGG-16's batch-8 3x3 inputs at 512, 256, 128 and 32, the merge's
# 32-channel 3x3 input, and ResNet-50's strided inputs (3x3 stride 2 at
# 128 and 64, 1x1 stride-2 projections at 128, 64 and 32)
ROWS = [(8, 512, 512, 64), (8, 256, 256, 128), (8, 128, 128, 256),
        (8, 32, 32, 512), (8, 128, 128, 32), (8, 128, 128, 128),
        (8, 64, 64, 256), (8, 64, 64, 512), (8, 32, 32, 1024)]
# (batch, h, w) label planes: 128 wide (512 bucket), 64 (256 bucket),
# and a 160-wide plane that pads to two 128-lane tiles
CC = [(8, 128, 128), (8, 64, 64), (2, 128, 160)]


@pytest.mark.parametrize("shape", WINOGRAD, ids=str)
def test_winograd_conv_compiles(one_chip, no_cache, shape):
    from repro.kernels.winograd_conv import winograd_conv2d

    n, h, w, cin, cout = shape
    text = _compile_text(
        one_chip,
        lambda x, k, b: winograd_conv2d(x, k, b, relu=True,
                                        interpret=False),
        ((n, h, w, cin), jnp.float32), ((3, 3, cin, cout), jnp.float32),
        ((cout,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", BFP, ids=str)
def test_bfp_matmul_compiles(one_chip, no_cache, shape):
    from repro.kernels.bfp_matmul import bfp_matmul

    m, k, n = shape
    text = _compile_text(
        one_chip,
        lambda a, b: bfp_matmul(a, b, block_size=32, mantissa_bits=10,
                                interpret=False),
        ((m, k), jnp.float32), ((k, n), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", FUSED_1X1, ids=str)
def test_fused_bfp_conv1x1_compiles(one_chip, no_cache, shape):
    from repro.kernels.bfp_matmul.kernel import bfp_matmul_quantized

    m, k, n = shape
    text = _compile_text(
        one_chip,
        lambda a, w, b: bfp_matmul_quantized(
            a, w, b, block_size=32, mantissa_bits=10, relu=True,
            interpret=False),
        ((m, k), jnp.float16), ((k, n), jnp.float32), ((n,), jnp.float32))
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("shape", ROWS, ids=str)
def test_bfp_roundtrip_rows_compiles(one_chip, no_cache, shape):
    from repro.kernels.bfp_matmul.kernel import bfp_roundtrip_rows

    text = _compile_text(
        one_chip,
        lambda x: bfp_roundtrip_rows(
            x.reshape(-1, x.shape[-1]), block_size=32, mantissa_bits=10,
            interpret=False).reshape(x.shape),
        (shape, jnp.float16))
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("shape", CC, ids=str)
def test_cc_label_compiles(one_chip, no_cache, shape):
    from repro.kernels.cc_label import cc_label_pallas

    text = _compile_text(
        one_chip,
        lambda s, l: cc_label_pallas(s, l, 0.5, 0.5, interpret=False),
        (shape, jnp.float32), (shape + (8,), jnp.float32))
    assert "tpu_custom_call" in text


def test_resnet50_engine_step_compiles(one_chip, no_cache, monkeypatch):
    """The served f32 ResNet-50 512x512 engine at batch 8, CC tail on the
    Pallas kernel.  Off the chip the code sees the CPU and would lower
    interpret-mode HLO, so the test steers both choices itself."""
    import repro.kernels
    from repro.configs.pixellink_std import RESNET50
    from repro.launch.serve import STDService
    from repro.runtime.executor import SingleDevice

    monkeypatch.setattr(repro.kernels, "default_interpret", lambda: False)
    hw, batch = (512, 512), 8
    svc = STDService(config=RESNET50, buckets=(512,), max_batch=batch)
    svc.factory.cc_pallas = True
    model = svc.factory.model(hw, "f32")
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    fn = svc.factory.plan_fn(hw, batch, SingleDevice(), "f32")
    compiled = fn.lower(
        params,
        jax.ShapeDtypeStruct((batch,) + hw + (3,), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((batch, 2), jnp.int32, sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
