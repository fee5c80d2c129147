"""The benchmark's counts and peaks: the dense count of each backbone
at 224x224 against its published figure, the credited count against
the crediting rule, the kernel launches per step, the peak table."""
import pytest

import _cb_paths  # noqa: F401
from chipbench import yardstick
from chipbench.references import pixellink_fcn as ref

ARCH = {"width": 1.0, "merge_ch": [128, 64, 32]}
HEAD = ("merge", "fuse_out", "head_logits")


def backbone(name, hw):
    return [cv for cv in ref.layer_list(dict(ARCH, backbone=name), hw)
            if not cv.name.startswith(HEAD)]


@pytest.mark.parametrize("name, published_gmac", [
    ("resnet50", 4.09),      # arXiv:1512.03385 Table 1 (v1.5 stride)
    ("vgg16", 15.35),        # the 13 convs of arXiv:1409.1556 config D
])
def test_dense_count_matches_published(name, published_gmac):
    got = yardstick.model_macs(backbone(name, (224, 224)), credited=False)
    assert abs(got / 1e9 - published_gmac) / published_gmac < 0.01


@pytest.mark.parametrize("name", ["resnet50", "vgg16"])
def test_credited_is_dense_with_3x3_stride1_quartered(name):
    convs = ref.layer_list(dict(ARCH, backbone=name), (512, 512))
    want = sum(yardstick.dense_macs(cv) // 4
               if cv.kind == "upsample" or (cv.k, cv.stride) == (3, 1)
               else yardstick.dense_macs(cv) for cv in convs)
    assert yardstick.model_macs(convs) == want
    assert yardstick.model_macs(convs) < yardstick.model_macs(
        convs, credited=False)


@pytest.mark.parametrize("name, calls", [
    ("resnet50", {"bfp_matmul_quantized": 40, "winograd_tile_matmul": 17}),
    ("vgg16", {"bfp_matmul_quantized": 7, "winograd_tile_matmul": 17}),
])
def test_kernel_calls_per_step(name, calls):
    assert yardstick.kernel_calls(
        ref.layer_list(dict(ARCH, backbone=name), (512, 512))) == calls


def test_unknown_device_kind_raises():
    assert yardstick.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        yardstick.peaks("cpu")


def test_roofline_is_the_larger_bound():
    peak = yardstick.peaks("TPU v5 lite")
    assert yardstick.least_seconds(197e12, 0, peak) == 1.0
    assert yardstick.least_seconds(0, 819e9, peak) == 1.0
    assert yardstick.least_seconds(197e12, 2 * 819e9, peak) == 2.0
