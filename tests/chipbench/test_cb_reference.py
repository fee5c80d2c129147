"""The plain reference against the served model, on the CPU at width
0.125 and a 64 plane, with seeded weights: in float32 the served
model's maps (its own batch-norm fold, no BFP) agree with the
reference's; a deliberately wrong weight mapping does not."""
import dataclasses

import jax
import numpy as np
import pytest

import _cb_paths  # noqa: F401
from chipbench import loadgen
from chipbench.references import pixellink_fcn as ref

MIX = {"size": {"min": 40, "max": 64, "step": 8}, "pool": 2,
       "max_instances": 2}


def served_maps(backbone, params, x):
    from repro.configs.pixellink_std import RESNET50
    from repro.launch.serve import STDService

    cfg = dataclasses.replace(RESNET50, backbone=backbone, width=0.125,
                              image_size=(64, 64), merge_ch=(16, 16, 8))
    svc = STDService(config=cfg, buckets=(64,), max_batch=2)
    model = svc.factory.model((64, 64), "f32")
    out = jax.jit(model.apply)(model.normalize_weights(params), x)
    return np.asarray(out["score"]), np.asarray(out["links"])


def flipped(params):
    """The classic mapping fault: kernels taken as correlation
    windows the other way round."""
    return {n: dict(p, w=p["w"][::-1, ::-1]) for n, p in params.items()}


@pytest.mark.parametrize("backbone", ["resnet50", "vgg16"])
def test_reference_agrees_with_served_maps_in_f32(backbone):
    arch = {"backbone": backbone, "width": 0.125, "merge_ch": [16, 16, 8]}
    raw = jax.jit(lambda k: ref.init_params(k, arch))(
        jax.random.PRNGKey(3))
    x = np.zeros((2, 64, 64, 3), np.float32)
    for i, img in enumerate(loadgen.image_pool(MIX, 3)):
        x[i, :img.shape[0], :img.shape[1]] = img
    s_ref, l_ref = map(np.asarray, jax.jit(
        lambda p, x: ref.forward(p, x, arch))(raw, x))
    s, l = served_maps(backbone, ref.to_program(raw), x)
    good = max(np.abs(s - s_ref).max(), np.abs(l - l_ref).max())
    assert good < 1e-4
    s, l = served_maps(backbone, flipped(ref.to_program(raw)), x)
    bad = max(np.abs(s - s_ref).max(), np.abs(l - l_ref).max())
    assert bad > 100 * max(good, 1e-6) and bad > 1e-2


@pytest.mark.parametrize("seed", [3, 4])
def test_calibrated_head_marks_the_configured_shares(seed):
    """On the calibration crop, a tenth of the pixels scores as text
    and half of each link direction is positive, whatever the seed's
    network would have marked."""
    arch = {"backbone": "resnet50", "width": 0.125, "merge_ch": [16, 16, 8]}
    x = np.zeros((1, 64, 64, 3), np.float32)
    x[0] = loadgen.synthetic_image(64, 64, np.random.default_rng(seed), 2)
    raw = jax.jit(lambda k, x: ref.calibrate(ref.init_params(k, arch), x,
                                             arch, 0.1, 0.5))(
        jax.random.PRNGKey(seed), x)
    score, links = map(np.asarray, jax.jit(
        lambda p, x: ref.forward(p, x, arch))(raw, x))
    assert abs((score > 0.5).mean() - 0.1) < 0.01
    shares = (links > 0.5).reshape(-1, ref.N_LINKS).mean(axis=0)
    assert np.all(np.abs(shares - 0.5) < 0.01)
