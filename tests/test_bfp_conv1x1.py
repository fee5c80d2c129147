"""The convs of small bfp PixelLinks (ResNet-50 and VGG-16 backbones)
on the Pallas path: the 1x1s on the fused kernel (one launch per word:
in-kernel activation quantization, load-time weight matrices, bias and
ReLU in the flush), the others with their activation round trip in one
pass and their load-time kernels as they are; against the jnp round-trip
path; raw weights refused; the kernel-word counters.  Pallas runs
interpreted on the CPU."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.interpreter import (KERNEL, MATRIX, ROWS_MIN_CIN,
                                    BFPConfig, word_kind)
from repro.models.fcn.heads import DetectionModel, build_head
from repro.models.fcn.pixellink import STDConfig


BACKBONES = ["resnet50", "vgg16"]


def small_config(backbone, **kw):
    return STDConfig(name="small", backbone=backbone, width=0.125,
                     image_size=(32, 32), merge_ch=(16, 16, 8),
                     upsample_mode="fused", bfp=BFPConfig(),
                     storage_fp16=True, **kw)


def small_model(use_pallas, backbone="resnet50"):
    return DetectionModel(small_config(backbone, use_pallas=use_pallas),
                          build_head("pixellink"))


def conv_words(model):
    prog = model.program
    return [(prog.words[i], word_kind(prog.words[i], prog.layer_specs[i]))
            for i in prog.weight_bindings
            if prog.layer_specs[i].op == "conv"]


def n_1x1(model):
    return sum(kind == "conv1x1" for _, kind in conv_words(model))


def n_other(model):
    """The conv words off the 1x1 kernel (3x3, strided)."""
    return sum(kind != "conv1x1" for _, kind in conv_words(model))


def unmarked(params):
    """The normalized weights with each load-time form back under
    ``w`` as an HWIO kernel: BFP values the engine cannot tell from
    raw."""
    out = {}
    for name, p in params.items():
        p = dict(p)
        if MATRIX in p:
            p["w"] = p.pop(MATRIX)[None, None]
        if KERNEL in p:
            p["w"] = p.pop(KERNEL)
        out[name] = p
    return out


@pytest.fixture(scope="module", params=BACKBONES)
def setup(request):
    fused = small_model(True, request.param)
    plain = small_model(False, request.param)
    raw = fused.init_params(jax.random.PRNGKey(0))
    x = jax.random.uniform(jax.random.PRNGKey(1), (1, 32, 32, 3))
    normed = jax.jit(fused.normalize_weights)(raw)
    jitted = {id(m): jax.jit(m.apply) for m in (fused, plain)}
    run = lambda m, p: jitted[id(m)](p, x)
    return dict(fused=fused, plain=plain, raw=raw, normed=normed, run=run,
                x=x, out=run(fused, normed), fused_fn=jitted[id(fused)])


def test_normalize_weights_marks_every_1x1_word(setup):
    normed, model = setup["normed"], setup["fused"]
    marked = [n for n, p in normed.items() if MATRIX in p]
    assert len(marked) == n_1x1(model) > 0
    for name in marked:
        assert "w" not in normed[name] and normed[name][MATRIX].ndim == 2


def test_normalize_weights_marks_every_other_conv_word(setup):
    """3x3 and strided convs keep their HWIO kernel, BFP-normalized,
    under a key of its own."""
    normed, model = setup["normed"], setup["fused"]
    marked = [n for n, p in normed.items() if KERNEL in p]
    assert len(marked) == n_other(model) > 0
    for name in marked:
        assert "w" not in normed[name] and normed[name][KERNEL].ndim == 4


def test_fused_path_matches_roundtrip_path(setup):
    want = setup["run"](setup["plain"], setup["normed"])
    for key in ("score", "links"):
        np.testing.assert_allclose(
            np.asarray(setup["out"][key], np.float32),
            np.asarray(want[key], np.float32), atol=1e-2)


def test_raw_weights_are_refused(setup):
    """Raw weights, or the normalized ones handed over unmarked (each
    conv kernel under ``w``), are refused when the engine is traced:
    nothing quantizes a conv's weights in the call."""
    for params in (setup["raw"], unmarked(setup["normed"])):
        with pytest.raises(ValueError, match="normalize_weights"):
            jax.eval_shape(setup["fused"].apply, params, setup["x"])


def n_rows(model):
    """The conv words off the 1x1 kernel whose activation round trip the
    one-pass kernel takes: those of ``ROWS_MIN_CIN`` input channels or
    more (at width 1/8 some are narrower, and stay with XLA)."""
    rows = sum(kind != "conv1x1" and mc.in_ch >= ROWS_MIN_CIN
               for mc, kind in conv_words(model))
    assert 0 < rows < n_other(model)
    return rows


def test_counter_reads_fused_or_fallback_words(setup):
    model = setup["fused"]
    assert model.engine.kernel_words() == {
        "bfp1x1_fused_words": n_1x1(model), "bfp_rows_words": n_rows(model)}
    # without the Pallas kernels every conv takes the jnp round trip
    assert setup["plain"].engine.kernel_words() == {
        "bfp1x1_fused_words": 0, "bfp_rows_words": 0}


def test_rows_kernel_runs_under_the_roundtrip_scope(setup):
    """Each word the counter books calls the one-pass kernel once,
    inside the word's ``bfp_roundtrip`` scope (what the benchmark's
    quantize_share reads)."""
    model = setup["fused"]
    text = setup["fused_fn"].lower(setup["normed"], setup["x"]).as_text(
        debug_info=True)
    calls = set(re.findall(r'loc\("([^"]*)/jit\(bfp_roundtrip_rows\)"',
                           text))
    assert calls and all(re.fullmatch(
        r"jit\(apply\)/w\d{3}\.(conv3x3|conv_strided)/bfp_roundtrip", c)
        for c in calls), calls
    assert len(calls) == n_rows(model)


@pytest.mark.parametrize("backbone", BACKBONES)
def test_service_books_the_counter_at_engine_build(backbone):
    from repro.launch.serve import STDService

    svc = STDService(config=small_config(backbone), buckets=(32,),
                     max_batch=1, precision="bfp")
    model = svc.factory.model((32, 32), "bfp")
    model.engine.use_pallas = True
    svc.infer_labels(np.zeros((1, 32, 32, 3), np.float32), [(32, 32)])
    snap = svc.metrics_snapshot()
    assert snap["std_bfp1x1_fused_words"] == n_1x1(model)
    assert snap["std_bfp_rows_words"] == n_rows(model)
    assert not any("fallback" in k or "incall" in k for k in snap)
