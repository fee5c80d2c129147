"""The 1x1 BFP convs of a small bfp PixelLink on the fused kernel (one
launch per word: in-kernel activation quantization, load-time weight
matrices, bias and ReLU in the flush), against the round-trip path;
raw weights through the in-call fallback; the kernel-word counter.
Pallas runs interpreted on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.interpreter import MATRIX, BFPConfig, word_kind
from repro.models.fcn.heads import DetectionModel, build_head
from repro.models.fcn.pixellink import STDConfig


def small_model(use_pallas):
    cfg = STDConfig(name="small", backbone="resnet50", width=0.125,
                    image_size=(32, 32), merge_ch=(16, 16, 8),
                    upsample_mode="fused", bfp=BFPConfig(),
                    storage_fp16=True, use_pallas=use_pallas)
    return DetectionModel(cfg, build_head("pixellink"))


def n_1x1(model):
    prog = model.program
    return sum(word_kind(prog.words[i], prog.layer_specs[i]) == "conv1x1"
               for i in prog.weight_bindings)


def unmarked(params):
    """The normalized weights with each 1x1 matrix back under ``w``
    as an HWIO kernel: BFP values the engine cannot tell from raw."""
    out = {}
    for name, p in params.items():
        p = dict(p)
        if MATRIX in p:
            p["w"] = p.pop(MATRIX)[None, None]
        out[name] = p
    return out


@pytest.fixture(scope="module")
def setup():
    fused, plain = small_model(True), small_model(False)
    raw = fused.init_params(jax.random.PRNGKey(0))
    x = jax.random.uniform(jax.random.PRNGKey(1), (1, 32, 32, 3))
    normed = jax.jit(fused.normalize_weights)(raw)
    run = lambda m, p: jax.jit(m.apply)(p, x)
    return dict(fused=fused, plain=plain, raw=raw, normed=normed, run=run,
                out=run(fused, normed))


def test_normalize_weights_marks_every_1x1_word(setup):
    normed, model = setup["normed"], setup["fused"]
    marked = [n for n, p in normed.items() if MATRIX in p]
    assert len(marked) == n_1x1(model) > 0
    for name in marked:
        assert "w" not in normed[name] and normed[name][MATRIX].ndim == 2


def test_fused_path_matches_roundtrip_path(setup):
    want = setup["run"](setup["plain"], setup["normed"])
    for key in ("score", "links"):
        np.testing.assert_allclose(
            np.asarray(setup["out"][key], np.float32),
            np.asarray(want[key], np.float32), atol=1e-2)


def test_raw_weights_take_the_fallback_with_the_same_answer(setup):
    """The normalized weights handed over raw (each 1x1 kernel under
    ``w``, unmarked) are normalized again in the call, which trunc
    rounding leaves unchanged, and run the same kernel: the same maps
    bit for bit."""
    got = setup["run"](setup["fused"], unmarked(setup["normed"]))
    for key in ("score", "links"):
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(setup["out"][key]))


def test_counter_reads_fused_or_fallback_words(setup):
    fused, n = setup["fused"].engine, n_1x1(setup["fused"])
    assert fused.kernel_words(setup["normed"]) == {
        "bfp1x1_fused_words": n, "bfp1x1_fallback_words": 0}
    for params in (setup["raw"], unmarked(setup["normed"])):
        assert fused.kernel_words(params) == {
            "bfp1x1_fused_words": 0, "bfp1x1_fallback_words": n}
    # without the Pallas kernels no word runs on the BFP matmul kernel
    assert setup["plain"].engine.kernel_words(setup["normed"]) == {
        "bfp1x1_fused_words": 0, "bfp1x1_fallback_words": 0}


def test_service_books_the_counter_at_engine_build():
    from repro.launch.serve import STDService

    cfg = STDConfig(name="small", backbone="resnet50", width=0.125,
                    image_size=(32, 32), merge_ch=(16, 16, 8),
                    upsample_mode="fused", bfp=BFPConfig(),
                    storage_fp16=True)
    svc = STDService(config=cfg, buckets=(32,), max_batch=1,
                     precision="bfp")
    model = svc.factory.model((32, 32), "bfp")
    model.engine.use_pallas = True
    svc.infer_labels(np.zeros((1, 32, 32, 3), np.float32), [(32, 32)])
    snap = svc.metrics_snapshot()
    assert snap["std_bfp1x1_fused_words"] == n_1x1(model)
    assert snap["std_bfp1x1_fallback_words"] == 0
