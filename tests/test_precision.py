"""Precision-mode serving tests: the (bucket, batch, plan, precision)
engine identity, the bfp-vs-f32 accuracy-parity gate, and the engine
state/bootstrap bugfix regressions that rode along (concurrent
transposed tracing, BFP engines refusing unquantized weights,
backend-derived Pallas interpret default)."""
import inspect
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Assembler, BFPConfig, FCNEngine, LayerSpec
from repro.core.interpreter import KERNEL, MATRIX


def tiny_program(hw=(16, 16), *, bn=False):
    specs = [
        LayerSpec("c1", "conv", ["input"], out_ch=8, kernel=3, relu=True,
                  bn=bn),
        LayerSpec("c2", "conv", ["c1"], out_ch=8, kernel=3, relu=True),
        LayerSpec("cc", "conv", ["c2"], out_ch=4, kernel=1),
        LayerSpec("sg", "sigmoid", ["cc"]),
    ]
    return Assembler((hw[0], hw[1], 3)).assemble(specs, outputs=["sg"])


def _std_model(hw, precision="f32"):
    from repro.models.fcn.pixellink import PixelLinkModel, STDConfig

    return PixelLinkModel(STDConfig(
        backbone="vgg16", width=0.125, image_size=hw,
        merge_ch=(16, 16, 8),
        bfp=BFPConfig() if precision == "bfp" else None,
        storage_fp16=(precision == "bfp"),
    ))


class TestEngineLRUPrecision:
    """Tentpole: precision is part of the engine identity — a precision
    change is a new compiled engine and a new param entry, never a
    cache hit on the other numerics."""

    def test_distinct_engines_and_params_per_precision(self):
        from repro.runtime.executor import EngineFactory, SingleDevice

        fac = EngineFactory(_std_model)
        hw = (64, 64)
        f_f32 = fac.plan_fn(hw, 1, SingleDevice(), "f32")
        f_bfp = fac.plan_fn(hw, 1, SingleDevice(), "bfp")
        assert f_f32 is not f_bfp
        assert len(fac) == 2
        # cache hits return the identical callable per precision
        assert fac.plan_fn(hw, 1, SingleDevice(), "f32") is f_f32
        assert fac.plan_fn(hw, 1, SingleDevice(), "bfp") is f_bfp
        # compiled stats record the precision axis
        precs = {e["precision"] for e in fac.stats["compiled"]}
        assert precs == {"f32", "bfp"}
        # bfp params are the f32 set through normalize_weights: same
        # factory, different trees (BN folded / weights quantized)
        pf = fac.params(hw, "f32")
        pb = fac.params(hw, "bfp")
        assert pf is not pb

    def test_both_precisions_serve_same_weight_set(self):
        """f32 and bfp engines produce close (not identical) maps from
        the shared PRNGKey(0) weight set — close proves one weight set,
        a nonzero delta proves the bfp engine actually quantized."""
        from repro.runtime.executor import EngineFactory

        fac = EngineFactory(_std_model)
        hw = (64, 64)
        x = jax.random.uniform(jax.random.PRNGKey(3), (1, 64, 64, 3))
        of = fac.model(hw, "f32").apply(fac.params(hw, "f32"), x)
        ob = fac.model(hw, "bfp").apply(fac.params(hw, "bfp"), x)
        d = float(jnp.max(jnp.abs(of["score"] - ob["score"])))
        assert 0.0 < d < 0.05

    def test_unknown_precision_rejected(self):
        from repro.runtime.executor import (EngineFactory, SingleDevice,
                                            check_precision)

        with pytest.raises(ValueError, match="unknown precision"):
            check_precision("fp8")
        fac = EngineFactory(_std_model)
        with pytest.raises(ValueError, match="unknown precision"):
            fac.plan_fn((64, 64), 1, SingleDevice(), "fp8")

    def test_legacy_single_arg_factory_pins_f32(self):
        from repro.runtime.executor import EngineFactory, SingleDevice

        fac = EngineFactory(lambda hw: _std_model(hw))
        assert fac.plan_fn((64, 64), 1, SingleDevice()) is not None
        with pytest.raises(ValueError, match="precision-aware"):
            fac.plan_fn((64, 64), 1, SingleDevice(), "bfp")


class TestConcurrentTranspose:
    """Bugfix regression: FCNEngine used to stash ``transposed`` as
    mutable instance state read later by ``_conv`` — two concurrent
    traces could bake the WRONG kernel orientation into a compiled
    engine.  ``transposed`` is now a threaded argument."""

    def test_no_transposed_attribute(self):
        eng = FCNEngine(tiny_program())
        params = eng.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 16, 3))
        eng(params, x)
        eng(params, x, transposed=True)
        assert not hasattr(eng, "_transposed")

    def test_concurrent_traces_keep_orientation(self):
        prog = tiny_program((16, 16))
        progT = tiny_program((16, 16))
        eng = FCNEngine(prog)
        engT = FCNEngine(progT)
        params = eng.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 16, 3))
        xT = jnp.transpose(x, (0, 2, 1, 3))
        want = np.asarray(eng(params, x)["sg"])
        wantT = np.asarray(engT(params, xT, transposed=True)["sg"])

        n_rounds, n_threads = 8, 4
        errors = []
        barrier = threading.Barrier(n_threads)

        def worker(i):
            try:
                for r in range(n_rounds):
                    barrier.wait()
                    if (i + r) % 2 == 0:
                        got = np.asarray(eng(params, x)["sg"])
                        ref = want
                    else:
                        got = np.asarray(engT(params, xT,
                                              transposed=True)["sg"])
                        ref = wantT
                    np.testing.assert_allclose(got, ref, atol=1e-5)
            except Exception as e:            # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[0]


class TestBFPWeightQuantization:
    """Bugfix regression: with ``bfp`` set, ``_conv`` used to quantize
    activations but silently run UN-quantized f32 weights unless the
    caller remembered ``normalize_weights()`` first.  A BFP engine now
    reads a conv's weights only in their load-time form and refuses raw
    ones at trace time."""

    def setup_method(self, _):
        self.prog = tiny_program(bn=False)     # no BN: normalize_weights
                                               # is then ONLY the BFP
                                               # weight roundtrip
        self.x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 16, 3))

    @pytest.mark.parametrize("name,key", [("cc", MATRIX), ("c2", KERNEL)],
                             ids=["conv1x1", "conv3x3"])
    def test_raw_params_are_refused(self, name, key):
        """One word's normalized weights handed back raw (under ``w``)
        are refused when the engine is traced, naming the key it reads
        and normalize_weights."""
        eng = FCNEngine(self.prog, bfp=BFPConfig(mantissa_bits=6))
        params = eng.normalize_weights(eng.init_params(jax.random.PRNGKey(1)))
        p = dict(params[name])
        w = p.pop(key)
        p["w"] = w[None, None] if key == MATRIX else w
        params[name] = p
        with pytest.raises(ValueError, match=f"'{key}'.*normalize_weights"):
            jax.eval_shape(eng, params, self.x)

    def test_raw_params_differ_from_f32(self):
        """The load-time weight roundtrip must actually bite: on the same
        weights a coarse mantissa visibly moves the output vs the f32
        engine."""
        eng_f = FCNEngine(self.prog)
        eng_b = FCNEngine(self.prog, bfp=BFPConfig(mantissa_bits=6))
        params = eng_f.init_params(jax.random.PRNGKey(1))
        a = eng_f(params, self.x)["sg"]
        b = eng_b(eng_b.normalize_weights(params), self.x)["sg"]
        assert float(jnp.max(jnp.abs(a - b))) > 0.0


class TestInterpretDefault:
    """Bugfix regression: the Pallas kernels defaulted interpret=True
    everywhere, so even TPU runs interpreted.  The default now derives
    from the backend (compiled on TPU, interpreted elsewhere)."""

    def test_default_is_backend_derived(self):
        from repro.kernels import default_interpret
        from repro.kernels.bfp_matmul.ops import bfp_matmul
        from repro.kernels.winograd_conv.ops import winograd_conv2d

        for fn in (winograd_conv2d, bfp_matmul):
            p = inspect.signature(fn).parameters["interpret"]
            assert p.default is None, fn.__qualname__
        assert default_interpret() == (jax.default_backend() != "tpu")

    def test_winograd_runs_without_explicit_interpret(self):
        from repro.kernels.winograd_conv.ops import winograd_conv2d

        k = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(k[0], (1, 8, 8, 4))
        w = jax.random.normal(k[1], (3, 3, 4, 8))
        y = winograd_conv2d(x, w)
        assert y.shape == (1, 8, 8, 8)

    def test_bfp_matmul_runs_without_explicit_interpret(self):
        from repro.kernels.bfp_matmul.ops import bfp_matmul

        k = jax.random.split(jax.random.PRNGKey(1))
        a = jax.random.normal(k[0], (16, 32))
        b = jax.random.normal(k[1], (32, 8))
        y = bfp_matmul(a, b)
        np.testing.assert_allclose(np.asarray(y), np.asarray(a @ b),
                                   atol=0.2, rtol=0.2)


class TestServicePrecision:
    """Service-level wiring: STDService(precision=...) routes plan_fn /
    params / telemetry through the requested numerics, and the
    bfp-vs-f32 parity gate holds on the serving buckets."""

    def test_service_records_per_precision_walls(self):
        from repro.launch.serve import STDService
        from repro.runtime.telemetry import CostBook

        img = (np.random.default_rng(0).random((40, 56, 3)) * 255
               ).astype(np.float32)
        svc = STDService(width=0.125, buckets=(64,), max_batch=2,
                         book=CostBook(warmup=0), precision="bfp")
        boxes = svc(img)
        assert isinstance(boxes, list)
        assert svc.factory.stats["compiled"][0]["precision"] == "bfp"
        hw = (64, 64)
        assert svc.book.step_count(hw, 1, "single_device",
                                   precision="bfp") == 1
        assert svc.book.step_count(hw, 1, "single_device") == 0
        # snapshot labels carry the precision only for non-f32
        keys = [k for k in svc.book.snapshot()
                if "step_count" in k and 'stage="step"' in k]
        assert keys and all('precision="bfp"' in k for k in keys)

    def test_invalid_precision_rejected(self):
        from repro.launch.serve import STDService

        with pytest.raises(ValueError, match="unknown precision"):
            STDService(width=0.125, precision="int8")

    def test_parity_gate_on_bucket_grid(self):
        """The acceptance gate: bfp maps within the accuracy budget of
        f32 (and provably quantized), boxes exactly equal under the
        0.5-threshold margin guard."""
        from benchmarks.serve_bench import precision_parity_gate
        from repro.runtime.executor import EngineFactory

        fac = EngineFactory(_std_model)
        for hw in ((64, 64), (64, 128)):
            x = jax.random.uniform(jax.random.PRNGKey(7),
                                   (1,) + hw + (3,))
            of = fac.model(hw, "f32").apply(fac.params(hw, "f32"), x)
            ob = fac.model(hw, "bfp").apply(fac.params(hw, "bfp"), x)
            d, boxes_equal = precision_parity_gate(
                of["score"], of["links"], ob["score"], ob["links"])
            assert 0.0 < d < 0.05, (hw, d)
            assert boxes_equal, hw

    def test_measured_cost_reads_per_precision_series(self):
        from repro.runtime.planner import (AnalyticCost, MeasuredCost,
                                           PlanFeatures)
        from repro.runtime.telemetry import CostBook

        book = CostBook(warmup=0)
        hw, feats = (64, 64), PlanFeatures(flops=1e9, halo_bytes=0.0)
        for _ in range(MeasuredCost.MIN_OBSERVATIONS):
            book.record_step(hw, 1, "single_device", 0.5,
                             precision="bfp")
        mc_f32 = MeasuredCost(book, AnalyticCost())
        mc_bfp = MeasuredCost(book, AnalyticCost(), precision="bfp")
        # the bfp overlay sees the measurement, the f32 one falls back
        assert mc_bfp.step_cost(feats, hw, "single_device", 1,
                                data_n=1, model_n=1) == 0.5
        assert mc_f32.step_cost(feats, hw, "single_device", 1,
                                data_n=1, model_n=1) != 0.5
