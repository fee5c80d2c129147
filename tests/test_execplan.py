"""ExecutionPlan layer tests (runtime/executor.py): plan-keyed engine
LRU, unit-mesh plan parity (DataParallel / RowBand == SingleDevice),
halo_exchange semantics, bucket_hw oversize clamping + row-band routing
for over-tall inputs, and — slow tier — the 8-device host-mesh parity
acceptance test (data-parallel and row-band boxes identical to single
device)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def make_factory(capacity=16):
    from repro.models.fcn.pixellink import PixelLinkModel, STDConfig
    from repro.runtime.executor import EngineFactory

    return EngineFactory(
        lambda hw: PixelLinkModel(STDConfig(
            backbone="vgg16", width=0.125, image_size=hw,
            merge_ch=(16, 16, 8), mode="optimized", storage_fp16=False,
        )),
        capacity=capacity,
    )


@pytest.fixture(scope="module")
def unit_mesh():
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh((1, 1), ("data", "model"))


class TestPlanKeyedLRU:
    def _stub_factory(self, capacity):
        from repro.runtime.executor import EngineFactory

        fac = EngineFactory(lambda hw, precision="f32", model="pixellink":
                            None, capacity=capacity)
        fac._compile = (
            lambda hw, batch, plan, precision="f32", model="pixellink":
            ("engine", hw, batch, plan, precision, model))
        # no model behind the stub, so no memory plan to weigh engines by
        fac.engine_weight_bytes = lambda *a, **k: 0
        return fac

    def test_keyed_on_bucket_batch_plan(self, unit_mesh):
        from repro.runtime.executor import DataParallel, RowBand, SingleDevice

        fac = self._stub_factory(capacity=16)
        single = fac.plan_fn((64, 64), 2, SingleDevice())
        assert fac.plan_fn((64, 64), 2, SingleDevice()) is single  # hit
        # every key component is part of the identity
        assert fac.plan_fn((64, 128), 2, SingleDevice()) is not single
        assert fac.plan_fn((64, 64), 4, SingleDevice()) is not single
        assert fac.plan_fn((64, 64), 2, SingleDevice(),
                           "bfp") is not single
        dp = fac.plan_fn((64, 64), 2, DataParallel(unit_mesh, "data"))
        rb = fac.plan_fn((64, 64), 2, RowBand(unit_mesh, axis="model"))
        assert dp is not single and rb is not single and dp is not rb
        from repro.runtime.executor import GridPlan

        gr = fac.plan_fn((64, 64), 2, GridPlan(unit_mesh))
        assert gr not in (single, dp, rb)
        assert fac.plan_fn((64, 64), 2, GridPlan(unit_mesh)) is gr  # hit
        assert len(fac) == 7
        assert fac.engines.hits == 2 and fac.engines.misses == 7

    def test_eviction_at_capacity(self, unit_mesh):
        from repro.runtime.executor import DataParallel, SingleDevice

        fac = self._stub_factory(capacity=2)
        a = fac.plan_fn((64, 64), 1, SingleDevice())
        fac.plan_fn((64, 64), 1, DataParallel(unit_mesh, "data"))
        fac.plan_fn((64, 64), 2, SingleDevice())       # evicts `a`'s key
        assert len(fac) == 2
        assert fac.plan_fn((64, 64), 1, SingleDevice()) is not a  # recompiled

    def test_model_and_param_caches_are_bounded(self):
        from repro.runtime.executor import EngineFactory

        built = []
        fac = EngineFactory(lambda hw: built.append(hw) or object(),
                            capacity=1)
        a = fac.model((64, 64))
        assert fac.model((64, 64)) is a          # cached
        fac.model((128, 64))                     # evicts (64, 64)
        assert len(fac._models) == 1
        assert fac.model((64, 64)) is not a      # rebuilt after eviction
        assert built == [(64, 64), (128, 64), (64, 64)]

    def test_plans_are_hashable_dataclasses(self, unit_mesh):
        from repro.runtime.executor import DataParallel, RowBand, SingleDevice

        assert SingleDevice() == SingleDevice()
        assert hash(DataParallel(unit_mesh)) == hash(DataParallel(unit_mesh))
        assert RowBand(unit_mesh) != DataParallel(unit_mesh)


class TestPlanBatchMultiple:
    def test_single_and_rowband_are_one(self, unit_mesh):
        from repro.runtime.executor import (RowBand, SingleDevice,
                                            plan_batch_multiple)

        assert plan_batch_multiple(SingleDevice()) == 1
        assert plan_batch_multiple(RowBand(unit_mesh)) == 1

    def test_data_parallel_is_axis_size(self, unit_mesh):
        from repro.runtime.executor import DataParallel, plan_batch_multiple

        assert plan_batch_multiple(DataParallel(unit_mesh, "data")) == 1

    def test_grid_is_data_axis_size(self, unit_mesh):
        from repro.runtime.executor import GridPlan, plan_batch_multiple

        assert plan_batch_multiple(GridPlan(unit_mesh)) == 1

    def test_band_height_unit_covers_all_plans(self, unit_mesh):
        from repro.runtime.executor import (GridPlan, RowBand, SingleDevice,
                                            band_height_unit)

        assert band_height_unit(SingleDevice(), 32) == 32
        assert band_height_unit(RowBand(unit_mesh, "model", bands=8),
                                32) == 256
        assert band_height_unit(GridPlan(unit_mesh, bands=4), 32) == 128


class TestHaloExchange:
    def test_unit_axis_is_zero_padding(self, unit_mesh):
        import jax.numpy as jnp
        import jax
        from jax.sharding import PartitionSpec as P

        from repro.runtime.collectives import halo_exchange

        x = jnp.arange(24.0).reshape(1, 4, 3, 2)
        f = jax.shard_map(
            lambda a: halo_exchange(a, "model", 2, axis=1, axis_size=1),
            mesh=unit_mesh, in_specs=P(), out_specs=P(), check_vma=False,
        )
        got = np.asarray(f(x))
        want = np.asarray(jnp.pad(x, ((0, 0), (2, 2), (0, 0), (0, 0))))
        np.testing.assert_array_equal(got, want)

    def test_zero_halo_is_identity(self, unit_mesh):
        import jax.numpy as jnp
        import jax
        from jax.sharding import PartitionSpec as P

        from repro.runtime.collectives import halo_exchange

        x = jnp.ones((1, 4, 3, 2))
        f = jax.shard_map(
            lambda a: halo_exchange(a, "model", 0, axis=1, axis_size=1),
            mesh=unit_mesh, in_specs=P(), out_specs=P(), check_vma=False,
        )
        np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x))


class TestFCNActivationSpecs:
    def test_batch_and_rows_axes(self):
        from jax.sharding import PartitionSpec as P

        from repro.runtime.sharding import fcn_activation_specs

        dp = fcn_activation_specs(batch_axis="data")
        assert dp["image"] == P("data", None, None, None)
        assert dp["labels"] == P("data", None, None)
        rb = fcn_activation_specs(rows_axis="model")
        assert rb["image"] == P(None, "model", None, None)
        assert rb["score"] == P(None, "model", None)

    def test_grid_composes_both_axes(self):
        """The 2-D specs the GridPlan shard_map runs under: batch over
        "data" AND rows over "model" in one layout."""
        from jax.sharding import PartitionSpec as P

        from repro.runtime.sharding import fcn_activation_specs

        g = fcn_activation_specs(batch_axis="data", rows_axis="model")
        assert g["image"] == P("data", "model", None, None)
        assert g["score"] == P("data", "model", None)
        assert g["links"] == P("data", "model", None, None)
        assert g["labels"] == P("data", "model", None)

    def test_fcn_batch_axis_divisibility(self, unit_mesh):
        from repro.runtime.sharding import fcn_batch_axis

        # size-1 axes replicate; divisibility rules exercised on the
        # multi-device mesh in the slow tier
        assert fcn_batch_axis(unit_mesh, 8, "data") is None


class TestUnitMeshPlanParity:
    """DataParallel and RowBand on a 1x1 host mesh must match the
    SingleDevice plan exactly — same program, same numerics, shard_map
    plumbing only (the multi-device version runs in the slow tier)."""

    def test_labels_identical_across_plans(self, unit_mesh):
        import jax.numpy as jnp

        from repro.runtime.executor import DataParallel, RowBand, SingleDevice

        fac = make_factory()
        hw = (64, 64)
        params = fac.params(hw)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.random((2, 64, 64, 3)).astype(np.float32))
        vq = jnp.asarray(np.array([[16, 16], [12, 14]], np.int32))
        want, want_conv = fac.plan_fn(hw, 2, SingleDevice())(params, x, vq)
        want = np.asarray(want)
        assert np.asarray(want_conv).all()
        from repro.runtime.executor import GridPlan

        for plan in (DataParallel(unit_mesh, "data"),
                     RowBand(unit_mesh, axis="model"),
                     GridPlan(unit_mesh)):
            got, conv = fac.plan_fn(hw, 2, plan)(params, x, vq)
            np.testing.assert_array_equal(np.asarray(got), want)
            assert np.asarray(conv).shape == (2,)
            assert np.asarray(conv).all()

    def test_rowband_rejects_misaligned_bands(self):
        from repro.launch.mesh import make_host_mesh
        from repro.runtime.executor import RowBand

        fac = make_factory()
        mesh = make_host_mesh((1, 1), ("data", "model"))
        with pytest.raises(ValueError, match="bands"):
            fac.plan_fn((64, 64), 1, RowBand(mesh, axis="model", bands=2))

    def test_data_parallel_rejects_missing_axis(self, unit_mesh):
        from repro.runtime.executor import DataParallel

        fac = make_factory()
        with pytest.raises(ValueError, match="no axis"):
            fac.plan_fn((64, 64), 2, DataParallel(unit_mesh, "nope"))

    def test_grid_rejects_missing_or_equal_axes(self, unit_mesh):
        from repro.runtime.executor import GridPlan

        fac = make_factory()
        with pytest.raises(ValueError, match="no axis"):
            fac.plan_fn((64, 64), 2, GridPlan(unit_mesh, data_axis="nope"))
        with pytest.raises(ValueError, match="axes must differ"):
            fac.plan_fn((64, 64), 2,
                        GridPlan(unit_mesh, data_axis="model"))

    def test_grid_rejects_misaligned_bands(self, unit_mesh):
        from repro.runtime.executor import GridPlan

        fac = make_factory()
        with pytest.raises(ValueError, match="bands"):
            fac.plan_fn((64, 64), 1, GridPlan(unit_mesh, bands=2))


class TestOversizeBuckets:
    def test_bucket_hw_clamps_instead_of_raising(self):
        from repro.launch.serve import bucket_hw

        assert bucket_hw(48, 100, (64, 128)) == (64, 128)
        # regression: used to raise ValueError (min() of empty sequence)
        assert bucket_hw(300, 80, (64, 128, 256)) == (512, 128)
        assert bucket_hw(100, 3000, (64, 128)) == (128, 3072)

    def test_bucket_hw_fails_fast_beyond_max_width(self):
        from repro.launch.serve import MAX_WIDTH, bucket_hw

        with pytest.raises(ValueError, match="serving limit"):
            bucket_hw(MAX_WIDTH + 8, 64, (64,))
        with pytest.raises(ValueError, match="serving limit"):
            bucket_hw(64, MAX_WIDTH + 8, (64,))

    def test_over_tall_request_served_not_crashed(self):
        from repro.launch.serve import STDService

        svc = STDService(width=0.125, buckets=(64,), max_batch=2)
        img = np.random.default_rng(0).random((100, 48, 3)).astype(np.float32)
        boxes = svc(img)                          # clamped to (128, 64)
        assert isinstance(boxes, list)
        assert any(e["hw"] == (128, 64) for e in svc.factory.stats["compiled"])

    def test_row_band_height_unit(self, unit_mesh):
        from repro.runtime.executor import RowBand, row_band_height_unit

        assert row_band_height_unit(RowBand(unit_mesh, "model"), 32) == 32
        assert row_band_height_unit(
            RowBand(unit_mesh, "model", bands=8), 32) == 256

    def test_tall_height_rounds_to_band_unit(self, unit_mesh):
        from repro.launch.serve import STDService
        from repro.runtime.executor import RowBand

        # an 8-band tall plan needs H % 256 == 0 (8 bands x stride 32);
        # the naive bucket clamp alone (192) used to crash the plan
        # compiler for heights like 150
        # (bands=8 on a 1-wide axis would be rejected at plan-compile
        # time, but _tall_height is pure arithmetic over the plan shape)
        svc = STDService(width=0.125, buckets=(64,),
                         tall_plan=RowBand(unit_mesh, "model", bands=8))
        assert svc._tall_height(192) == 256
        assert svc._tall_height(256) == 256
        assert svc._tall_height(257) == 512

    def test_over_tall_routes_to_rowband_plan(self, unit_mesh):
        from repro.launch.serve import STDService
        from repro.runtime.executor import RowBand

        svc = STDService(width=0.125, buckets=(64,), max_batch=2,
                         tall_plan=RowBand(unit_mesh, axis="model"))
        img = np.random.default_rng(0).random((100, 48, 3)).astype(np.float32)
        boxes = svc(img)
        plans = [e["plan"] for e in svc.factory.stats["compiled"]]
        assert "row_band[model=1]" in plans
        # on the unit mesh the row-band plan is numerically the single
        # device plan: boxes must agree with the clamped-bucket service
        ref = STDService(width=0.125, buckets=(64,), max_batch=2)
        assert [b["box"] for b in boxes] == [b["box"] for b in ref(img)]

    def test_over_wide_transposes_onto_rowband_plan(self, unit_mesh):
        from repro.launch.serve import STDService
        from repro.runtime.executor import RowBand

        svc = STDService(width=0.125, buckets=(64,), max_batch=2,
                         tall_plan=RowBand(unit_mesh, axis="model"))
        wide = np.random.default_rng(1).random((48, 100, 3)).astype(np.float32)
        boxes = svc(wide)
        assert svc.stats["transposed"] == 1      # rides the §IV.B trick
        assert any(e["hw"] == (128, 64) and e["plan"].startswith("row_band")
                   for e in svc.factory.stats["compiled"])
        # boxes come back in original (un-transposed) coordinates:
        # (x0, y0, x1, y1) at 1/4 scale of the 48x100 image
        assert all(b["box"][2] <= 100 // 4 and b["box"][3] <= 48 // 4
                   for b in boxes)


@pytest.mark.slow
class TestHostMeshParity:
    """The acceptance check: on an 8-device host mesh, a data-parallel
    plan and a row-band plan produce boxes identical to the single-device
    plan on the same inputs, end to end through STDService."""

    def test_plans_produce_identical_boxes(self):
        code = textwrap.dedent(f"""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import sys
            sys.path.insert(0, {SRC!r})
            import numpy as np
            from repro.data.images import RequestStream
            from repro.launch.mesh import make_mesh
            from repro.launch.serve import STDService
            from repro.runtime.executor import DataParallel, RowBand

            images = RequestStream(
                6, seed=3, hw_range=((48, 64), (48, 64))).images()
            kw = dict(width=0.125, buckets=(64,), max_batch=4)
            key = lambda rs: [[b["box"] for b in r] for r in rs]

            base = STDService(**kw)
            want = key([base(img) for img in images])

            mesh = make_mesh((4, 2), ("data", "model"))
            try:
                STDService(width=0.125, buckets=(64,), max_batch=3,
                           plan=DataParallel(mesh, "data"))
                raise AssertionError("max_batch=3 on a 4-wide data axis "
                                     "must be rejected")
            except ValueError:
                pass
            dp = STDService(**kw, plan=DataParallel(mesh, "data"))
            got_seq = key([dp(img) for img in images])
            got_bat = key(dp.serve_batched(images))
            assert got_seq == want, "data-parallel sequential diverged"
            assert got_bat == want, "data-parallel batched diverged"
            plans = {{e["plan"] for e in dp.factory.stats["compiled"]}}
            assert plans == {{"data_parallel[data=4]"}}, plans

            rb = STDService(**kw, plan=RowBand(mesh, axis="model"))
            got_rb = key([rb(img) for img in images])
            assert got_rb == want, "row-band diverged"

            # over-tall image exceeding the largest bucket routes to the
            # row-band plan and matches the clamped single-device result
            # (200 -> bucket 256, already a multiple of 8 bands x 32)
            tall = np.random.default_rng(7).random(
                (200, 48, 3)).astype(np.float32)
            mesh8 = make_mesh((1, 8), ("data", "model"))
            svc_tall = STDService(**kw, tall_plan=RowBand(mesh8, axis="model"))
            got_tall = [b["box"] for b in svc_tall(tall)]
            assert any(e["plan"] == "row_band[model=8]"
                       for e in svc_tall.factory.stats["compiled"])
            ref_tall = [b["box"] for b in base(tall)]
            assert got_tall == ref_tall, "over-tall row-band diverged"

            # regression: heights whose bucket clamp (192) is NOT a
            # multiple of bands*stride must pad up to 256 and serve,
            # not crash the plan compiler
            awkward = np.random.default_rng(8).random(
                (150, 48, 3)).astype(np.float32)
            boxes = svc_tall(awkward)
            assert isinstance(boxes, list)
            assert any(e["hw"] == (256, 64) and e["plan"] == "row_band[model=8]"
                       for e in svc_tall.factory.stats["compiled"])
            print("HOST_MESH_PLANS_OK")
        """)
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=900,
        )
        assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
        assert "HOST_MESH_PLANS_OK" in out.stdout
