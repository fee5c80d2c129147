"""GridPlan (DataParallel x RowBand composed on a 2-D mesh) — slow tier.

Each test spawns a subprocess with an 8-device host platform (the main
pytest process must keep seeing ONE device; see conftest).  Covers:

  * halo_exchange on a 2x4 (data, model) mesh: rows move along "model"
    only, each data-parallel batch shard keeps its own plane, true-border
    halos are zero, and both the ppermute and the all_gather fallback
    paths are exact;
  * the acceptance check — GridPlan boxes identical to SingleDevice for
    fixed-seed inputs, end to end through STDService (plus cost-model
    routing of over-tall and transposed over-wide images onto row-banded
    plans);
  * a property-based plan-parity suite (hypothesis shim): random seeds /
    buckets / batch sizes, identical boxes across SingleDevice vs
    DataParallel vs RowBand vs GridPlan, skipping assertions when any
    score or link lands within 1e-6 of the 0.5 threshold (Winograd tile
    regrouping at non-tile-multiple band offsets can shift scores by
    ~1e-6 — see runtime/executor.py).
"""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
TESTS = os.path.dirname(__file__)


def run_sub(body: str, timeout: int = 900) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, {SRC!r})
        sys.path.insert(0, {TESTS!r})
    """) + textwrap.dedent(body)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout


class TestHaloExchange2D:
    def test_model_axis_only_on_2x4_mesh(self):
        """Direct unit test: on a (data=2, model=4) mesh the exchange is
        correct along "model" for the narrow (ppermute), band-equal, and
        wide (all_gather) halo paths, and never leaks rows between batch
        shards on the "data" axis."""
        out = run_sub("""
            import numpy as np
            import jax
            import jax.numpy as jnp
            from jax.sharding import PartitionSpec as P
            from repro.launch.mesh import make_mesh
            from repro.runtime.collectives import halo_exchange

            mesh = make_mesh((2, 4), ("data", "model"))
            # global (N=2, H=8, W=1, C=1): batch over "data", rows over
            # "model" -> local band (1, 2, 1, 1); the +100 offset makes
            # any cross-data leak change values, not just positions
            x = np.arange(2 * 8, dtype=np.float32).reshape(2, 8, 1, 1)
            x[1] += 100.0

            def want(halo):
                # reference: zero-pad each image's own plane, slice each
                # band's extended window back out
                bands = []
                for n in range(2):
                    padded = np.pad(x[n, :, 0, 0], (halo, halo))
                    bands.append(np.concatenate(
                        [padded[i * 2:i * 2 + 2 + 2 * halo]
                         for i in range(4)]
                    ))
                return np.stack(bands)

            # halo=1: ppermute path; halo=2: whole-band edge case;
            # halo=3 and 5: all_gather fallback (receptive field spans
            # several bands); axis_size both static and psum-derived
            for halo, axis_size in [(1, 4), (1, 0), (2, 4), (3, 4),
                                    (3, 0), (5, 4)]:
                f = jax.shard_map(
                    lambda a: halo_exchange(
                        a, "model", halo, axis=1, axis_size=axis_size),
                    mesh=mesh, in_specs=P("data", "model", None, None),
                    out_specs=P("data", "model", None, None),
                    check_vma=False,
                )
                got = np.asarray(f(jnp.asarray(x))).squeeze()
                np.testing.assert_array_equal(
                    got, want(halo),
                    err_msg=f"halo={halo} axis_size={axis_size}",
                )
            print("HALO_2D_OK")
        """, timeout=300)
        assert "HALO_2D_OK" in out

    def test_rejects_tuple_axis_names(self):
        """A tuple of mesh axes would silently band over the flattened
        product axis; it must be rejected up front (no devices needed —
        the check fires before any collective)."""
        from repro.runtime.collectives import halo_exchange

        import jax.numpy as jnp

        with pytest.raises(TypeError, match="single named mesh axis"):
            halo_exchange(jnp.ones((1, 4, 1, 1)), ("data", "model"), 1)


class TestGridPlanParity:
    def test_grid_boxes_identical_to_single_device(self):
        """The acceptance check: on an 8-device 2x4 host mesh GridPlan
        produces boxes identical to SingleDevice for fixed-seed inputs,
        sequential and micro-batched, and the cost-model planner routes
        over-tall / transposed over-wide images onto row-banded plans."""
        out = run_sub("""
            import numpy as np
            from repro.data.images import RequestStream
            from repro.launch.mesh import make_mesh
            from repro.launch.serve import STDService
            from repro.runtime.executor import GridPlan
            from repro.runtime.planner import Planner

            mesh = make_mesh((2, 4), ("data", "model"))
            # grid on model=4 needs H % (4*32) == 0 -> 128-row buckets
            kw = dict(width=0.125, buckets=(128,), max_batch=4)
            key = lambda rs: [[b["box"] for b in r] for r in rs]
            images = RequestStream(
                6, seed=3, hw_range=((48, 96), (48, 96))).images()

            base = STDService(**kw)
            want = key([base(img) for img in images])

            grid = STDService(**kw, plan=GridPlan(mesh))
            got_seq = key([grid(img) for img in images])
            assert got_seq == want, "grid sequential diverged"
            got_bat = key(grid.serve_batched(images))
            assert got_bat == want, "grid batched diverged"
            plans = {e["plan"] for e in grid.factory.stats["compiled"]}
            assert plans == {"grid[data=2,model=4]"}, plans

            # cost-model routing: over-tall images (bucket clamp 256,
            # already a band-unit multiple) are forced onto a row-banded
            # plan and match the single-device reference
            svc = STDService(width=0.125, buckets=(64,), max_batch=4,
                             planner=Planner(mesh))
            tall = np.random.default_rng(7).random(
                (200, 48, 3)).astype(np.float32)
            got_tall = [b["box"] for b in svc(tall)]
            choice = svc.stats["plan_choices"][(256, 64)]
            assert choice.startswith(("row_band", "grid")), choice
            ref = STDService(width=0.125, buckets=(64,), max_batch=4)
            assert got_tall == [b["box"] for b in ref(tall)], \\
                "planner-routed over-tall diverged"

            # transposed over-wide rides the same row-banded routing;
            # the reference must transpose too (a non-transposing
            # service pads the ORIGINAL orientation to a different
            # bucket), so compare against tall_plan=SingleDevice —
            # same §IV.B transpose trick, single-device engine
            from repro.runtime.executor import SingleDevice
            wide = np.random.default_rng(9).random(
                (48, 200, 3)).astype(np.float32)
            got_wide = [b["box"] for b in svc(wide)]
            assert svc.stats["transposed"] == 1
            choice = svc.stats["plan_choices"][(256, 64)]
            assert choice.startswith(("row_band", "grid")), choice
            ref_t = STDService(width=0.125, buckets=(64,), max_batch=4,
                               tall_plan=SingleDevice())
            assert got_wide == [b["box"] for b in ref_t(wide)], \\
                "planner-routed over-wide diverged"
            print("GRID_PARITY_OK")
        """)
        assert "GRID_PARITY_OK" in out

    def test_grid_rejects_misaligned_height(self):
        """Band-height invariant at compile time: H not divisible into
        bands x deepest stride must raise, not mis-shard."""
        out = run_sub("""
            from repro.launch.mesh import make_mesh
            from repro.models.fcn.pixellink import PixelLinkModel, STDConfig
            from repro.runtime.executor import EngineFactory, GridPlan

            fac = EngineFactory(lambda hw: PixelLinkModel(STDConfig(
                backbone="vgg16", width=0.125, image_size=hw,
                merge_ch=(16, 16, 8), mode="optimized",
                storage_fp16=False)))
            mesh = make_mesh((2, 4), ("data", "model"))
            try:
                fac.plan_fn((64, 64), 2, GridPlan(mesh))
            except ValueError as e:
                assert "band height" in str(e) or "divisible" in str(e)
            else:
                raise AssertionError("H=64 on 4 bands must be rejected")
            try:
                fac.plan_fn((128, 64), 3, GridPlan(mesh))
            except ValueError as e:
                assert "divisible" in str(e)
            else:
                raise AssertionError("batch=3 on data=2 must be rejected")

            # a data-sharded tall_plan is bound by the same max_batch
            # divisibility rule as the service default plan: padded
            # batches must never exceed the configured maximum
            from repro.launch.serve import STDService
            try:
                STDService(width=0.125, buckets=(64,), max_batch=5,
                           tall_plan=GridPlan(mesh))
            except ValueError as e:
                assert "multiple" in str(e)
            else:
                raise AssertionError(
                    "max_batch=5 with a data=2 tall_plan must be rejected")
            print("GRID_VALIDATION_OK")
        """, timeout=300)
        assert "GRID_VALIDATION_OK" in out


class TestPlanParityProperty:
    def test_random_seeds_buckets_batches(self):
        """Property suite: for random (seed, bucket, batch), all four
        plans label identically — modulo the 0.5-threshold guard."""
        out = run_sub("""
            import numpy as np
            import jax
            import jax.numpy as jnp
            from _hypothesis_compat import given, settings, strategies as st
            from repro.launch.mesh import make_mesh
            from repro.models.fcn.pixellink import PixelLinkModel, STDConfig
            from repro.runtime.executor import (DataParallel, EngineFactory,
                                                GridPlan, RowBand,
                                                SingleDevice)

            mesh = make_mesh((2, 4), ("data", "model"))
            fac = EngineFactory(lambda hw: PixelLinkModel(STDConfig(
                backbone="vgg16", width=0.125, image_size=hw,
                merge_ch=(16, 16, 8), mode="optimized",
                storage_fp16=False)))
            # (bucket, batch) combos bounded so engines compile once and
            # examples replay from the LRU; heights are band-unit
            # multiples of the 2x4 mesh (4 bands x stride 32)
            COMBOS = [((128, 64), 2), ((128, 64), 4), ((256, 64), 2)]
            guards = {}
            checked = [0]
            skipped = [0]

            def score_gap(hw, params, x):
                fn = guards.get((hw, x.shape[0]))
                if fn is None:
                    model = fac.model(hw)
                    fn = jax.jit(lambda p, a: model.apply(p, a))
                    guards[(hw, x.shape[0])] = fn
                out = fn(params, x)
                return float(jnp.minimum(
                    jnp.min(jnp.abs(out["score"] - 0.5)),
                    jnp.min(jnp.abs(out["links"] - 0.5)),
                ))

            @settings(max_examples=6)
            @given(st.integers(0, 2**31 - 1), st.sampled_from(COMBOS))
            def prop(seed, combo):
                hw, batch = combo
                params = fac.params(hw)
                rng = np.random.default_rng(seed)
                x = jnp.asarray(
                    rng.random((batch,) + hw + (3,)).astype(np.float32))
                vq = jnp.asarray(np.stack([
                    rng.integers(1, hw[0] // 4 + 1, size=batch),
                    rng.integers(1, hw[1] // 4 + 1, size=batch),
                ], axis=1).astype(np.int32))
                # the known guard: Winograd tile regrouping at band
                # offsets can shift scores ~1e-6, enough to flip a
                # threshold decision only when a score is already within
                # 1e-6 of 0.5 — skip those (never observed with these
                # seeds, min gap is typically ~1e-4)
                if score_gap(hw, params, x) < 1e-6:
                    skipped[0] += 1
                    return
                want = np.asarray(
                    fac.plan_fn(hw, batch, SingleDevice())(params, x, vq)[0])
                for plan in (DataParallel(mesh, "data"),
                             RowBand(mesh, axis="model"),
                             GridPlan(mesh)):
                    got = np.asarray(
                        fac.plan_fn(hw, batch, plan)(params, x, vq)[0])
                    assert np.array_equal(got, want), (
                        f"{type(plan).__name__} diverged: hw={hw} "
                        f"batch={batch} seed={seed}")
                checked[0] += 1

            prop()
            assert checked[0] >= 1, "every example hit the threshold guard"
            print(f"PROP_PARITY_OK checked={checked[0]} "
                  f"skipped={skipped[0]}")
        """)
        assert "PROP_PARITY_OK" in out
