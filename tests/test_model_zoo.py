"""Model-zoo tests: PixelLink, EAST, and DB heads all compile through
ONE assembler -> microcode -> FCNEngine seam (paper Fig. 4's
configuration flow), the per-model microcode disassembly stays
byte-stable against golden snapshots, the engine LRU keys on the model
axis without collisions, STDService routes per model, and every head's
serving decode matches its pure-NumPy reference oracle on shared maps.

Golden snapshots live in tests/golden/microcode_<model>.txt and are
regenerated (never hand-edited) by scripts/regen_golden_models.py."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.microcode import ExtOp
from repro.models.fcn import (
    DEFAULT_MODEL,
    MODEL_ZOO,
    DetectionModel,
    build_head,
    check_model,
)
from repro.models.fcn.pixellink import STDConfig
from repro.runtime.executor import EngineFactory, SingleDevice
from repro.runtime.telemetry import CostBook

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
GOLDEN_HW = (64, 64)


def golden_model(name: str, hw=GOLDEN_HW) -> DetectionModel:
    """The canonical zoo build the golden snapshots freeze: a tiny
    vgg16 trunk in reference mode, so the microcode depends only on the
    assembler + the head's LayerSpecs — never on precision or runtime
    knobs."""
    return DetectionModel(
        STDConfig(name=f"{name}_vgg16", backbone="vgg16", width=0.125,
                  image_size=tuple(hw), merge_ch=(16, 16, 8),
                  mode="reference", storage_fp16=False),
        build_head(name),
    )


def golden_path(name: str) -> str:
    return os.path.join(GOLDEN_DIR, f"microcode_{name}.txt")


def golden_memplan_path(name: str) -> str:
    return os.path.join(GOLDEN_DIR, f"microcode_{name}_memplan.txt")


def golden_memplan_text(name: str) -> str:
    """Memplan-optimized disassembly + plan annotations for one zoo
    model (core.memplan.plan_disassembly over the canonical golden
    build) — the snapshot that freezes the planner's schedule, slot
    assignment, free-after sets, and fusion facts per head."""
    from repro.core.memplan import plan_disassembly

    return plan_disassembly(golden_model(name).program) + "\n"


def _zoo_factory(capacity: int = 8) -> EngineFactory:
    return EngineFactory(
        lambda hw, precision="f32", model=DEFAULT_MODEL:
            golden_model(model, hw),
        capacity=capacity,
    )


class TestZooCompile:
    @pytest.mark.parametrize("name", sorted(MODEL_ZOO))
    def test_head_compiles_and_applies(self, name):
        """Every zoo head assembles to non-empty microcode and its
        apply() returns exactly the maps the head declares, at the
        declared ranks (quarter-res plane)."""
        m = golden_model(name)
        assert len(m.program.words) > 0
        assert np.asarray(m.microcode_bytes()).size == 32 * len(
            m.program.words)
        params = m.init_params(jax.random.PRNGKey(0))
        x = jax.random.uniform(jax.random.PRNGKey(1), (1, 64, 64, 3))
        out = m.apply(params, x)
        for map_name, rank in m.head.maps:
            assert map_name in out
            assert out[map_name].ndim == rank
            assert out[map_name].shape[1:3] == (16, 16)

    def test_db_residual_head_uses_add_ext_op(self):
        """The DB head's shortcut merge must lower to the explicit
        elementwise-add ext op — the microcode seam the assembler
        add-op channel fix exists for."""
        prog = golden_model("db").program
        adds = [w for w in prog.words if w.ext_opcode == ExtOp.ADD]
        assert adds, "DB program lowered without an ADD ext op"
        # binary add: in_ch is ONE operand's channels, not the sum
        (add,) = adds[-1:]
        assert add.in_ch == add.out_ch

    def test_check_model_rejects_unknown(self):
        with pytest.raises(ValueError, match="unknown model"):
            check_model("craft")


class TestGoldenMicrocode:
    @pytest.mark.parametrize("name", sorted(MODEL_ZOO))
    def test_disassembly_matches_golden(self, name):
        """Byte-stable microcode per model.  On intentional assembler /
        head changes, regenerate with scripts/regen_golden_models.py
        in the same commit."""
        text = golden_model(name).program.disassemble() + "\n"
        with open(golden_path(name)) as f:
            assert f.read() == text, (
                f"microcode drift for {name!r}; if intentional run "
                "scripts/regen_golden_models.py"
            )

    @pytest.mark.parametrize("name", sorted(MODEL_ZOO))
    def test_assembly_is_deterministic(self, name):
        a = golden_model(name).program.disassemble()
        b = golden_model(name).program.disassemble()
        assert a == b

    @pytest.mark.parametrize("name", sorted(MODEL_ZOO))
    def test_memplan_disassembly_matches_golden(self, name):
        """Byte-stable memory plan per model: schedule, arena slots,
        free-after sets, and fusion facts.  A planner or assembler
        change that moves any of them fails here with a diff; if
        intentional, regenerate with scripts/regen_golden_models.py."""
        with open(golden_memplan_path(name)) as f:
            assert f.read() == golden_memplan_text(name), (
                f"memory-plan drift for {name!r}; if intentional run "
                "scripts/regen_golden_models.py"
            )

    @pytest.mark.parametrize("name", sorted(MODEL_ZOO))
    def test_memplan_is_deterministic(self, name):
        assert golden_memplan_text(name) == golden_memplan_text(name)


class TestMemplanBoxParity:
    @pytest.mark.parametrize("name", sorted(MODEL_ZOO))
    @pytest.mark.parametrize("hw", [(64, 64), (96, 96)])
    def test_planned_engine_boxes_identical(self, name, hw):
        """Property over the bucket grid: the engine on its memory plan
        (buffers dropped at last use) and the same engine on the
        identity plan (every word run, everything kept) must be
        BOX-IDENTICAL — same weights, same maps, same reference decode.
        Maps are compared bitwise: the plan may only reorder
        bookkeeping, never arithmetic."""
        from repro.core.memplan import identity_plan

        m = DetectionModel(
            STDConfig(name=f"{name}_vgg16", backbone="vgg16",
                      width=0.125, image_size=hw,
                      merge_ch=(16, 16, 8), mode="optimized",
                      storage_fp16=False),
            build_head(name),
        )
        params = m.init_params(jax.random.PRNGKey(0))
        x = jax.random.uniform(jax.random.PRNGKey(5), (1, *hw, 3))
        planned = m.engine.memplan
        assert planned.reduction > 0
        maps_on = m.apply(params, x)
        m.engine.memplan = identity_plan(m.program, planned.dtype_bytes)
        maps_off = m.apply(params, x)
        for k in maps_off:
            assert np.array_equal(np.asarray(maps_off[k]),
                                  np.asarray(maps_on[k])), k
        valid = (hw[0], hw[1] - 8)

        def boxes(maps):
            return sorted(b["box"] for b in m.head.reference_decode(
                {k: np.asarray(v[0]) for k, v in maps.items()
                 if k != "logits"},
                valid,
            ))

        assert boxes(maps_on) == boxes(maps_off)


class TestEngineLRUModelAxis:
    def test_models_and_params_keyed_per_model(self):
        fac = _zoo_factory()
        by_name = {n: fac.model(GOLDEN_HW, "f32", n)
                   for n in sorted(MODEL_ZOO)}
        assert len({id(m) for m in by_name.values()}) == len(MODEL_ZOO)
        for n, m in by_name.items():
            assert m.head.name == n
            # cache hit: same key returns the same object
            assert fac.model(GOLDEN_HW, "f32", n) is m
        pid = {n: id(fac.params(GOLDEN_HW, "f32", n))
               for n in sorted(MODEL_ZOO)}
        assert len(set(pid.values())) == len(MODEL_ZOO)

    def test_engines_keyed_per_model_no_collision(self):
        """Same (bucket, batch, plan, precision), different model must
        compile DIFFERENT engines — and each engine's payload arity
        proves which head actually ran."""
        fac = _zoo_factory()
        plan = SingleDevice()
        fns = {n: fac.plan_fn(GOLDEN_HW, 1, plan, "f32", n)
               for n in ("pixellink", "east", "db")}
        assert len({id(f) for f in fns.values()}) == 3
        x = jnp.asarray(np.random.default_rng(0).uniform(
            size=(1, *GOLDEN_HW, 3)).astype(np.float32))
        vq = jnp.asarray([[16, 16]], jnp.int32)
        out = {n: fns[n](fac.params(GOLDEN_HW, "f32", n), x, vq)
               for n in fns}
        assert len(out["pixellink"]) == 2      # (labels, converged)
        assert len(out["db"]) == 2             # (labels, converged)
        assert len(out["east"]) == 3           # (score, geo, converged)
        assert np.asarray(out["east"][1]).shape == (1, 16, 16, 4)
        models = {e.get("model") for e in fac.stats["compiled"]}
        assert models == {"pixellink", "east", "db"}

    def test_unknown_model_rejected_at_plan_fn(self):
        fac = _zoo_factory()
        with pytest.raises(ValueError, match="unknown model"):
            fac.plan_fn(GOLDEN_HW, 1, SingleDevice(), "f32", "craft")


class TestServiceModelRouting:
    def _service(self, **kw):
        from repro.launch.serve import STDService
        return STDService(width=0.125, buckets=(64,), max_batch=2,
                          max_wait_ms=4.0, engine_cache_capacity=0,
                          book=CostBook(warmup=0), **kw)

    def test_east_serves_and_labels_telemetry(self):
        svc = self._service(model="east")
        img = np.random.default_rng(2).uniform(
            size=(48, 52, 3)).astype(np.float32)
        boxes = svc.serve_batched([img])[0]
        assert isinstance(boxes, list)
        for b in boxes:
            assert {"label", "box", "area", "score"} <= set(b)
        assert all(e["model"] == "east"
                   for e in svc.factory.stats["compiled"])
        snap = svc.book.snapshot()
        assert any('model="east"' in k for k in snap)
        assert not any('model="pixellink"' in k for k in snap)

    def test_east_device_postprocess_rejected(self):
        with pytest.raises(ValueError, match="no label-map payload"):
            self._service(model="east", postprocess="device")

    def test_db_device_host_box_parity(self):
        img = np.random.default_rng(3).uniform(
            size=(48, 48, 3)).astype(np.float32)
        host = self._service(model="db", postprocess="host")
        dev = self._service(model="db", postprocess="device",
                            boxes_capacity=64)
        bh = host.serve_batched([img])[0]
        bd = dev.serve_batched([img])[0]
        assert [b["box"] for b in bh] == [b["box"] for b in bd]


class TestDecodeParity:
    @pytest.mark.parametrize("name", sorted(MODEL_ZOO))
    def test_decode_matches_reference(self, name):
        """Serving decode (device tail + head.decode) and the pure
        NumPy reference decode must produce identical box sets from ONE
        shared set of eager maps — this gates the decode algorithms, so
        jit-vs-eager float noise at the 0.5 threshold can't flake it."""
        m = golden_model(name)
        head = m.head
        params = m.init_params(jax.random.PRNGKey(3))
        x = jax.random.uniform(jax.random.PRNGKey(4), (1, 64, 64, 3))
        maps = m.apply(params, x)
        valid = (64, 56)      # ragged width exercises the crop path
        fac = _zoo_factory()
        vq = jnp.asarray([[valid[0] // 4, valid[1] // 4]], jnp.int32)
        tail = head.tail(fac, maps, vq)
        arrs = [np.asarray(a)[0] for a in tail[:head.n_payload]]
        payload = arrs[0] if head.n_payload == 1 else tuple(arrs)
        got, kind = head.decode(payload, valid)
        ref = head.reference_decode(
            {k: np.asarray(v[0]) for k, v in maps.items()
             if k != "logits"},
            valid,
        )
        assert kind == "host"
        assert sorted(b["box"] for b in got) \
            == sorted(b["box"] for b in ref)
        if name == "db":      # unclip must have clamped inside the crop
            for b in got:
                x0, y0, x1, y1 = b["box"]
                assert 0 <= x0 <= x1 < valid[1] // 4
                assert 0 <= y0 <= y1 < valid[0] // 4


class TestTelemetryModelAxis:
    def test_series_split_and_labeled_per_model(self):
        book = CostBook(warmup=0)
        book.record_step((64, 64), 1, "single_device", 0.010)
        book.record_step((64, 64), 1, "single_device", 0.020,
                         model="east")
        assert book.step_count((64, 64), 1, "single_device") == 1
        assert book.step_count((64, 64), 1, "single_device",
                               model="east") == 1
        assert book.step_ewma((64, 64), 1, "single_device",
                              model="east") == pytest.approx(0.020)
        snap = book.snapshot()
        east = [k for k in snap if 'model="east"' in k]
        assert east
        # the default model keeps the historical (unlabeled) shape
        base = [k for k in snap
                if "step_count" in k and "model=" not in k]
        assert base
