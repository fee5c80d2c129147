"""Static microcode optimizer + data-pool memory planner (core/memplan.py)
and its serving integrations: liveness / dead-word / dead-store analysis
on synthetic programs, arena slot accounting, admissible-batch math, the
byte-weighted engine LRU, per-bucket batch caps in the MicroBatcher, the
engine-memory metrics export, and the mode-aware upsample FLOP
accounting in the cost model (core/rowband.program_band_costs)."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import FCNEngine, fuse
from repro.core.assembler import Assembler, LayerSpec, STORAGE_BYTES
from repro.core.memplan import (
    _END,
    MemPlan,
    admissible_batch,
    identity_plan,
    optimize_program,
    plan_disassembly,
    plan_program,
)
from repro.core.rowband import program_band_costs
from repro.launch.batching import FakeClock, LRUCache, MicroBatcher
from repro.runtime.planner import (
    CostParams,
    PlanFeatures,
    features_for_program,
    step_cost,
)
from repro.runtime.telemetry import CostBook, cost_params_from_dict

HW = (8, 8)


def asm(specs, outputs, hw=HW):
    return Assembler((hw[0], hw[1], 3)).assemble(specs, outputs)


def chain_program():
    return asm(
        [
            LayerSpec("c1", "conv", out_ch=4, kernel=3, relu=True),
            LayerSpec("c2", "conv", inputs=["c1"], out_ch=4, kernel=3),
            LayerSpec("c3", "conv", inputs=["c2"], out_ch=2, kernel=1),
        ],
        ["c3"],
    )


class TestLiveness:
    def test_chain_frees_each_region_at_last_use(self):
        p = chain_program()
        plan = plan_program(p)
        assert plan.dead_words == ()
        assert plan.dead_stores == ()
        assert plan.schedule == (0, 1, 2)
        w = plan.words
        assert w[0].free_after == (p.input_addr,)
        assert w[1].free_after == (p.words[0].out_addr,)
        assert w[2].free_after == (p.words[1].out_addr,)
        # the program output is never freed
        out_addr = p.outputs["c3"]
        assert all(out_addr not in wp.free_after for wp in w.values())

    def test_peak_naive_and_slots_exact(self):
        # f32 sizes on an 8x8 plane: input 768, c1/c2 1024, c3 512.
        # drop-at-last-use peak is input+c1 then c1+c2 = 2048; best-fit
        # slot reuse covers the chain with two 1024-byte slots.
        plan = plan_program(chain_program(), dtype_bytes=4)
        assert plan.peak_bytes == 2048
        assert plan.naive_bytes == 768 + 1024 + 1024 + 512
        assert plan.pool_bytes == 2048
        assert plan.slot_bytes == (1024, 1024)
        assert 0.0 < plan.reduction < 1.0

    def test_dtype_bytes_scales_linearly(self):
        p = chain_program()
        f32 = plan_program(p, dtype_bytes=4)
        fp16 = plan_program(p, dtype_bytes=2)
        assert f32.peak_bytes == 2 * fp16.peak_bytes
        assert f32.naive_bytes == 2 * fp16.naive_bytes

    def test_concat_walk_frees_both_members(self):
        p = asm(
            [
                LayerSpec("a", "conv", out_ch=4, kernel=3),
                LayerSpec("b", "conv", out_ch=4, kernel=3),
                LayerSpec("m", "conv", inputs=["a", "b"], out_ch=4,
                          kernel=1),
            ],
            ["m"],
        )
        plan = plan_program(p)
        assert plan.dead_words == ()
        # the concat consumer reads one 8-channel extent; liveness must
        # walk it back to BOTH member regions
        assert set(plan.words[2].free_after) == {
            p.words[0].out_addr, p.words[1].out_addr,
        }

    def test_binary_add_second_operand_read_via_ext_addr2(self):
        p = asm(
            [
                LayerSpec("a", "conv", out_ch=4, kernel=3),
                LayerSpec("b", "conv", out_ch=4, kernel=3),
                LayerSpec("s", "add", inputs=["a", "b"]),
            ],
            ["s"],
        )
        plan = plan_program(p)
        assert plan.dead_words == ()          # b is live ONLY via ext_addr2
        assert set(plan.words[2].free_after) == {
            p.words[0].out_addr, p.words[1].out_addr,
        }


class TestElimination:
    def dead_branch_program(self):
        return asm(
            [
                LayerSpec("c1", "conv", out_ch=4, kernel=3),
                LayerSpec("dead", "conv", inputs=["c1"], out_ch=8,
                          kernel=3),
                LayerSpec("c2", "conv", inputs=["c1"], out_ch=2,
                          kernel=1),
            ],
            ["c2"],
        )

    def test_unreachable_word_is_dead(self):
        plan = plan_program(self.dead_branch_program())
        assert plan.dead_words == (1,)
        assert plan.schedule == (0, 2)
        assert 1 not in plan.words

    def test_default_engine_runs_without_dead_word_weights(self):
        """An engine built with defaults runs its plan: the dead word is
        never traced, so it needs no weights, and the output equals the
        identity-plan run's, which runs every word."""
        p = self.dead_branch_program()
        eng = FCNEngine(p)
        params = eng.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (1, *HW, 3))
        live = {k: v for k, v in params.items() if k != "dead"}
        got = eng(live, x)["c2"]
        eng.memplan = identity_plan(p, eng.memplan.dtype_bytes)
        with pytest.raises(KeyError):
            eng(live, x)
        want = eng(params, x)["c2"]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_optimize_program_removes_and_remaps(self):
        p = self.dead_branch_program()
        opt = optimize_program(p)
        assert len(opt.words) == 2
        assert [opt.layer_specs[i].name for i in range(2)] == ["c1", "c2"]
        assert set(opt.weight_bindings.values()) == {"c1", "c2"}
        assert opt.outputs == p.outputs
        assert opt.addr_shapes == p.addr_shapes     # layout untouched
        assert plan_program(opt).dead_words == ()

    def test_optimize_is_identity_without_dead_words(self):
        p = chain_program()
        assert optimize_program(p) is p

    def test_register_only_cache_is_dead_store(self):
        # c1 caches into the res register; c2 reads the INPUT plane and
        # adds the register.  c1's arena region is never read -> it must
        # execute (the register needs its value) but skip the store.
        p = asm(
            [
                LayerSpec("c1", "conv", out_ch=4, kernel=3, res="cache"),
                LayerSpec("c2", "conv", out_ch=4, kernel=3, res="add"),
                LayerSpec("c3", "conv", inputs=["c2"], out_ch=2,
                          kernel=1),
            ],
            ["c3"],
        )
        plan = plan_program(p)
        assert plan.dead_words == ()
        assert plan.dead_stores == (0,)
        assert plan.words[0].store is False
        assert plan.words[1].drop_cache is True
        assert p.words[0].out_addr not in plan.slot_of

    def test_cached_and_read_region_is_stored(self):
        # here the cache source is ALSO read from the arena -> real store
        p = asm(
            [
                LayerSpec("c1", "conv", out_ch=4, kernel=3, res="cache"),
                LayerSpec("c2", "conv", inputs=["c1"], out_ch=4,
                          kernel=3, res="add"),
            ],
            ["c2"],
        )
        plan = plan_program(p)
        assert plan.dead_stores == ()
        assert plan.words[0].store is True
        assert plan.words[1].drop_cache is True

    def test_res_add_with_empty_cache_raises(self):
        with pytest.raises(ValueError, match="empty cache"):
            plan_program(asm(
                [LayerSpec("c1", "conv", out_ch=4, kernel=3, res="add")],
                ["c1"],
            ))

    def test_duplicate_out_addr_falls_back_to_identity_plan(self):
        p = chain_program()
        p.words[1] = dataclasses.replace(
            p.words[1], out_addr=p.words[0].out_addr)
        plan = plan_program(p)
        assert plan.dead_words == ()
        assert plan.peak_bytes == plan.naive_bytes
        assert all(wp.free_after == () for wp in plan.words.values())


class TestAdmissibleBatch:
    def test_floor_division_of_budget(self):
        assert admissible_batch(100, 450) == 4
        assert admissible_batch(100, 99) == 1       # never below the floor

    def test_rounds_down_to_multiple(self):
        assert admissible_batch(100, 790, multiple=4) == 4
        assert admissible_batch(100, 1600, multiple=4) == 16

    def test_never_below_multiple_or_floor(self):
        assert admissible_batch(100, 100, multiple=4) == 4
        assert admissible_batch(100, 250, floor=2) == 2
        assert admissible_batch(0, 1000) == 1       # degenerate plans
        assert admissible_batch(100, 0) == 1


class TestPlanDisassembly:
    def test_deterministic_and_annotated(self):
        p = chain_program()
        a = plan_disassembly(p)
        assert a == plan_disassembly(p)
        assert "# memplan: words=3 live=3" in a
        assert "# bytes: peak=2048" in a
        assert "# slots: n=2" in a
        assert "fuse_relu" in a                      # c1 carries the relu bit

    def test_dead_words_dropped_from_text(self):
        text = plan_disassembly(TestElimination().dead_branch_program())
        assert "dead_words=1" in text
        # only live words get annotation rows
        assert "# w001" not in text
        assert "# w000" in text and "# w002" in text


class TestZooPlans:
    @pytest.fixture(scope="class")
    def model(self):
        from repro.models.fcn import DetectionModel, build_head
        from repro.models.fcn.pixellink import STDConfig

        return DetectionModel(
            STDConfig(name="pixellink_vgg16", backbone="vgg16",
                      width=0.125, image_size=(64, 64),
                      merge_ch=(16, 16, 8), mode="reference",
                      storage_fp16=False),
            build_head("pixellink"),
        )

    def test_real_head_halves_the_naive_footprint(self, model):
        plan = plan_program(model.program)
        assert plan.dead_words == ()
        assert plan.dead_stores == ()
        assert plan.reduction > 0.5
        assert plan.peak_bytes < plan.pool_bytes <= plan.naive_bytes

    def test_fusion_facts_present(self, model):
        plan = plan_program(model.program)
        facts = list(plan.words.values())
        assert any(wp.fuse_relu for wp in facts)
        assert any(wp.fuse_upsample for wp in facts)


class TestUpsampleFlopModes:
    def upsample_program(self):
        return asm([LayerSpec("up", "upsample", out_ch=4)], ["up"])

    def test_optimized_counts_fused_macs(self):
        p = self.upsample_program()
        macs = fuse.upsample_mac_counts(HW[0], HW[1], 3, 4)
        opt = program_band_costs(p, mode="optimized")["flops"]
        ref = program_band_costs(p, mode="reference")["flops"]
        # fused path: one 9-tap MAC per INPUT position (4x fewer); the
        # cost model pins exactly the 75% MAC reduction of
        # fuse.upsample_mac_counts — mode="optimized" is the default
        assert opt == 2.0 * 9 * 3 * 4 * HW[0] * HW[1]
        assert ref == 4.0 * opt
        assert opt / ref == pytest.approx(1.0 - macs["reduction"])
        assert program_band_costs(p)["flops"] == opt

    def test_nearest_upsample_unaffected_by_mode(self):
        p = asm([LayerSpec("up", "upsample", out_ch=4,
                           upsample_mode="nearest")], ["up"])
        assert (program_band_costs(p, mode="optimized")["flops"]
                == program_band_costs(p, mode="reference")["flops"])

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError):
            program_band_costs(self.upsample_program(), mode="eager")


class TestPlannerFeatures:
    def test_features_carry_act_bytes(self):
        p = chain_program()
        f = features_for_program(p, 1)
        assert f.act_bytes == float(plan_program(p).peak_bytes)
        f16 = features_for_program(p, 1, dtype_bytes=2)
        assert f16.act_bytes == f.act_bytes / 2

    def test_step_cost_memory_term_monotone(self):
        base = PlanFeatures(flops=1e9, halo_bytes=0.0, deepest_stride=32)
        heavy = dataclasses.replace(base, act_bytes=1e9)
        c0 = step_cost(base, "single_device", 4)
        c1 = step_cost(heavy, "single_device", 4)
        assert c1 > c0
        # act_bytes defaults to 0 -> legacy features cost the same as
        # before the memory term existed
        assert c0 == step_cost(
            dataclasses.replace(base, act_bytes=0.0), "single_device", 4)

    def test_cost_params_dict_back_compat(self):
        # pre-memplan JSON files carry no hbm_bw field; loading them
        # must fall back to the default, not crash
        p = cost_params_from_dict({"peak_flops": 1e12})
        assert p.hbm_bw == CostParams().hbm_bw


class TestByteWeightedLRU:
    def test_evicts_lru_first_over_budget(self):
        c = LRUCache(capacity=10, byte_budget=100)
        c.put("a", 1, weight=60)
        c.put("b", 2, weight=60)
        assert "a" not in c and "b" in c
        assert c.weight_bytes == 60

    def test_most_recent_entry_always_survives(self):
        c = LRUCache(capacity=10, byte_budget=100)
        c.put("a", 1, weight=60)
        c.put("big", 2, weight=500)       # over budget alone: still kept
        assert "big" in c and "a" not in c
        assert len(c) == 1

    def test_zero_budget_disables_byte_rule(self):
        c = LRUCache(capacity=10)
        c.put("a", 1, weight=10**12)
        c.put("b", 2, weight=10**12)
        assert "a" in c and "b" in c

    def test_unweighted_entries_count_zero(self):
        c = LRUCache(capacity=10, byte_budget=100)
        c.put("a", 1)
        c.put("b", 2, weight=90)
        assert "a" in c and "b" in c
        assert c.weight_bytes == 90


class TestBatcherBucketCaps:
    def caps(self, key):
        return {"big": 2, "small": 16}.get(key, 0)

    def test_cap_replaces_max_batch(self):
        mb = MicroBatcher(lambda k, ps: ps, max_batch=8,
                          max_batch_for=self.caps)
        assert mb._cap("big") == 2
        assert mb._cap("small") == 16      # raised ABOVE max_batch
        assert mb._cap("other") == 8       # <=0 falls back

    def test_cap_errors_fall_back_to_max_batch(self):
        def boom(key):
            raise RuntimeError("no plan")

        mb = MicroBatcher(lambda k, ps: ps, max_batch=8,
                          max_batch_for=boom)
        assert mb._cap("big") == 8

    def test_capped_bucket_flushes_at_cap(self):
        clock = FakeClock()
        mb = MicroBatcher(lambda k, ps: [x * 2 for x in ps],
                          max_batch=8, max_wait_ms=5.0, clock=clock,
                          inflight=0, max_batch_for=self.caps)
        with mb:
            futs = [mb.submit("big", i) for i in range(4)]
            assert [f.result(timeout=30) for f in futs] == [0, 2, 4, 6]
            futs = [mb.submit("small", i) for i in range(3)]
            clock.advance(0.01)
            assert [f.result(timeout=30) for f in futs] == [0, 2, 4]
        flushed = [(b["key"], b["n"], b["reason"])
                   for b in mb.stats["batches"]]
        assert flushed.count(("big", 2, "full")) == 2
        assert all(n <= 2 for k, n, _ in flushed if k == "big")
        assert ("small", 3, "timeout") in flushed


class TestServiceIntegration:
    @pytest.fixture(scope="class")
    def svc(self):
        from repro.launch.serve import STDService

        # budget = 2 images' worth of the 64x64 plan -> cap 2 < max 4
        return STDService(width=0.125, buckets=(64,), max_batch=4,
                          engine_cache_capacity=0, book=CostBook(warmup=0),
                          activation_budget_bytes=2 * 262144)

    def test_bucket_cap_from_plan(self, svc):
        per_img = svc.factory.memplan((64, 64)).peak_bytes
        assert svc._bucket_cap((64, 64)) == admissible_batch(
            per_img, svc.activation_budget_bytes)
        assert svc._bucket_cap((64, 64)) < svc.max_batch

    def test_engine_weight_is_plan_peak_times_batch(self, svc):
        fac = svc.factory
        assert fac.engine_weight_bytes((64, 64), 3) == \
            3 * fac.memplan((64, 64)).peak_bytes
        # bfp engines store fp16 activations: half the planned bytes
        assert fac.memplan((64, 64), "bfp").peak_bytes == \
            fac.memplan((64, 64)).peak_bytes // 2

    def test_engine_memory_gauges_exported(self, svc):
        row = svc.measure_engine_memory((64, 64), batch=1)
        assert row["planned_peak_bytes"] == \
            svc.factory.memplan((64, 64)).peak_bytes
        snap = svc.metrics_snapshot()
        lbl = 'bucket="64x64",batch="1",plan="single_device"'
        planned = [k for k in snap
                   if k.startswith("std_engine_planned_peak_bytes")
                   and lbl in k and 'model="pixellink"' in k]
        assert len(planned) == 1
        assert snap[planned[0]] == float(row["planned_peak_bytes"])
        assert any(k.startswith("std_bucket_batch_cap{bucket=\"64x64\"")
                   for k in snap)
        assert any(k.startswith("std_engine_temp_bytes") and lbl in k
                   for k in snap)
        # planned-vs-measured sanity: same order of magnitude (XLA
        # fuses aggressively, so only a generous band is stable)
        ratio = row["temp_bytes"] / row["planned_peak_bytes"]
        assert 0.1 < ratio < 50.0

    def test_lifetime_sentinel_exceeds_any_program(self):
        assert _END > 10**6
        assert isinstance(MemPlan.reduction, property)
