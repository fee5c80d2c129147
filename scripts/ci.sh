#!/usr/bin/env bash
# Tiered CI: fast tier first for quick signal (property tests capped to
# a few seeded examples — the cap applies to the _hypothesis_compat shim;
# with real hypothesis installed, per-test @settings win and the smoke
# tier is full-size — slow-marked multi-process tests excluded), then the
# full fast tier, then the slow tier.  Extra args pass to pytest.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== smoke tier (capped property examples) =="
HYPOTHESIS_COMPAT_MAX_EXAMPLES=5 python -m pytest -q -x -m "not slow" "$@"

echo "== fast tier (full example counts) =="
python -m pytest -q -m "not slow" "$@"

echo "== tier-2: GridPlan parity + cost-model planner on an 8-device (2x4) host mesh =="
# Grid-parity property suite and planner routing: the gridplan tests
# spawn 8-device (2x4 data x model) subprocesses themselves; the fast
# planner suite rides along so a planner regression fails this stage
# even when invoked with path args that skip the fast tiers.
python -m pytest -q -m "slow" tests/test_gridplan.py
python -m pytest -q tests/test_planner.py

echo "== tier-2: async pipelined dispatch parity + in-flight stress on the 8-device mesh =="
# Async-vs-sync box parity (GridPlan, 0.5-threshold guard) and the
# bounded in-flight stress run; the subprocess sets the 8-device
# (2x4 data x model) host platform itself.  The fast-tier async tests
# (dispatch/completion semantics, fake-clock harness, stats hammer)
# already ran in the tiers above.
python -m pytest -q -m "slow" tests/test_async_serving.py

echo "== tier-2: calibrate smoke — fit CostParams on host CPU, reload, route =="
# A tiny serve_bench --calibrate sweep must produce a params file that
# parses, reloads into CostParams, and routes the canonical bucket grid
# identically to the in-memory fit (the --cost-params seam).
calib_tmp="$(mktemp -d)"
trap 'rm -rf "$calib_tmp"' EXIT
python -m benchmarks.serve_bench --calibrate "$calib_tmp/cost_params.json" \
  --width 0.125 --buckets 64 --max-batch 2 --calib-steps 2
python - "$calib_tmp/cost_params.json" <<'PYEOF'
import json, sys
from repro.runtime.planner import CostParams, choose_kind, PlanFeatures
from repro.runtime.telemetry import cost_params_from_dict, load_cost_params

path = sys.argv[1]
doc = json.load(open(path))
assert doc["measurements"], "calibration saved no measurement rows"
p1 = cost_params_from_dict(doc["cost_params"])
p2 = load_cost_params(path)
assert p1 == p2 and isinstance(p2, CostParams)
feats = lambda h, w: PlanFeatures(flops=2e5 * h * w / 64.0,
                                  halo_bytes=3e4 * w / 64.0,
                                  deepest_stride=32)
grid = [((h, w), b, (dn, mn))
        for (h, w) in ((64, 64), (128, 128), (512, 64), (2048, 64))
        for b in (1, 8) for (dn, mn) in ((1, 1), (4, 1), (1, 4), (2, 4))]
route = lambda p: [choose_kind(feats(*hw), hw, b, data_n=dn, model_n=mn,
                               params=p) for hw, b, (dn, mn) in grid]
assert route(p1) == route(p2), "reloaded params routed differently"
print(f"calibrate smoke OK: {len(doc['measurements'])} rows, "
      f"{len(grid)} routes identical after reload")
PYEOF

echo "== tier-2: precision modes — bfp-vs-f32 box parity + engine-state regressions =="
# The bfp-vs-f32 accuracy-parity smoke (0.5-threshold guard on the
# bucket grid), the per-precision engine LRU keying, the concurrent
# transposed-tracing regression, and the raw-weight refusal of BFP
# engines all live in test_precision.py; the kernel
# interpret-default regressions ride along.  These also run in the fast
# tiers — this stage keeps them failing loudly when CI is invoked with
# path args that skip the fast tiers.
python -m pytest -q tests/test_precision.py

echo "== tier-2: device postprocess — parity suite + serve_bench A/B smoke =="
# The postprocess parity suite (log-hop + Pallas CCL vs the union-find
# oracle, device-vs-host box extraction, serpentine worst case, service
# wiring) plus a tiny serve_bench --postprocess device run proving the
# exact-box-parity gate passes and the device tail measurably reduces
# the blocked stage="postprocess" wall.  The suite also runs in the
# fast tiers; this stage keeps it failing loudly under path args.
python -m pytest -q tests/test_postprocess_device.py
python -m benchmarks.serve_bench --postprocess device \
  --width 0.125 --buckets 64 --max-batch 2 --requests 8

echo "== tier-2: model zoo — EAST/DB parity suite + serve_bench --model smoke =="
# The three detection heads through the one assembler->microcode seam:
# golden disassembly byte-stability, cross-model engine-LRU keying,
# per-model service routing, and each head's serving decode vs its
# NumPy reference oracle — plus a tiny serve_bench --model sweep
# proving the per-model box-parity gate passes end to end.  The suite
# also runs in the fast tiers; this stage keeps it failing loudly when
# CI is invoked with path args that skip them.
python -m pytest -q tests/test_model_zoo.py
python scripts/regen_golden_models.py --check
python -m benchmarks.serve_bench --model pixellink east db \
  --width 0.125 --buckets 64 --max-batch 2 --requests 6

echo "== tier-2: fleet router — deterministic multi-replica sim + serve_bench --replicas smoke =="
# The pod-scale serving suite: FakeClock fleet sim pinning p99-vs-round-
# robin tail separation, batch-sheds-before-interactive admission, the
# online refit flipping a routing decision without restart, and replica
# health exclusion/recovery — plus a tiny serve_bench --replicas A/B
# proving two real replicated services route, refit, and aggregate one
# labelled scrape end to end.  The suite also runs in the fast tiers;
# this stage keeps it failing loudly under path args.
python -m pytest -q tests/test_router.py
python -m benchmarks.serve_bench --replicas 2 \
  --router round_robin p99 \
  --width 0.125 --buckets 64 --max-batch 2 --requests 8

echo "== tier-2: memplan — static memory planner suite =="
# The static microcode optimizer / data-pool memory planner: liveness,
# dead-word/dead-store elimination, arena slot accounting, the
# byte-weighted engine LRU, per-bucket batch caps, and memplan golden
# snapshots (--check above already gates them).
python -m pytest -q tests/test_memplan.py

echo "== tier-2: slow distributed/serving tests on a multi-device host mesh =="
# The pytest process itself sees 8 host CPU devices, activating any
# in-process multi-device tests; subprocess-based tests override
# XLA_FLAGS themselves before importing jax, so they are unaffected.
# exit 5 = nothing collected (e.g. a path argument with no slow tests)
# (test_gridplan.py / test_async_serving.py already ran in their stages)
XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
python -m pytest -q -m "slow" --ignore=tests/test_gridplan.py \
  --ignore=tests/test_async_serving.py "$@" \
  || { rc=$?; [ "$rc" -eq 5 ] || exit "$rc"; }
