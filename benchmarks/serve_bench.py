"""STD serving benchmark (the Fig. 9a comparison), two load models:

closed-loop — sequential vs C4-pipelined vs dynamic micro-batched on a
seeded mixed-resolution request stream; reports TPS and p50/p99
per-request latency per mode.

open-loop (``--open-loop``) — Poisson arrivals at one or more offered
rates (``--rates``, requests/s): requests are submitted on a seeded
exponential-interarrival clock regardless of completions, the way real
traffic hits a service.  Reports offered vs achieved TPS, p50/p99
latency, and admission-control sheds per rate — the knee where achieved
TPS flattens and latency diverges is the service's capacity.  Each rate
sweeps the async pipeline depth (``--inflight``, always including the
fully synchronous ``0`` baseline) and reports the overlap gain:
achieved TPS at depth N over achieved TPS on the serialized path at the
same offered load.

Each mode is warmed on the same stream first (compiles are a one-time
deployment cost in the paper's serving story; the steady-state pass is
the measurement), then timed.

``--plan`` picks the ExecutionPlan the service runs on a host device
mesh (``--mesh-shape DATA MODEL``): ``single`` (default), ``data``
(batch over "data"), ``rowband`` (rows over "model"), ``grid`` (both at
once — the composed §IV plan), or ``auto`` (cost-model routing per
bucket via runtime/planner.py).  For row-banded plans the buckets are
rounded up to the band-height unit.  Every run also prints a
``serve_plan`` line per bucket: the plan the cost model would choose and
its estimated step cost — under ``auto`` that choice is also what
actually ran.

calibration (``--calibrate out.json``) — no load model at all: time
every eligible (bucket, plan, batch) combo with blocked steps, fit the
``runtime/planner.CostParams`` constants by least squares
(runtime/telemetry.fit_cost_params), save them to JSON.
``--cost-params out.json`` reloads the fit into the planner, so
``--plan auto`` routes on measured constants instead of the v5e napkin
defaults (the ROADMAP "calibrated cost model" loop).

precision A/B (``--precision bfp``) — the numerics sweep: build one f32
and one bfp service over the same buckets (PRNGKey(0) determinism means
both run ONE underlying weight set — the bfp side through the paper's
Fig. 4 normalization), time blocked steps per (bucket, batch) into each
service's CostBook, and report per-bucket per-precision step walls plus
the bfp/f32 speedup.  Every bucket must pass the accuracy-parity gate
first (docs/serving.md "Precision modes"): bfp score/link maps stay
within an eps accuracy budget of f32 AND the recovered boxes match
exactly once pixels inside the eps margin of the 0.5 threshold are
excluded — confident disagreements fail the run.

fleet A/B (``--replicas N --router round_robin p99 least_loaded``) —
the pod-scale sweep: N replicated services, each with its own
replica-labelled CostBook, behind a launch/router.Router; ONE seeded
request stream (alternating interactive/batch deadline classes) runs
once per named routing policy, and the report carries a per-policy
``--router`` axis: TPS, p50/p99 request latency, placements per
replica, sheds per deadline class, and how many replicas the online
refit re-calibrated from their live books.  One host makes the
replicas homogeneous, so policies should land within noise of each
other here — the heterogeneous-fleet separations (p99 routing beating
round robin on tail latency, batch shedding before interactive) are
pinned deterministically on a FakeClock in tests/test_router.py.

postprocess A/B (``--postprocess device``) — the serving-tail sweep:
serve one seeded request stream through a host-postprocess and a
device-postprocess service (identical weights and routing), gate on
EXACT box parity for every request and bucket, and report per-mode
complete-stage busy time, total ``stage="postprocess"`` walls, TPS, and
p50/p99 — the run fails unless the device path measurably reduces the
postprocess wall (docs/serving.md "Postprocess pipeline").

Run:  PYTHONPATH=src python -m benchmarks.serve_bench --requests 32
      PYTHONPATH=src python -m benchmarks.serve_bench --requests 64 \
          --open-loop --rates 8 32 128 --inflight 1 2 4
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m benchmarks.serve_bench --requests 16 \
          --plan grid --mesh-shape 2 4 --open-loop --rates 8
      PYTHONPATH=src python -m benchmarks.serve_bench \
          --calibrate /tmp/cost.json --buckets 64 128 --max-batch 4
      PYTHONPATH=src python -m benchmarks.serve_bench --plan auto \
          --cost-params /tmp/cost.json
      PYTHONPATH=src python -m benchmarks.serve_bench --precision bfp \
          --buckets 64 --width 0.125 --max-batch 4
      PYTHONPATH=src python -m benchmarks.serve_bench --replicas 2 \
          --router round_robin p99 --buckets 64 --width 0.125
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np


def _pctl(xs, q):
    return float(np.percentile(np.asarray(xs), q) * 1e3) if len(xs) else 0.0


DEEPEST_STRIDE = 32      # vgg16 stride pyramid -> band-height unit factor
                         # (assumption checked against the real model by
                         # _check_band_units once the service exists)


def _check_band_units(svc, planner, plan_kind, buckets):
    """The bucket rounding in _plan_setup assumed DEEPEST_STRIDE; verify
    it against the stride pyramid of the model the service actually
    built, so a backbone/merge change fails here with a clear message
    instead of a ValueError from the plan compiler mid-sweep."""
    if plan_kind not in ("rowband", "grid"):
        return
    top = max(buckets)
    deepest = svc.factory.deepest_stride((top, top))
    unit = planner.height_unit(deepest)
    bad = [b for b in buckets if b % unit]
    if bad:
        raise SystemExit(
            f"buckets {bad} are not multiples of the band-height unit "
            f"{unit} (model deepest stride {deepest} x {planner.model_n} "
            f"bands != assumed {DEEPEST_STRIDE}); adjust --buckets or "
            f"--mesh-shape"
        )


def _plan_setup(plan_kind, mesh_shape, buckets, max_batch,
                cost_params=None):
    """Resolve ``--plan``/``--mesh-shape`` into STDService kwargs, the
    cost-model planner used for the per-bucket report column, and the
    (possibly band-unit-rounded) buckets.  ``cost_params`` is a fitted
    constants file from ``--calibrate`` (see run_calibration); when
    given, the planner's analytic model runs on the fitted constants
    instead of the v5e napkin defaults."""
    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.executor import DataParallel, GridPlan, RowBand
    from repro.runtime.planner import Planner
    from repro.runtime.telemetry import load_cost_params

    n = jax.device_count()
    if mesh_shape is None:
        mesh_shape = {
            "single": (1, 1),
            "data": (n, 1),
            "rowband": (1, n),
        }.get(plan_kind, (2, n // 2) if n % 2 == 0 and n > 1 else (1, n))
    mesh = make_host_mesh(tuple(mesh_shape), ("data", "model"))
    params = load_cost_params(cost_params) if cost_params else None
    planner = Planner(mesh, params=params)
    kw = {}
    if plan_kind == "data":
        kw["plan"] = DataParallel(mesh)
    elif plan_kind == "rowband":
        kw["plan"] = RowBand(mesh)
    elif plan_kind == "grid":
        kw["plan"] = GridPlan(mesh)
    elif plan_kind == "auto":
        kw["planner"] = planner
    elif plan_kind != "single":
        raise SystemExit(f"unknown --plan {plan_kind!r}")
    if plan_kind in ("rowband", "grid"):
        # every bucket height must divide into bands x deepest stride
        unit = planner.height_unit(DEEPEST_STRIDE)
        buckets = tuple(sorted({-(-b // unit) * unit for b in buckets}))
    dn = planner.data_n if plan_kind in ("data", "grid", "auto") else 1
    if max_batch % max(dn, 1):
        raise SystemExit(
            f"--max-batch {max_batch} must be a multiple of the mesh "
            f"data axis {dn} for --plan {plan_kind}"
        )
    return kw, planner, tuple(buckets)


def report_plan_choices(svc, planner, max_batch, verbose=True):
    """The planner-choice column: for every bucket the service compiled,
    what the cost model routes it to (and at what estimated step cost)
    next to what actually ran.  Under --plan auto the service records
    its live routing decisions in stats["plan_choices"] — report those
    (they were made at the batches that actually formed); for fixed
    plans fall back to a hypothetical choice at max_batch."""
    from repro.runtime.executor import describe_plan

    planner.bind_features(svc._plan_features)
    routed = svc.stats.get("plan_choices", {})
    ran = {}
    for e in svc.factory.stats["compiled"]:
        ran.setdefault(e["hw"], set()).add(e["plan"])
    rows = {}
    for hw in sorted(ran):
        choice = routed.get(hw) or describe_plan(
            planner.choose(hw, max_batch))
        # the estimate must belong to the plan named on the row — a
        # routed choice may not be the argmin (force_banded, or routing
        # happened at a different live batch)
        table = planner.costs(hw, max_batch)
        kind = choice.split("[", 1)[0]
        est_us = table.get(kind, min(table.values())) * 1e6
        rows[hw] = {"planner": choice, "est_us": est_us,
                    "ran": sorted(ran[hw])}
        if verbose:
            print(f"serve_plan,bucket={hw[0]}x{hw[1]},"
                  f"planner={choice},est {est_us:.0f} us,"
                  f"ran={'/'.join(sorted(ran[hw]))}")
    return rows


def run_calibration(out_path: str, *, width: float = 0.25,
                    buckets=(64, 128), max_batch: int = 8,
                    mesh_shape=None, steps: int = 3,
                    verbose: bool = True):
    """The measured half of the cost model: sweep every eligible
    (bucket, plan_kind, batch) combo on the current mesh, time ``steps``
    blocked-until-materialized engine steps each (after one warmup call
    that absorbs the jit compile), least-squares fit the CostParams
    constants from the measurements (runtime/telemetry.fit_cost_params
    — the analytic step cost is linear in them), and save the fit to
    ``out_path`` JSON.  ``--cost-params out_path`` reloads it into the
    planner, so routing on THIS backend runs on constants this backend
    actually exhibited."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import STDService
    from repro.runtime.executor import plan_batch_multiple
    from repro.runtime.planner import Planner, eligible_kinds
    from repro.runtime.telemetry import (
        CostBook,
        StepMeasurement,
        fit_cost_params,
        save_cost_params,
    )

    if steps < 1:
        raise SystemExit("--calib-steps must be >= 1")
    n = jax.device_count()
    if mesh_shape is None:
        mesh_shape = (2, n // 2) if n % 2 == 0 and n > 1 else (1, n)
    mesh = make_host_mesh(tuple(mesh_shape), ("data", "model"))
    planner = Planner(mesh)
    if planner.model_n > 1:
        unit = planner.height_unit(DEEPEST_STRIDE)
        buckets = tuple(sorted({-(-b // unit) * unit for b in buckets}))
    if max_batch % max(planner.data_n, 1):
        raise SystemExit(
            f"--max-batch {max_batch} must be a multiple of the mesh "
            f"data axis {planner.data_n}"
        )
    # measured_routing off: the sweep must visit every plan kind at
    # fixed, analytic-routing-independent combos, not chase its own
    # measurements around
    svc = STDService(width=width, buckets=tuple(buckets),
                     max_batch=max_batch, planner=planner,
                     engine_cache_capacity=0, measured_routing=False)
    _check_band_units(svc, planner,
                      "grid" if planner.model_n > 1 else "single", buckets)

    book = CostBook(warmup=0)      # the sweep warms explicitly below
    rows = []
    batch_points = sorted({1, max(1, max_batch // 2), max_batch})
    for bkt in buckets:
        hw = (bkt, bkt)
        feats = svc._plan_features(hw)
        kinds = eligible_kinds(hw, data_n=planner.data_n,
                               model_n=planner.model_n,
                               deepest_stride=feats.deepest_stride)
        seen = set()
        for kind in kinds:
            plan = planner.plan_for_kind(kind)
            m = plan_batch_multiple(plan)
            for b0 in batch_points:
                b = -(-b0 // m) * m          # divisibility padding
                if b > max_batch or (kind, b) in seen:
                    continue
                seen.add((kind, b))
                fn = svc.factory.plan_fn(hw, b, plan)
                params = svc.factory.params(hw)
                x = jnp.zeros((b, hw[0], hw[1], 3), jnp.float32)
                vq = jnp.asarray([[hw[0] // 4, hw[1] // 4]] * b,
                                 jnp.int32)
                jax.block_until_ready(fn(params, x, vq))   # compile+warm
                for _ in range(steps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(params, x, vq))
                    dt = time.perf_counter() - t0
                    book.record_step(hw, b, kind, dt)
                    rows.append(StepMeasurement(
                        flops=feats.flops, halo_bytes=feats.halo_bytes,
                        halo_layers=feats.halo_layers, kind=kind,
                        batch=b, data_n=planner.data_n,
                        model_n=planner.model_n, seconds=dt,
                    ))
                if verbose:
                    p50 = book.step_percentile(hw, b, kind, 50)
                    print(f"calibrate,bucket={hw[0]}x{hw[1]},"
                          f"plan={kind},batch={b},"
                          f"p50 {p50 * 1e3:.2f} ms,steps={steps}")
    fitted = fit_cost_params(rows)
    save_cost_params(fitted, out_path, measurements=rows, meta={
        "width": width, "buckets": list(buckets),
        "mesh_shape": list(mesh_shape), "max_batch": max_batch,
        "steps": steps, "backend": jax.default_backend(),
    })
    if verbose:
        from repro.runtime.telemetry import cost_params_to_dict

        for k, v in cost_params_to_dict(fitted).items():
            print(f"calibrate_fit,{k}={v:.6g}")
        fit_planner = Planner(mesh, params=fitted)
        fit_planner.bind_features(svc._plan_features)
        for bkt in buckets:
            hw = (bkt, bkt)
            for b in (1, max_batch):
                from repro.runtime.executor import describe_plan

                print(f"calibrate_route,bucket={hw[0]}x{hw[1]},"
                      f"batch={b},"
                      f"plan={describe_plan(fit_planner.choose(hw, b))}")
        print(f"calibrate_saved,{out_path},rows={len(rows)}")
    return fitted


PARITY_EPS = 0.05      # accuracy budget for the bfp-vs-f32 parity gate:
                       # max |bfp - f32| over score/link probabilities,
                       # and the 0.5-threshold margin inside which pixel
                       # decisions are excluded from box comparison


def precision_parity_gate(score_f, links_f, score_b, links_b, *,
                          eps: float = PARITY_EPS,
                          score_thr: float = 0.5, link_thr: float = 0.5):
    """The bfp-vs-f32 accuracy-parity check, per batch of probability
    maps (same weights, two numerics).  Two conditions:

      1. ``0 < max|bfp - f32| < eps`` — the upper bound is the accuracy
         budget; the LOWER bound proves the bfp side actually quantized
         (a cross-precision engine-cache bug would produce exact zeros).
      2. boxes under the 0.5-threshold guard: pixels whose f32
         probability sits within ``eps`` of the threshold are excluded
         (clamped to the f32 value — a near-threshold flip is noise, not
         an accuracy loss); every remaining pixel decision, and so the
         recovered boxes, must match EXACTLY.  A confident disagreement
         (f32 says 0.9 text, bfp says 0.2) breaks the equality.

    Returns ``(max_delta, boxes_equal)``.
    """
    import jax.numpy as jnp

    from repro.models.fcn import postprocess as pp

    d = max(float(jnp.max(jnp.abs(score_b - score_f))),
            float(jnp.max(jnp.abs(links_b - links_f))))
    sc = jnp.where(jnp.abs(score_f - score_thr) <= eps, score_f, score_b)
    lc = jnp.where(jnp.abs(links_f - link_thr) <= eps, links_f, links_b)

    def boxes(s, l):
        return [
            sorted(bx["box"] for bx in pp.boxes_from_labels(
                np.asarray(pp.cc_label(s[i], l[i], score_thr, link_thr))))
            for i in range(s.shape[0])
        ]

    return d, boxes(score_f, links_f) == boxes(sc, lc)


def run_precision_ab(*, width: float = 0.25, buckets=(64, 128),
                     max_batch: int = 8, steps: int = 3,
                     eps: float = PARITY_EPS, seed: int = 0,
                     verbose: bool = True):
    """f32-vs-bfp A/B over the full bucket grid: per (bucket, batch)
    blocked step walls from each service's CostBook (the per-precision
    ``stage="step"`` series measured routing reads), gated by the
    accuracy-parity check on every bucket.  Both services are seeded
    identically, so the bfp side serves the SAME weights through the
    paper's Fig. 4 normalization — the comparison is numerics-only."""
    import jax.numpy as jnp

    from repro.launch.serve import STDService
    from repro.runtime.telemetry import CostBook

    if steps < 1:
        raise SystemExit("--calib-steps must be >= 1")
    svcs = {
        prec: STDService(width=width, buckets=tuple(buckets),
                         max_batch=max_batch, engine_cache_capacity=0,
                         book=CostBook(warmup=0), precision=prec)
        for prec in ("f32", "bfp")
    }
    rng = np.random.default_rng(seed)
    batch_points = sorted({1, max(1, max_batch // 2), max_batch})
    out = {}
    for bkt in buckets:
        hw = (bkt, bkt)
        # -- parity gate first: a bucket that fails accuracy must not
        # report a speedup
        x1 = rng.random((1, hw[0], hw[1], 3)).astype(np.float32)
        maps = {}
        for prec, svc in svcs.items():
            model = svc.factory.model(hw, prec)
            params = svc.factory.params(hw, prec)
            o = model.apply(params, jnp.asarray(x1))
            maps[prec] = (o["score"], o["links"])
        d, boxes_equal = precision_parity_gate(
            *maps["f32"], *maps["bfp"], eps=eps,
            score_thr=svcs["f32"].factory.score_thr,
            link_thr=svcs["f32"].factory.link_thr)
        if verbose:
            print(f"precision_parity,bucket={hw[0]}x{hw[1]},"
                  f"max_delta={d:.4g},boxes_equal={boxes_equal}")
        if not 0.0 < d < eps:
            raise SystemExit(
                f"precision parity FAILED at bucket {hw}: max bfp-f32 "
                f"delta {d:.4g} outside (0, {eps}) — zero means the bfp "
                f"engine never quantized (cross-precision cache hit?), "
                f"past eps means the accuracy budget is blown"
            )
        if not boxes_equal:
            raise SystemExit(
                f"precision parity FAILED at bucket {hw}: boxes diverge "
                f"beyond the {eps}-margin 0.5-threshold guard"
            )
        # -- timed A/B: blocked steps into each service's book
        for b in batch_points:
            x = rng.random((b, hw[0], hw[1], 3)).astype(np.float32)
            vhws = [(hw[0], hw[1])] * b
            row = {}
            for prec, svc in svcs.items():
                svc.infer_labels(x, vhws)          # compile + warm
                for _ in range(steps):
                    svc.infer_labels(x, vhws)
                row[prec] = svc.book.step_percentile(
                    hw, b, "single_device", 50, precision=prec)
            row["speedup"] = (row["f32"] / row["bfp"]
                              if row["bfp"] else float("nan"))
            out[(hw, b)] = dict(row, max_delta=d)
            if verbose:
                print(f"precision_ab,bucket={hw[0]}x{hw[1]},batch={b},"
                      f"f32 p50 {row['f32'] * 1e3:.2f} ms,"
                      f"bfp p50 {row['bfp'] * 1e3:.2f} ms,"
                      f"speedup x{row['speedup']:.2f}")
    return out


def run_postprocess_ab(*, requests: int = 48, width: float = 0.25,
                       buckets=(64, 128), max_batch: int = 8,
                       max_wait_ms: float = 8.0, seed: int = 0,
                       boxes_capacity: int = 256, pre_workers: int = 4,
                       steps: int = 3, verbose: bool = True):
    """Host-vs-device postprocess A/B on ONE seeded request stream.

    Both services share weights (PRNGKey(0) determinism) and routing;
    only the serving tail differs — full label-plane D2H + host box
    extraction vs compact on-device rows + trivial decode.  The gate is
    EXACT box parity on every request, reported per bucket; the
    measurement is each mode's ``stage="postprocess"`` wall over a
    BLOCKED single-threaded pass (``steps`` repeats per request — the
    serving-concurrent walls also land in each book, but post workers
    contend for the GIL with dispatch/completion there, so the blocked
    pass is what the reduction gate reads, the same pattern as the
    precision A/B's blocked steps).  Completion-stage busy time, TPS,
    and p50/p99 from the concurrent serving pass are reported alongside.
    Fails unless boxes match everywhere AND the device path's blocked
    postprocess wall is below the host's (the tail reduction this mode
    exists for)."""
    from repro.data.images import RequestStream
    from repro.launch.serve import STDService, bucket_hw
    from repro.runtime.telemetry import CostBook

    if requests < 1:
        raise SystemExit("--requests must be >= 1")
    images = RequestStream(
        requests, seed=seed,
        hw_range=((48, max(buckets)), (48, max(buckets))),
    ).images()
    svcs, results = {}, {}
    for mode in ("host", "device"):
        svc = STDService(width=width, buckets=tuple(buckets),
                         max_batch=max_batch, max_wait_ms=max_wait_ms,
                         engine_cache_capacity=0, inflight=1,
                         book=CostBook(warmup=0), postprocess=mode,
                         boxes_capacity=boxes_capacity)
        svc.serve_batched(images, pre_workers=pre_workers)   # warm/compile
        results[mode] = svc.serve_batched(images,
                                          pre_workers=pre_workers)
        svcs[mode] = svc

    # -- exact-parity gate, reported per bucket ----------------------------
    per_bucket: dict = {}
    for i, img in enumerate(images):
        bkt = bucket_hw(img.shape[0], img.shape[1], tuple(buckets))
        ok = ([b["box"] for b in results["host"][i]]
              == [b["box"] for b in results["device"][i]])
        n_ok, n_all = per_bucket.get(bkt, (0, 0))
        per_bucket[bkt] = (n_ok + ok, n_all + 1)
    for bkt, (n_ok, n_all) in sorted(per_bucket.items()):
        if verbose:
            print(f"postprocess_parity,bucket={bkt[0]}x{bkt[1]},"
                  f"boxes_equal={n_ok}/{n_all}")
        if n_ok != n_all:
            raise SystemExit(
                f"postprocess parity FAILED at bucket {bkt}: "
                f"{n_all - n_ok}/{n_all} requests' device boxes diverge "
                f"from the host path"
            )

    # -- blocked postprocess measurement (single-threaded, the gate) -------
    def pp_wall_sum(svc):
        return sum(
            svc.book.step_total(hw, b, kind, stage="postprocess")
            for (hw, b, kind) in svc.book.step_keys(stage="postprocess")
        )

    blocked = {}
    for mode, svc in svcs.items():
        before = pp_wall_sum(svc)
        for img in images:
            x, valid, tr = svc.preprocess(img)
            payload = svc._finalize(svc._dispatch(x[None], [valid]))[0]
            for _ in range(max(steps, 1)):
                svc.postprocess(payload, valid, tr,
                                bucket_hw=tuple(x.shape[:2]))
        blocked[mode] = pp_wall_sum(svc) - before

    # -- busy-time / throughput report -------------------------------------
    out = {}
    for mode, svc in svcs.items():
        mb = svc.stats["batching"]
        lat = svc.stats["batched_latency_s"]
        out[mode] = {
            "tps": svc.stats["batched_tps"],
            "p50_ms": _pctl(lat, 50),
            "p99_ms": _pctl(lat, 99),
            "complete_busy_s": mb["complete_busy_s"],
            "post_busy_s": mb["post_busy_s"],
            "postprocess_wall_s": blocked[mode],
            "overflows": svc.stats["pp_overflow"],
            "nonconverged": svc.stats["nonconverged"],
        }
        if verbose:
            r = out[mode]
            print(f"postprocess_ab,mode={mode},"
                  f"tps {r['tps']:.2f},"
                  f"p50 {r['p50_ms']:.1f} ms,p99 {r['p99_ms']:.1f} ms,"
                  f"complete_busy {r['complete_busy_s'] * 1e3:.1f} ms,"
                  f"pp_wall {r['postprocess_wall_s'] * 1e3:.1f} ms,"
                  f"overflows {r['overflows']}")
    host_w, dev_w = (out["host"]["postprocess_wall_s"],
                     out["device"]["postprocess_wall_s"])
    if verbose:
        red = 1.0 - dev_w / host_w if host_w > 0 else float("nan")
        dc = (out["host"]["complete_busy_s"]
              - out["device"]["complete_busy_s"])
        print(f"postprocess_ab,pp_wall_reduction {red * 100:.1f}%,"
              f"complete_busy_delta {dc * 1e3:+.1f} ms")
    if not dev_w < host_w:
        raise SystemExit(
            f"postprocess A/B FAILED: device pp wall {dev_w * 1e3:.2f} ms "
            f"not below host {host_w * 1e3:.2f} ms — the compact tail "
            f"should always beat full-plane host extraction"
        )
    return out


def run_model_zoo(models, *, requests: int = 8, width: float = 0.25,
                  buckets=(64,), max_batch: int = 4,
                  max_wait_ms: float = 8.0, seed: int = 0,
                  pre_workers: int = 4, verbose: bool = True):
    """Per-model box-parity gate + serving smoke over the detection zoo.

    For each model, every request in ONE seeded stream runs a single
    eager forward to materialize the head's maps, then BOTH decoders
    consume those same maps: the serving path (the head's device tail +
    ``decode``) and the head's pure-NumPy ``reference_decode`` oracle.
    Comparing decodes of one map set gates the decode algorithms
    themselves — jit-vs-eager forward numerics stay out of it, which
    matters because random-init sigmoid scores cluster near the 0.5
    threshold where a float-reassociation wiggle flips pixels.  The
    gate is exact box-set equality per bucket (SystemExit on any
    mismatch), followed by a micro-batched serving smoke through the
    model's own compiled engines."""
    import jax.numpy as jnp

    from repro.data.images import RequestStream
    from repro.launch.serve import STDService, bucket_hw
    from repro.runtime.telemetry import CostBook

    if requests < 1:
        raise SystemExit("--requests must be >= 1")
    images = RequestStream(
        requests, seed=seed,
        hw_range=((48, max(buckets)), (48, max(buckets))),
    ).images()
    out = {}
    for name in models:
        svc = STDService(width=width, buckets=tuple(buckets),
                         max_batch=max_batch, max_wait_ms=max_wait_ms,
                         engine_cache_capacity=0,
                         book=CostBook(warmup=0), model=name)
        head = svc.head
        per_bucket: dict = {}
        for img in images:
            x, valid, tr = svc.preprocess(img)
            hw = tuple(x.shape[:2])
            model = svc.factory.model(hw, "f32", name)
            params = svc.factory.params(hw, "f32", name)
            maps = model.apply(params, jnp.asarray(x[None]))
            vq = jnp.asarray([[valid[0] // 4, valid[1] // 4]], jnp.int32)
            tail = head.tail(svc.factory, maps, vq)
            arrs = [np.asarray(a)[0] for a in tail[:head.n_payload]]
            payload = arrs[0] if head.n_payload == 1 else tuple(arrs)
            got, _ = head.decode(payload, valid)
            ref = head.reference_decode(
                {k: np.asarray(v[0]) for k, v in maps.items()
                 if k != "logits"},
                valid,
            )
            ok = (sorted(b["box"] for b in got)
                  == sorted(b["box"] for b in ref))
            bkt = bucket_hw(img.shape[0], img.shape[1], tuple(buckets))
            n_ok, n_all = per_bucket.get(bkt, (0, 0))
            per_bucket[bkt] = (n_ok + ok, n_all + 1)
        for bkt, (n_ok, n_all) in sorted(per_bucket.items()):
            if verbose:
                print(f"model_parity,model={name},"
                      f"bucket={bkt[0]}x{bkt[1]},"
                      f"boxes_equal={n_ok}/{n_all}")
            if n_ok != n_all:
                raise SystemExit(
                    f"model-zoo parity FAILED for {name!r} at bucket "
                    f"{bkt}: {n_all - n_ok}/{n_all} requests' serving "
                    f"decode diverges from the NumPy reference decode"
                )
        results = svc.serve_batched(images, pre_workers=pre_workers)
        out[name] = {
            "tps": svc.stats["batched_tps"],
            "boxes": [len(r) for r in results],
            "parity": {f"{b[0]}x{b[1]}": v for b, v in per_bucket.items()},
            "compiled": list(svc.factory.stats["compiled"]),
        }
        if verbose:
            print(f"model_zoo,model={name},"
                  f"tps {out[name]['tps']:.2f},"
                  f"boxes {sum(out[name]['boxes'])},"
                  f"engines {len(out[name]['compiled'])}")
    return out


def run_fleet_ab(policies, *, replicas: int = 2, requests: int = 16,
                 width: float = 0.25, buckets=(64,), max_batch: int = 4,
                 max_wait_ms: float = 8.0, seed: int = 0,
                 max_outstanding: int = 0, verbose: bool = True):
    """Replicated-serving A/B (docs/serving.md "Fleet"): ONE seeded
    request stream through ``replicas`` STDServices behind a
    launch/router.Router, once per routing policy — the ``--router``
    axis of the report.

    The fleet is built once and reused across policies (compiles are a
    one-time deployment cost; every engine the scheduler can form is
    warmed up front), so later policies also run on books the earlier
    passes populated — exactly the telemetry the p99 policy scores on.
    Requests alternate interactive/batch deadline classes; with
    ``max_outstanding`` 0 admission is unbounded (no sheds), a positive
    bound exercises the batch-sheds-first policy.  After each pass the
    router's online refit runs once against every replica's live book
    (each replica carries a single-device planner, so the fit swaps in
    without changing what ran)."""
    from repro.data.images import RequestStream
    from repro.launch.batching import LatencyRecorder, QueueFull, round_batch
    from repro.launch.mesh import make_host_mesh
    from repro.launch.router import POLICIES, Router, ServiceReplica
    from repro.launch.serve import STDService
    from repro.runtime.fault_tolerance import Watchdog
    from repro.runtime.planner import Planner
    from repro.runtime.telemetry import CostBook

    if requests < 1:
        raise SystemExit("--requests must be >= 1")
    if replicas < 1:
        raise SystemExit("--replicas must be >= 1")
    policies = list(dict.fromkeys(policies))      # dedupe, keep order
    for p in policies:
        if p not in POLICIES:
            raise SystemExit(f"unknown --router policy {p!r}; "
                             f"expected one of {POLICIES}")
    images = RequestStream(
        requests, seed=seed,
        hw_range=((48, max(buckets)), (48, max(buckets))),
    ).images()

    fleet = []
    for i in range(replicas):
        # a (1, 1) mesh keeps routing on single_device while still
        # giving the replica a planner for the online refit to update
        planner = Planner(make_host_mesh((1, 1), ("data", "model")))
        svc = STDService(width=width, buckets=tuple(buckets),
                         max_batch=max_batch, max_wait_ms=max_wait_ms,
                         engine_cache_capacity=0, book=CostBook(warmup=0),
                         planner=planner, measured_routing=False)
        # warm every pow2 (bucket, batch) engine the scheduler can form
        # (same reasoning as bench_open_loop: steady state is the
        # measurement)
        shapes = {svc.preprocess(img)[0].shape[:2] for img in images}
        sizes = {round_batch(n, max_batch)
                 for n in range(1, max_batch + 1)}
        for b in sorted(sizes):
            for hw in shapes:
                svc.infer_labels(
                    np.zeros((b, hw[0], hw[1], 3), np.float32),
                    [(hw[0], hw[1])] * b,
                )
        # health exclusion stays out of the on-host smoke: real-clock
        # jitter (GC, compile cache misses) must not bench one replica
        # out of a homogeneous fleet mid-measurement
        fleet.append(ServiceReplica(
            f"r{i}", svc,
            watchdog=Watchdog(threshold=float("inf"), ema=0.5,
                              warmup_steps=0)))

    out = {}
    for policy in policies:
        router = Router(fleet, policy=policy,
                        max_outstanding=max_outstanding)
        rec = LatencyRecorder()
        shed = 0
        with router:
            t0 = time.perf_counter()
            futs = []
            for i, img in enumerate(images):
                cls = "interactive" if i % 2 == 0 else "batch"
                try:
                    fut = router.submit(img, deadline_class=cls)
                except QueueFull:
                    shed += 1
                    continue
                futs.append(rec.track(fut, t0=time.perf_counter()))
            for f in futs:
                f.result(timeout=600)
            rec.wait()
            wall = time.perf_counter() - t0
            refit = router.refit_now()
        out[policy] = {
            "tps": len(futs) / wall if wall > 0 else 0.0,
            "p50_ms": _pctl(rec.samples, 50),
            "p99_ms": _pctl(rec.samples, 99),
            "placed": dict(router.stats["placed"]),
            "shed": dict(router.stats["shed"]),
            "submitted": dict(router.stats["submitted"]),
            "refit_replicas": sorted(refit),
        }
        if verbose:
            r = out[policy]
            placed = "/".join(f"{k}={v}"
                              for k, v in sorted(r["placed"].items()))
            print(f"fleet_ab,router={policy},replicas={replicas},"
                  f"tps {r['tps']:.2f},"
                  f"p50 {r['p50_ms']:.1f} ms,p99 {r['p99_ms']:.1f} ms,"
                  f"placed {placed},"
                  f"shed int={r['shed']['interactive']}"
                  f"/batch={r['shed']['batch']},"
                  f"refit={len(r['refit_replicas'])} replicas")
    if verbose:
        # the aggregated scrape: one flat surface for the whole fleet,
        # per-replica series disjoint via the book labels
        snap = Router(fleet, policy=policies[-1]).metrics_snapshot()
        per_replica = sum(1 for k in snap if 'replica="' in k)
        print(f"fleet_metrics,series={len(snap)},"
              f"replica_labelled={per_replica}")
    return out


def bench_serving(requests: int = 32, width: float = 0.25,
                  buckets=(64, 128), max_batch: int = 8,
                  max_wait_ms: float = 8.0, seed: int = 0,
                  pre_workers: int = 4, verbose: bool = True,
                  plan_kind: str = "single", mesh_shape=None,
                  inflight: int = 1, cost_params=None):
    """Returns {mode: {tps, p50_ms, p99_ms}} plus parity/batching info."""
    from repro.data.images import RequestStream
    from repro.launch.serve import STDService

    if requests < 1:
        raise SystemExit("--requests must be >= 1")
    extra_kw, planner, buckets = _plan_setup(
        plan_kind, mesh_shape, tuple(buckets), max_batch,
        cost_params=cost_params,
    )
    images = RequestStream(
        requests, seed=seed,
        hw_range=((48, max(buckets)), (48, max(buckets))),
    ).images()
    svc = STDService(width=width, buckets=tuple(buckets),
                     max_batch=max_batch, max_wait_ms=max_wait_ms,
                     engine_cache_capacity=0,      # hold every warm shape
                     inflight=inflight,
                     # benchmarks need REPRODUCIBLE routing: the live
                     # measured overlay would flip plans mid-measurement
                     # (compile stalls inside the timed phase).  The
                     # measured->fitted loop here is --calibrate +
                     # --cost-params instead.
                     measured_routing=False, **extra_kw)
    _check_band_units(svc, planner, plan_kind, buckets)

    results = {}

    # -- sequential: warm (compiles every (bucket, 1) engine), then time
    seq_boxes = [svc(img) for img in images]
    lat = []
    t0 = time.perf_counter()
    for img in images:
        t = time.perf_counter()
        svc(img)
        lat.append(time.perf_counter() - t)
    results["sequential"] = {
        "tps": requests / (time.perf_counter() - t0),
        "p50_ms": _pctl(lat, 50), "p99_ms": _pctl(lat, 99),
    }

    # -- pipelined: engines already warm; per-request latency is not
    # observable inside the 3-stage pipeline, report the stage-bound
    # approximation (wall / n is the throughput-side view)
    svc.serve_pipelined(images)                       # warm thread path
    t0 = time.perf_counter()
    pipe_boxes = svc.serve_pipelined(images)
    wall = time.perf_counter() - t0
    results["pipelined"] = {
        "tps": requests / wall,
        "p50_ms": wall / requests * 1e3, "p99_ms": wall / requests * 1e3,
    }

    # -- micro-batched: warm pass compiles the (bucket, batch) variants
    # the scheduler actually forms, timed pass measures steady state
    svc.serve_batched(images, pre_workers=pre_workers)
    t0 = time.perf_counter()
    batch_boxes = svc.serve_batched(images, pre_workers=pre_workers)
    wall = time.perf_counter() - t0
    lat = svc.stats["batched_latency_s"]
    results["batched"] = {
        "tps": requests / wall,
        "p50_ms": _pctl(lat, 50), "p99_ms": _pctl(lat, 99),
    }

    key = lambda rs: [[b["box"] for b in r] for r in rs]
    parity = (key(seq_boxes) == key(pipe_boxes) == key(batch_boxes))
    sizes = [b["n"] for b in svc.stats["batching"]["batches"]]
    info = {
        "parity": parity,
        "mean_batch": float(np.mean(sizes)) if sizes else 0.0,
        "flush_full": svc.stats["batching"]["flush_full"],
        "flush_timeout": svc.stats["batching"]["flush_timeout"],
    }
    if verbose:
        for mode, r in results.items():
            print(f"serve_{mode},{r['tps']:.2f} TPS,"
                  f"p50 {r['p50_ms']:.1f} ms,p99 {r['p99_ms']:.1f} ms")
        print(f"serve_info,parity={parity},mean_batch={info['mean_batch']:.2f},"
              f"flush_full={info['flush_full']},"
              f"flush_timeout={info['flush_timeout']}")
    info["plans"] = report_plan_choices(svc, planner, max_batch, verbose)
    return {"modes": results, **info}


def bench_open_loop(requests: int = 32, rates=(8.0, 32.0),
                    width: float = 0.25, buckets=(64, 128),
                    max_batch: int = 8, max_wait_ms: float = 8.0,
                    seed: int = 0, max_pending: int = 0,
                    admission: str = "block", verbose: bool = True,
                    plan_kind: str = "single", mesh_shape=None,
                    inflight_values=(2,), cost_params=None):
    """Open-loop (Poisson arrival) serving: offered load vs achieved TPS
    and p50/p99 latency, per offered rate and per async pipeline depth
    (``inflight_values``; the synchronous depth 0 is always swept as
    the overlap-gain baseline).  Returns {rate: {inflight: {...}}}."""
    from repro.data.images import RequestStream
    from repro.launch.batching import LatencyRecorder, QueueFull
    from repro.launch.serve import STDService

    extra_kw, planner, buckets = _plan_setup(
        plan_kind, mesh_shape, tuple(buckets), max_batch,
        cost_params=cost_params,
    )
    images = RequestStream(
        requests, seed=seed,
        hw_range=((48, max(buckets)), (48, max(buckets))),
    ).images()
    svc = STDService(width=width, buckets=tuple(buckets),
                     max_batch=max_batch, max_wait_ms=max_wait_ms,
                     engine_cache_capacity=0,
                     measured_routing=False,       # see bench_serving
                     **extra_kw)
    _check_band_units(svc, planner, plan_kind, buckets)
    # warm every pow2 (bucket, batch) engine the open-loop phase can form
    # (at low offered rates batches trickle in as 1s and 2s, sizes the
    # closed-loop pass never compiles) — steady state is the measurement
    from repro.launch.batching import round_batch

    shapes = {svc.preprocess(img)[0].shape[:2] for img in images}
    sizes = {round_batch(n, max_batch) for n in range(1, max_batch + 1)}
    for b in sorted(sizes):
        for hw in shapes:
            svc.infer_labels(
                np.zeros((b, hw[0], hw[1], 3), np.float32),
                [(hw[0], hw[1])] * b,
            )
    # admission control applies to the measured open-loop phase only (the
    # warm pass must compile every shape, not shed)
    svc.max_pending = max_pending
    svc.admission = admission

    # depth 0 (fully serialized dispatch->completion) is the overlap
    # baseline every async depth is reported against
    depths = sorted({0, *(int(n) for n in inflight_values)})

    results = {}
    for rate in rates:
        per_depth = {}
        for n in depths:
            rng = np.random.default_rng(seed)
            arrivals = np.cumsum(rng.exponential(1.0 / rate, size=requests))
            svc.inflight = n             # next start_batched picks it up
            svc.start_batched()
            rec = LatencyRecorder()
            futs, shed = [], 0
            t0 = time.perf_counter()
            try:
                for img, due in zip(images, arrivals):
                    now = time.perf_counter() - t0
                    if due > now:
                        time.sleep(due - now)
                    t = time.perf_counter()
                    try:
                        fut = svc.submit(img)
                    except QueueFull:
                        shed += 1
                        continue
                    futs.append(rec.track(fut, t0=t))
                for f in futs:
                    f.result(timeout=600)
                # event-driven: every sample has landed once this returns
                rec.wait()
            finally:
                svc.stop_batched()
            wall = time.perf_counter() - t0
            mb = svc.stats["batching"]
            per_depth[n] = {
                "offered_tps": rate,
                "inflight": n,
                "achieved_tps": len(futs) / wall,
                "completed": len(futs),
                "shed": shed,
                "p50_ms": _pctl(rec.samples, 50),
                "p99_ms": _pctl(rec.samples, 99),
                "inflight_peak": mb["inflight_peak"],
                "stage_occupancy": mb["stage_occupancy"],
            }
        base_tps = per_depth[0]["achieved_tps"]
        for n in depths:
            r = per_depth[n]
            r["overlap_gain"] = (r["achieved_tps"] / base_tps
                                 if base_tps > 0 else 0.0)
            if verbose:
                occ = r["stage_occupancy"]
                print(f"serve_open_loop,offered {rate:.1f} rps,"
                      f"inflight {n},"
                      f"achieved {r['achieved_tps']:.2f} TPS,"
                      f"p50 {r['p50_ms']:.1f} ms,"
                      f"p99 {r['p99_ms']:.1f} ms,"
                      f"shed {r['shed']},"
                      f"gain x{r['overlap_gain']:.2f},"
                      f"occ d{occ.get('dispatch', 0.0):.2f}"
                      f"/c{occ.get('complete', 0.0):.2f}")
        results[rate] = per_depth
    results["plans"] = report_plan_choices(svc, planner, max_batch, verbose)
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--width", type=float, default=0.25)
    ap.add_argument("--buckets", type=int, nargs="+", default=[64, 128])
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pre-workers", type=int, default=4)
    ap.add_argument("--open-loop", action="store_true",
                    help="also run Poisson-arrival open-loop sweeps")
    ap.add_argument("--rates", type=float, nargs="+", default=[8.0, 32.0],
                    help="offered open-loop rates, requests/s")
    ap.add_argument("--max-pending", type=int, default=0,
                    help="admission-control queue bound (0 = unbounded)")
    ap.add_argument("--inflight", type=int, nargs="+", default=[2],
                    help="async pipeline depths to sweep in open-loop "
                         "mode (0 = fully synchronous dispatch, always "
                         "included as the overlap-gain baseline); the "
                         "closed-loop pass runs at max(inflight)")
    ap.add_argument("--admission", default="block",
                    choices=["block", "reject"])
    ap.add_argument("--plan", default="single",
                    choices=["single", "data", "rowband", "grid", "auto"],
                    help="ExecutionPlan: fixed single/data/rowband/grid, "
                         "or auto (cost-model routing per bucket)")
    ap.add_argument("--mesh-shape", type=int, nargs=2, default=None,
                    metavar=("DATA", "MODEL"),
                    help="host mesh (data, model) axis sizes; default "
                         "derives from the visible device count")
    ap.add_argument("--calibrate", metavar="OUT_JSON", default=None,
                    help="run the calibration sweep ONLY: time every "
                         "eligible (bucket, plan, batch) combo, "
                         "least-squares fit the CostParams constants, "
                         "save them to OUT_JSON, and exit")
    ap.add_argument("--calib-steps", type=int, default=3,
                    help="timed steps per (bucket, plan, batch) combo "
                         "in --calibrate mode (one extra warmup call "
                         "absorbs the compile)")
    ap.add_argument("--cost-params", metavar="IN_JSON", default=None,
                    help="load fitted CostParams from a --calibrate "
                         "file; the planner (--plan auto and the "
                         "serve_plan report) routes on them instead of "
                         "the napkin defaults")
    ap.add_argument("--precision", default="f32",
                    choices=["f32", "bfp"],
                    help="'bfp' runs the precision A/B sweep ONLY: "
                         "f32-vs-bfp blocked step walls per (bucket, "
                         "batch) from the CostBook, gated by the "
                         "accuracy-parity check on every bucket")
    ap.add_argument("--postprocess", default="host",
                    choices=["host", "device"],
                    help="'device' runs the postprocess A/B sweep ONLY: "
                         "host vs device serving tail on one stream, "
                         "gated on exact box parity per bucket and on a "
                         "measured postprocess-wall reduction")
    ap.add_argument("--boxes-capacity", type=int, default=256,
                    help="device-postprocess compact-rows capacity "
                         "(components past it fall back to the host "
                         "path per image)")
    ap.add_argument("--replicas", type=int, default=0,
                    help="run the fleet A/B sweep ONLY: N replicated "
                         "services behind launch/router.Router, one "
                         "seeded stream per --router policy; "
                         "--max-pending bounds router admission "
                         "(0 = unbounded)")
    ap.add_argument("--router", nargs="+",
                    default=["round_robin", "p99"],
                    choices=["round_robin", "p99", "least_loaded"],
                    help="routing policies the fleet A/B sweeps (the "
                         "--router axis of the report)")
    ap.add_argument("--model", nargs="+", default=None,
                    choices=["pixellink", "east", "db"],
                    help="run the model-zoo sweep ONLY: for each named "
                         "detection head, gate its serving decode "
                         "against the NumPy reference decode on one "
                         "seeded stream (exact box parity per bucket), "
                         "then smoke-serve the stream through its "
                         "compiled engines")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    if args.replicas:
        return run_fleet_ab(args.router,
                            replicas=args.replicas,
                            requests=args.requests,
                            width=args.width,
                            buckets=tuple(args.buckets),
                            max_batch=args.max_batch,
                            max_wait_ms=args.max_wait_ms,
                            seed=args.seed,
                            max_outstanding=args.max_pending)
    if args.model:
        return run_model_zoo(args.model,
                             requests=args.requests,
                             width=args.width,
                             buckets=tuple(args.buckets),
                             max_batch=args.max_batch,
                             max_wait_ms=args.max_wait_ms,
                             seed=args.seed,
                             pre_workers=args.pre_workers)
    if args.postprocess == "device":
        return run_postprocess_ab(requests=args.requests,
                                  width=args.width,
                                  buckets=tuple(args.buckets),
                                  max_batch=args.max_batch,
                                  max_wait_ms=args.max_wait_ms,
                                  seed=args.seed,
                                  boxes_capacity=args.boxes_capacity,
                                  pre_workers=args.pre_workers)
    if args.precision == "bfp":
        return run_precision_ab(width=args.width,
                                buckets=tuple(args.buckets),
                                max_batch=args.max_batch,
                                steps=args.calib_steps,
                                seed=args.seed)
    if args.calibrate:
        run_calibration(args.calibrate, width=args.width,
                        buckets=tuple(args.buckets),
                        max_batch=args.max_batch,
                        mesh_shape=args.mesh_shape,
                        steps=args.calib_steps)
        return None
    out = bench_serving(args.requests, args.width, tuple(args.buckets),
                        args.max_batch, args.max_wait_ms, args.seed,
                        args.pre_workers, plan_kind=args.plan,
                        mesh_shape=args.mesh_shape,
                        inflight=max(args.inflight),
                        cost_params=args.cost_params)
    if args.plan == "auto":
        # routing is batch-dependent, so sequential (batch 1) and
        # micro-batched modes may legitimately run DIFFERENT plans for
        # one bucket; banded vs single engines can differ by ~1e-6
        # Winograd tile-regrouping noise, enough to flip a box at an
        # unlucky 0.5-threshold score — report instead of failing
        if not out["parity"]:
            print("serve_warn,auto-mode modes routed to different plans; "
                  "box parity not guaranteed bit-exact")
    else:
        assert out["parity"], \
            "batched/pipelined boxes diverged from sequential"
    if args.open_loop:
        out["open_loop"] = bench_open_loop(
            args.requests, tuple(args.rates), args.width,
            tuple(args.buckets), args.max_batch, args.max_wait_ms,
            args.seed, args.max_pending, args.admission,
            plan_kind=args.plan, mesh_shape=args.mesh_shape,
            inflight_values=tuple(args.inflight),
            cost_params=args.cost_params,
        )
    return out


if __name__ == "__main__":
    main()
