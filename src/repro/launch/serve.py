"""STD serving driver — the paper's deployment shape (Fig. 2/9): batched
scene-text-detection requests through the microcode FCN engine, with the
paper's throughput tricks:

  * random-size inputs bucketed to a few compiled shapes (§IV.B analogue
    of row-wise segmentation; the transpose trick applied verbatim for
    over-wide images),
  * dynamic micro-batching: an async request queue groups images by
    resolution bucket and runs one compiled batched engine per bucket
    (launch/batching.py), flushing on ``max_batch`` or ``max_wait_ms``,
    with optional bounded-queue admission control (reject/block),
  * module-level pipelining (C4): host preprocess / device FCN / host
    CC-postprocess overlap as pipeline stages, so stage i of image n
    overlaps stage i+1 of image n-1,
  * async pipelined dispatch: the micro-batcher's infer path is split
    into a dispatch stage (submits device work without blocking — JAX
    async dispatch) and a completion stage (blocks on D2H), with a
    bounded ``inflight`` queue between them, so H2D/compute/D2H of
    batches from different buckets overlap (docs/serving.md),
  * engine compilation delegated to the ExecutionPlan layer
    (runtime/executor.py): one EngineFactory holds the models, params,
    and a (bucket, batch, plan)-keyed LRU; the service just picks a plan
    — SingleDevice by default, DataParallel over a mesh's "data" axis,
    the §IV.B RowBand plan for over-tall images that exceed the largest
    bucket, or the composed GridPlan (batch over "data" AND rows over
    "model" at once),
  * plan routing: either the fixed rules (service-wide ``plan`` +
    ``tall_plan`` for over-tall images) or a cost model
    (runtime/planner.py ``Planner``) that picks a plan PER BUCKET from
    FLOPs + halo bytes + batch-split occupancy — heterogeneous buckets
    in one service then route to different plans through the same
    engine LRU,
  * device-side postprocess (``postprocess="device"``): the CC tail
    already runs on device; this mode also compacts each label map into
    a fixed-capacity ``(capacity + 1, 6)`` boxes tensor on device
    (EngineFactory.boxes_fn), so the completion stage materializes a
    few hundred bytes per image instead of the full plane and the host
    tail is a trivial O(capacity) decode — per-image walls land in the
    CostBook under ``stage="postprocess"`` for both modes, and images
    whose component count overflows the capacity fall back to the host
    path (counted, never wrong),
  * measured-cost telemetry: every layer writes into one
    runtime/telemetry.CostBook (engine dispatch walls, full
    dispatch-through-D2H step walls, scheduler stage timings and queue
    gauges); with a planner configured the measured step EWMAs overlay
    the analytic cost model (``MeasuredCost``), so routing adapts
    online to what steps actually cost, and
    ``metrics_snapshot()`` / ``metrics_prometheus()`` export the lot
    in a flat scrapeable form for autoscalers,
  * TPS + latency accounting (feeds the Fig. 9a benchmark).

  PYTHONPATH=src python -m repro.launch.serve --requests 32 --width 0.25
"""
from __future__ import annotations

import argparse
import dataclasses
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.launch.batching import LatencyRecorder, MicroBatcher, round_batch
from repro.runtime.executor import (
    EngineFactory,
    ExecutionPlan,
    SingleDevice,
    band_height_unit,
    check_precision,
    describe_plan,
    plan_batch_multiple,
    plan_kind,
)
from repro.runtime.pipeline import HostPipeline
from repro.runtime.planner import Planner, features_for_program
from repro.runtime.telemetry import CostBook, prometheus_text

MAX_WIDTH = 4096          # the paper's width limit


def bucket_hw(h: int, w: int, buckets: Tuple[int, ...]) -> Tuple[int, int]:
    """Padded bucket shape for an (h, w) image.  Oversize dimensions
    round up to the next multiple of the largest bucket instead of
    raising, so the compiled-shape count stays bounded and over-tall
    inputs can route to the row-band plan.  Dimensions beyond the
    paper's MAX_WIDTH limit fail fast — a single huge request must not
    stall the infer thread with an unbounded compile/allocation."""
    top = max(buckets)

    def one(v: int) -> int:
        if v <= top:
            return min(b for b in buckets if b >= v)
        if v > MAX_WIDTH:
            raise ValueError(
                f"image dimension {v} exceeds the serving limit "
                f"{MAX_WIDTH} (paper §IV.B width bound)"
            )
        return -(-v // top) * top

    return one(h), one(w)


class STDService:
    """Bucketed STD serving on top of the ExecutionPlan layer: plan
    selection + request scheduling here, all engine compilation in
    runtime.executor.EngineFactory (sequential / pipelined /
    micro-batched serving modes)."""

    def __init__(self, width: float = 0.25, mode: str = "optimized",
                 buckets: Tuple[int, ...] = (64, 128, 256),
                 score_thr: float = 0.5, link_thr: float = 0.5,
                 max_batch: int = 8, max_wait_ms: float = 5.0,
                 batch_round: str = "pow2",
                 engine_cache_capacity: int = 16,
                 plan: Optional[ExecutionPlan] = None,
                 tall_plan: Optional[ExecutionPlan] = None,
                 planner: Optional[Planner] = None,
                 max_pending: int = 0, admission: str = "block",
                 inflight: int = 1,
                 book: Optional[CostBook] = None,
                 measured_routing: bool = True,
                 precision: str = "f32",
                 postprocess: str = "host",
                 boxes_capacity: int = 256,
                 model: str = "pixellink",
                 memplan: bool = True,
                 activation_budget_bytes: Optional[int] = None,
                 engine_cache_bytes: int = 0,
                 config: Optional[Any] = None,
                 device: Optional[Any] = None):
        """``config`` is the base ``STDConfig`` every bucket's model is
        built from (``dataclasses.replace`` sets the plane and the
        precision axis); without one the base is the reduced VGG-16
        serving model at ``width``/``mode``/``memplan``, which a config
        carries itself (so they are refused beside one).  ``device`` pins
        the SingleDevice engines and their parameters to one device, and
        with them each batch's inputs (one replica per chip behind
        launch/router.Router)."""
        from repro.core import BFPConfig
        from repro.models.fcn.heads import (
            DetectionModel, build_head, check_model,
        )
        from repro.models.fcn.pixellink import STDConfig

        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if config is not None and (width, mode, memplan) != (
                0.25, "optimized", True):
            raise ValueError("width/mode/memplan come from config when one "
                             "is given; set them on the STDConfig")
        if postprocess not in ("host", "device"):
            raise ValueError(
                f"postprocess must be 'host' or 'device', got {postprocess!r}"
            )
        if boxes_capacity < 1:
            raise ValueError("boxes_capacity must be >= 1")
        # which detection head this service routes requests to — every
        # cache, plan feature, and telemetry series keys on it
        self.model_name = check_model(model)
        self.head = build_head(model, score_thr=score_thr,
                               link_thr=link_thr)
        if postprocess == "device" and \
                not self.head.supports_device_postprocess:
            raise ValueError(
                f"model {model!r} has no label-map payload, so the "
                f"device-compact box tail does not apply; use "
                f"postprocess='host'"
            )
        # "device" compacts boxes on device (EngineFactory.boxes_fn);
        # named _mode because postprocess() is the stage method
        self.postprocess_mode = postprocess
        self.boxes_capacity = boxes_capacity
        self.precision = check_precision(precision)
        self.plan: ExecutionPlan = plan if plan is not None else SingleDevice()
        self.planner = planner
        if device is not None and (
                not isinstance(self.plan, SingleDevice)
                or tall_plan is not None or planner is not None):
            raise ValueError("device pins SingleDevice engines only; a "
                             "mesh plan places its own shards")
        m = plan_batch_multiple(self.plan)
        if tall_plan is not None:
            # tall_plan may be any plan type now, including data-sharded
            # ones whose padded batches must also stay within max_batch
            m = max(m, plan_batch_multiple(tall_plan))
        if planner is not None:
            # the planner may route any bucket to a data-parallel or grid
            # plan, whose padded batches must stay within max_batch
            m = max(m, planner.data_n)
        if max_batch % m:
            raise ValueError(
                f"max_batch={max_batch} must be a multiple of the plan's "
                f"data-parallel width {m}, or padded batches would exceed "
                f"the configured maximum"
            )
        self.buckets = buckets
        self.max_batch = max_batch
        self._batch_multiple = m
        # memory-aware batching (core.memplan): with a budget configured,
        # each bucket's flush size is capped by how many planned
        # activation footprints fit — a memory-heavy bucket compiles its
        # engines at a SMALLER batch (lower temp bytes), a light bucket
        # may batch above the fixed max_batch.  None = fixed max_batch.
        base = config if config is not None else STDConfig(
            backbone="vgg16", width=width, merge_ch=(16, 16, 8),
            mode=mode, memplan=memplan,
        )
        self.config = base
        self.memplan_enabled = bool(base.memplan)
        self.activation_budget_bytes = activation_budget_bytes
        self._bucket_caps: Dict[Tuple[int, int], int] = {}
        self.max_wait_ms = max_wait_ms
        self.batch_round = batch_round
        self.tall_plan = tall_plan
        self.max_pending = max_pending
        self.admission = admission
        if inflight < 0:
            raise ValueError("inflight must be >= 0")
        self.inflight = inflight
        self._lock = threading.Lock()
        self._batcher: Optional[MicroBatcher] = None
        self._mode = base.mode
        # the telemetry book every layer writes into: engine dispatch
        # walls (EngineFactory), full step walls (this service's
        # completion path), scheduler stage timings/gauges
        # (MicroBatcher) — metrics_snapshot() exports it all
        self.book = book if book is not None else CostBook()

        def make_model(hw, precision="f32", model="pixellink"):
            # "bfp" runs the paper's quantized datapath: BFP convs with
            # FP16 data-pool storage, and the Pallas kernels on TPU (they
            # lower through Mosaic only; interpret-mode Pallas elsewhere
            # would be orders of magnitude slower than XLA, so it stays
            # off in serving — the kernels themselves are covered by
            # tests).  The model arg selects the detection head; one
            # factory can serve several zoo models through the same LRU.
            bfp = precision == "bfp"
            return DetectionModel(dataclasses.replace(
                base, image_size=tuple(hw),
                bfp=(base.bfp or BFPConfig()) if bfp else None,
                storage_fp16=bfp,
                use_pallas=bfp and jax.default_backend() == "tpu",
            ), build_head(model, score_thr=score_thr,
                          link_thr=link_thr))

        self.factory = EngineFactory(
            make_model,
            score_thr=score_thr, link_thr=link_thr,
            capacity=engine_cache_capacity,
            book=self.book,
            engine_bytes_budget=engine_cache_bytes,
            device=device,
        )
        if planner is not None:
            planner.bind_features(self._plan_features,
                                  model=self.model_name)
            if measured_routing:
                # overlay measured step EWMAs over the analytic model:
                # combos the service has actually run route by what they
                # actually cost, through the same engine LRU — reading
                # this service's precision's AND model's step series
                planner.use_measurements(self.book,
                                         precision=self.precision,
                                         model=self.model_name)
        self.stats: Dict[str, Any] = {"n": 0, "latency_s": [],
                                      "transposed": 0, "plan_choices": {},
                                      "nonconverged": 0, "pp_overflow": 0}

    @property
    def _engines(self):
        """The factory's compiled-engine LRU (tests/introspection)."""
        return self.factory.engines

    def _plan_features(self, hw: Tuple[int, int]):
        """Cost-model features for one bucket, from the same assembled
        program the engine will run (planner wiring) — this service's
        OWN model's microcode, so per-model plan features differ."""
        model = self.factory.model(tuple(hw), self.precision,
                                   self.model_name)
        return features_for_program(
            model.program,
            self.factory.deepest_stride(tuple(hw), self.precision,
                                        self.model_name),
            mode=self._mode,
        )

    def _bucket_cap(self, hw: Tuple[int, int]) -> int:
        """Effective max batch for one bucket.  With an activation
        budget configured, the cap is how many planned per-image
        footprints (core.memplan peak bytes) fit, rounded to the plan
        batch multiple; without one it is the fixed max_batch.  Cached —
        MicroBatcher calls this under its scheduler lock."""
        if self.activation_budget_bytes is None or not self.memplan_enabled:
            return self.max_batch
        hw = tuple(hw)
        cap = self._bucket_caps.get(hw)
        if cap is None:
            from repro.core.memplan import admissible_batch

            per_image = self.factory.memplan(
                hw, self.precision, self.model_name).peak_bytes
            cap = admissible_batch(per_image, self.activation_budget_bytes,
                                   multiple=self._batch_multiple)
            self._bucket_caps[hw] = cap
        return cap

    def _plan_for(self, hw: Tuple[int, int], batch: int = 1) -> ExecutionPlan:
        """Plan routing.  With a cost-model planner configured, every
        bucket is routed by estimated step cost — over-tall shapes
        (taller than the largest bucket) are restricted to the
        row-banded kinds (RowBand/GridPlan), matching the §IV.B rule.
        Without one, the fixed rules apply: over-tall shapes go to
        ``tall_plan`` when configured, everything else to the service
        default."""
        over_tall = hw[0] > max(self.buckets)
        if self.planner is not None:
            plan = self.planner.choose(hw, batch, force_banded=over_tall,
                                       model=self.model_name)
            # routing runs on the dispatch thread while callers read
            # stats — every stats mutation holds _lock
            with self._lock:
                self.stats["plan_choices"][tuple(hw)] = describe_plan(plan)
            return plan
        if self.tall_plan is not None and over_tall:
            return self.tall_plan
        return self.plan

    def _routes_banded(self) -> bool:
        """Whether over-tall/over-wide images can ride a row-banded plan
        (fixed tall_plan rule or planner routing)."""
        return self.tall_plan is not None or self.planner is not None

    def _tall_height(self, bh: int) -> int:
        """Padded height for an over-tall image headed to a row-banded
        plan: rounded up so every band divides evenly through the stride
        pyramid (bands x deepest cumulative stride) — without this,
        clamped heights like 192 on an 8-band mesh would be rejected by
        the plan compiler."""
        top = max(self.buckets)
        deepest = self.factory.deepest_stride((top, top), self.precision,
                                              self.model_name)
        if self.planner is not None:
            unit = self.planner.height_unit(deepest)
        else:
            unit = band_height_unit(self.tall_plan, deepest)
        return -(-bh // unit) * unit

    # -- stages ---------------------------------------------------------------
    def preprocess(self, img: np.ndarray):
        """Random-size handling: transpose trick + bucket padding."""
        h, w = img.shape[:2]
        transposed = False
        # paper §IV.B over-wide rule; with banded routing configured
        # (fixed tall_plan or cost-model planner) the same trick also
        # turns any over-wide image into an over-tall one so it rides a
        # row-banded plan instead of a one-off monolithic engine at a
        # clamped width
        if w > MAX_WIDTH >= h or (
            self._routes_banded() and w > max(self.buckets) >= h
        ):
            img = np.transpose(img, (1, 0, 2))
            h, w = w, h
            transposed = True
            with self._lock:
                self.stats["transposed"] += 1
        bh, bw = bucket_hw(h, w, self.buckets)
        if self._routes_banded() and bh > max(self.buckets):
            bh = self._tall_height(bh)
        pad = np.zeros((bh, bw, 3), np.float32)
        pad[:h, :w] = img
        return pad, (h, w), transposed

    def _dispatch(self, stack: np.ndarray,
                  valid_hws: List[Tuple[int, int]]):
        """Route + pad + submit one batch; returns the pending device
        tuple — the head's ``(*payload, converged)`` on the
        host-postprocess path (``(labels, converged)`` for the CC
        heads), with the compact on-device ``(rows, counts)`` boxes
        appended on the device path — and the step-telemetry meta
        ``(hw, batch, kind, t0)`` the completion path hands to
        :meth:`_record_step`.  Nothing here blocks: the boxes fn is a
        jitted call on the pending labels, so it joins the same async
        dispatch chain."""
        hw = tuple(stack.shape[1:3])
        n_live = len(valid_hws)
        b = round_batch(n_live, self._bucket_cap(hw), self.batch_round)
        plan = self._plan_for(hw, b)
        m = plan_batch_multiple(plan)            # data-parallel divisibility
        b = -(-b // m) * m
        if b > n_live:
            stack = np.concatenate(
                [stack, np.zeros((b - n_live,) + stack.shape[1:],
                                 stack.dtype)]
            )
        valid_q = np.zeros((b, 2), np.int32)
        for i, (vh, vw) in enumerate(valid_hws):
            valid_q[i] = (vh // 4, vw // 4)
        fn = self.factory.plan_fn(hw, b, plan, self.precision,
                                  self.model_name)
        params = self.factory.params(hw, self.precision, self.model_name)
        t0 = time.perf_counter()
        # host arrays go straight to the engine's input shardings (a
        # mesh plan's shards, or the device the committed params sit on);
        # a default-device copy would be resharded by an extra compiled
        # slice program on the first call
        pending = fn(params, stack, valid_q)
        if self.postprocess_mode == "device":
            # labels are already valid-masked, so padding contributes no
            # components; coordinates live in label-map (quarter) space
            # (single-label-map heads only — enforced at construction)
            rows, counts = self.factory.boxes_fn(
                hw, b, self.boxes_capacity)(pending[0])
            pending = (*pending, rows, counts)
        return pending, (hw, b, plan_kind(plan), t0)

    def _record_step(self, meta) -> None:
        """One materialized batch's dispatch-through-D2H wall into the
        book — the ``stage="step"`` series MeasuredCost routes by.
        This is the DEPLOYMENT wall: on the async path (inflight > 0)
        it includes time queued behind earlier batches' finalize work,
        which is plan-independent load, roughly uniform across
        whichever plan runs — so steady-state measured-vs-measured
        comparisons stay fair, but measured-vs-analytic ones are biased
        under load (see "Calibrated routing" in docs/plans.md)."""
        hw, b, kind, t0 = meta
        self.book.record_step(hw, b, kind, time.perf_counter() - t0,
                              precision=self.precision,
                              model=self.model_name)

    def dispatch_labels(self, stack: np.ndarray,
                        valid_hws: List[Tuple[int, int]]):
        """(B, bh, bw, 3) padded batch -> pending device tuple —
        ``(labels, converged)`` label maps (B, bh/4, bw/4) int32 plus
        the per-image convergence flags, with the compact
        ``(rows, counts)`` boxes appended on the device-postprocess
        path.  NON-blocking: the returned arrays are un-materialized
        (JAX async dispatch), so the caller can submit the next bucket's
        batch while this one's H2D/compute/D2H run.  Materialize with
        ``np.asarray`` (the completion stage's job).

        The batch axis may be padded past ``len(valid_hws)`` (batch-size
        rounding); trailing slots are zero images whose outputs are
        discarded by the caller.
        """
        return self._dispatch(stack, valid_hws)[0]

    def infer_labels(self, stack: np.ndarray,
                     valid_hws: List[Tuple[int, int]]) -> np.ndarray:
        """Blocking dispatch + materialized LABEL MAPS (the synchronous
        path; benchmarks' warm loops key on this full-plane D2H)."""
        pending, meta = self._dispatch(stack, valid_hws)
        labels = np.asarray(pending[0])
        self._record_step(meta)
        self._count_nonconverged(np.asarray(pending[1]))
        return labels

    def _count_nonconverged(self, converged) -> None:
        """Count label maps that hit max_iters still changing — the
        silently-unconverged case the CC tail used to swallow.  Padded
        batch slots are all-zero images that converge in one round, so
        counting the full padded batch is exact."""
        k = int(np.size(converged) - np.count_nonzero(converged))
        if k:
            with self._lock:
                self.stats["nonconverged"] += k
            self.book.incr("pp_nonconverged", k)

    def _finalize(self, raw):
        """Materialize one dispatched batch into per-item postprocess
        payloads: a ``(rows, count)`` compact-box tuple per image on the
        device path (falling back to the full label map when the
        component count overflows ``boxes_capacity`` — counted, never
        wrong), or the head's per-image payload on the host path (the
        label map for the CC heads, a tuple of maps for multi-payload
        heads like EAST).  Records the ``stage="step"`` wall and the
        non-convergence counter."""
        pending, meta = raw
        n_payload = self.head.n_payload
        if len(pending) == n_payload + 3:       # device (rows, counts)
            labels, converged, rows, counts = pending
            rows = np.asarray(rows)                  # compact D2H payload
            counts = np.asarray(counts)
            self._record_step(meta)
            self._count_nonconverged(np.asarray(converged))
            out: List[Any] = []
            for i in range(rows.shape[0]):
                if counts[i] > self.boxes_capacity:
                    with self._lock:
                        self.stats["pp_overflow"] += 1
                    self.book.incr("pp_overflow")
                    out.append(np.asarray(labels[i]))
                else:
                    out.append((rows[i], int(counts[i])))
            return out
        arrs = [np.asarray(a) for a in pending[:n_payload]]
        self._record_step(meta)
        self._count_nonconverged(np.asarray(pending[n_payload]))
        if n_payload == 1:
            return [arrs[0][i] for i in range(arrs[0].shape[0])]
        return [tuple(a[i] for a in arrs)
                for i in range(arrs[0].shape[0])]

    def postprocess(self, payload, valid_hw: Tuple[int, int],
                    transposed: bool,
                    bucket_hw: Optional[Tuple[int, int]] = None
                    ) -> List[Dict]:
        """One image's inference payload -> boxes (the serving tail).

        The head owns the decode (models/fcn/heads.py): the CC heads
        type-dispatch device-compact ``(rows, count)`` tuples vs label
        maps, EAST runs its geometry decode + NMS.  The per-image wall
        lands in the CostBook under ``stage="postprocess"`` keyed by
        the bucket shape and the head's decode kind (derived from the
        payload plane when ``bucket_hw`` isn't given — device-compact
        rows carry no plane, so they require it)."""
        t0 = time.perf_counter()
        boxes, kind = self.head.decode(payload, valid_hw)
        if bucket_hw is None:
            plane = self.head.payload_plane(payload)
            if plane is None:
                raise ValueError(
                    "device-compact payloads carry no plane shape; pass "
                    "bucket_hw"
                )
            bucket_hw = (plane[0] * 4, plane[1] * 4)
        self.book.record_step(tuple(bucket_hw), 1, kind,
                              time.perf_counter() - t0,
                              stage="postprocess",
                              model=self.model_name)
        if transposed:                              # inverse transposition
            for b in boxes:
                x0, y0, x1, y1 = b["box"]
                b["box"] = (y0, x0, y1, x1)
        return boxes

    def _record_request(self, dt: float) -> None:
        """One finished request's accounting (any thread may call)."""
        with self._lock:
            self.stats["n"] += 1
            self.stats["latency_s"].append(dt)

    # -- scrapeable metrics (ROADMAP plan-aware autoscaling signals) -----------
    def metrics_snapshot(self) -> Dict[str, float]:
        """Everything an autoscaler needs, flat ``{metric_name: value}``
        (labels embedded Prometheus-style, so the dict stays flat):
        request counts and latency percentiles, the live per-bucket
        plan choices, scheduler queue depth / shed rate / batch
        occupancy / stage busy times (live batcher if running, else the
        last stopped one), and the full telemetry book — measured step
        EWMAs/percentiles per (bucket, batch, plan) plus scheduler
        series.  Field meanings are documented in docs/serving.md.
        Safe to call from any thread at any time."""
        out: Dict[str, float] = {}
        with self._lock:
            n = self.stats["n"]
            lat = list(self.stats["latency_s"])
            transposed = self.stats["transposed"]
            choices = dict(self.stats["plan_choices"])
            mb_snap = self.stats.get("batching_snapshot")
            batcher = self._batcher
        out["std_requests_total"] = float(n)
        out["std_transposed_total"] = float(transposed)
        if lat:
            out["std_request_latency_p50_ms"] = float(
                np.percentile(lat, 50) * 1e3)
            out["std_request_latency_p99_ms"] = float(
                np.percentile(lat, 99) * 1e3)
        for hw, desc in sorted(choices.items()):
            out[f'std_plan_choice{{bucket="{hw[0]}x{hw[1]}",'
                f'plan="{desc}"}}'] = 1.0
        if batcher is not None:             # live scrape beats the last stop
            mb_snap = batcher.stats_snapshot()
        for k, v in (mb_snap or {}).items():
            out[f"std_mb_{k}"] = float(v)
        # per-(bucket,batch,plan,model) engine memory gauges — planned
        # peak always; measured temp/peak for shapes a bench ran
        # measure_engine_memory() on (launch/hlo_analysis buffer sizes)
        for row in list(self.factory.stats.get("engine_memory", [])):
            lbl = (f'bucket="{row["hw"][0]}x{row["hw"][1]}",'
                   f'batch="{row["batch"]}",plan="{row["plan"]}",'
                   f'model="{row["model"]}"')
            out[f"std_engine_planned_peak_bytes{{{lbl}}}"] = float(
                row.get("planned_peak_bytes", 0))
            out[f"std_engine_temp_bytes{{{lbl}}}"] = float(row["temp_bytes"])
            out[f"std_engine_peak_bytes{{{lbl}}}"] = float(row["peak_bytes"])
        for hw, cap in sorted(self._bucket_caps.items()):
            out[f'std_bucket_batch_cap{{bucket="{hw[0]}x{hw[1]}"}}'] = \
                float(cap)
        out.update(self.book.snapshot())
        return out

    def measure_engine_memory(self, hw: Tuple[int, int],
                              batch: Optional[int] = None) -> Dict[str, Any]:
        """AOT-measure one bucket engine's buffer assignment at ``batch``
        (default: this bucket's effective cap) under the plan routing
        would pick — results land in ``stats["engine_memory"]`` and the
        ``std_engine_*_bytes`` gauges.  Explicit opt-in: one extra
        compile per shape."""
        hw = tuple(hw)
        b = int(batch) if batch is not None else self._bucket_cap(hw)
        m = self._batch_multiple
        b = -(-b // m) * m
        plan = self._plan_for(hw, b)
        return self.factory.measure_engine_memory(
            hw, b, plan, self.precision, self.model_name)

    def metrics_prometheus(self) -> str:
        """:meth:`metrics_snapshot` in Prometheus text-exposition form."""
        return prometheus_text(self.metrics_snapshot())

    def queue_gauges(self) -> Dict[str, float]:
        """Live scheduler load — queued requests and in-flight batches
        (zeros when the batcher is not running).  The cheap subset of
        :meth:`metrics_snapshot` a router polls per placement decision
        (launch/router.py scores replicas with it)."""
        batcher = self._batcher
        if batcher is None:
            return {"queue_depth": 0.0, "inflight": 0.0}
        snap = batcher.stats_snapshot()
        return {"queue_depth": snap.get("queue_depth", 0.0),
                "inflight": snap.get("inflight", 0.0)}

    def __call__(self, img: np.ndarray) -> List[Dict]:
        t0 = time.perf_counter()
        x, valid, tr = self.preprocess(img)
        out = self._finalize(self._dispatch(x[None], [valid]))[0]
        boxes = self.postprocess(out, valid, tr,
                                 bucket_hw=tuple(x.shape[:2]))
        self._record_request(time.perf_counter() - t0)
        return boxes

    # -- pipelined server (C4 module-level multithreading) ---------------------
    def serve_pipelined(self, images: List[np.ndarray]) -> List[List[Dict]]:
        def pre(img):
            return self.preprocess(img)

        def infer(item):
            x, valid, tr = item
            out = self._finalize(self._dispatch(x[None], [valid]))[0]
            return out, valid, tr, tuple(x.shape[:2])

        def post(item):
            out, valid, tr, bhw = item
            return self.postprocess(out, valid, tr, bucket_hw=bhw)

        pipe = HostPipeline([pre, infer, post], maxsize=4)
        t0 = time.perf_counter()
        results = pipe.run(images)
        dt = time.perf_counter() - t0
        with self._lock:
            self.stats["pipelined_tps"] = len(images) / dt
        return results

    # -- micro-batched server (the tentpole path) ------------------------------
    def _mb_infer(self, key, payloads):
        """Dispatch stage: submit one batch, return the PENDING device
        array (plus step-telemetry meta) without blocking — the
        completion stage materializes it, so the next bucket's batch
        dispatches while this one computes."""
        stack = np.stack([p[0] for p in payloads])
        return self._dispatch(stack, [p[1] for p in payloads])

    def _mb_finalize(self, key, raw):
        """Completion stage: block on the device result (D2H — the full
        label planes on the host path, the compact boxes tensor on the
        device path), record the measured step wall, and split into
        per-item payloads (the batch axis may be padded; the scheduler
        zips against live items only)."""
        return self._finalize(raw)

    def _mb_post(self, payload, out):
        x, valid, tr = payload
        return self.postprocess(out, valid, tr, bucket_hw=tuple(x.shape[:2]))

    def start_batched(self) -> "STDService":
        """Start the micro-batching scheduler (idempotent)."""
        if self._batcher is None:
            self._batcher = MicroBatcher(
                self._mb_infer, self._mb_post,
                finalize_fn=self._mb_finalize,
                max_batch=self.max_batch, max_wait_ms=self.max_wait_ms,
                max_pending=self.max_pending, admission=self.admission,
                inflight=self.inflight, book=self.book,
                max_batch_for=(self._bucket_cap
                               if self.activation_budget_bytes is not None
                               else None),
            )
            self._batcher.start()
        return self

    def stop_batched(self) -> None:
        if self._batcher is not None:
            self._batcher.stop()
            with self._lock:
                self.stats["batching"] = self._batcher.stats
                # scalar view survives the batcher for metric scrapes
                self.stats["batching_snapshot"] = \
                    self._batcher.stats_snapshot()
            self._batcher = None

    def submit(self, img: np.ndarray) -> Future:
        """Async request: preprocess on the caller thread (the pipeline's
        pre stage), then enqueue on the bucket's micro-batch."""
        if self._batcher is None:
            raise RuntimeError("call start_batched() first")
        x, valid, tr = self.preprocess(img)
        return self._batcher.submit(x.shape[:2], (x, valid, tr))

    def serve_batched(self, images: List[np.ndarray], *,
                      pre_workers: int = 4) -> List[List[Dict]]:
        """Closed-loop batched serving: preprocess+submit from a small
        thread pool (so buckets actually fill), gather futures in order."""
        started_here = self._batcher is None
        self.start_batched()
        rec = LatencyRecorder()
        t0 = time.perf_counter()

        def one(img):
            t = time.perf_counter()
            return rec.track(self.submit(img), t0=t)

        try:
            with ThreadPoolExecutor(pre_workers) as ex:
                futs = list(ex.map(one, images))
            results = [f.result(timeout=600) for f in futs]
            dt = time.perf_counter() - t0
            rec.wait()               # event-driven: no callback lag race
            with self._lock:
                self.stats["batched_tps"] = len(images) / dt
                self.stats["batched_latency_s"] = rec.samples
            return results
        finally:
            # a failed request must not strand the scheduler threads
            if started_here:
                self.stop_batched()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--width", type=float, default=0.25)
    ap.add_argument("--mode", default="optimized")
    ap.add_argument("--batched", action="store_true",
                    help="also run the micro-batched scheduler path")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--precision", default="f32", choices=["f32", "bfp"])
    ap.add_argument("--postprocess", default="host",
                    choices=["host", "device"],
                    help="box extraction: host label-map decode or "
                         "on-device compact rows")
    ap.add_argument("--model", default="pixellink",
                    choices=["pixellink", "east", "db"],
                    help="detection head to serve (models/fcn/heads.py "
                         "MODEL_ZOO)")
    args = ap.parse_args(argv)

    from repro.data.images import RequestStream
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    svc = STDService(width=args.width, mode=args.mode,
                     max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
                     precision=args.precision, postprocess=args.postprocess,
                     model=args.model)
    images = RequestStream(
        args.requests, seed=0, hw_range=((48, 120), (48, 120))
    ).images()
    # sequential (includes per-bucket compile on first hit)
    t0 = time.perf_counter()
    for img in images:
        svc(img)
    seq_dt = time.perf_counter() - t0
    # pipelined
    out = svc.serve_pipelined(images)
    msg = (f"[serve] {args.requests} reqs  "
           f"sequential {args.requests/seq_dt:.2f} TPS  "
           f"pipelined {svc.stats['pipelined_tps']:.2f} TPS")
    if args.batched:
        out_b = svc.serve_batched(images)
        assert [[b["box"] for b in r] for r in out] == \
               [[b["box"] for b in r] for r in out_b], "batched parity"
        msg += f"  batched {svc.stats['batched_tps']:.2f} TPS"
        sizes = [b["n"] for b in svc.stats["batching"]["batches"]]
        msg += f"  mean batch {np.mean(sizes):.2f}"
    msg += (f"  median latency {np.median(svc.stats['latency_s'])*1e3:.1f} ms"
            f"  boxes[0]={len(out[0])}")
    print(msg)
    return svc.stats


if __name__ == "__main__":
    main()
