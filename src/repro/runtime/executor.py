"""ExecutionPlan layer: one seam from assembled microcode to multi-device
serving.

The paper stacks three levels of parallelism over one fixed FCN datapath;
each level is an :class:`ExecutionPlan` target here, and every compiled
serving engine flows through :class:`EngineFactory` — so the scheduler
(launch/serve.py, launch/batching.py) never touches jit/shard_map
directly and later scaling work (multi-pod meshes, heterogeneous buckets,
async dispatch) only has to add plan types:

  * :class:`SingleDevice` — the baseline engine: the paper's batch-level
    parallelism only (one chip runs a (bucket, batch) shape end to end).
  * :class:`DataParallel` — the paper's batch level spread over a device
    mesh: shard_map splits the micro-batch over the mesh's "data" axis,
    each shard runs the full microcode program plus the CC-labeling tail
    on its slice (per-image ops, so per-shard == global).
  * :class:`RowBand` — the paper's §IV.B row-wise segmentation across
    devices: the image plane is split into horizontal bands over the
    "model" axis and each device runs the SAME program assembled at the
    band plane.  Every spatial layer halo-exchanges its own boundary
    rows (runtime/collectives.halo_exchange driven by
    FCNEngine._spatial_banded) — the multi-device generalization of
    core/rowband.conv2d_banded, layer by layer.  Band outputs equal the
    full plane mathematically; in "reference" mode (and wherever band
    offsets are Winograd-tile-aligned) they are bit-identical, while
    misaligned offsets in "optimized" mode regroup Winograd tiles and
    can shift scores by float-reassociation noise (~1e-6) — far inside
    the margin of any realistic 0.5-threshold decision.  This is the
    route for over-tall images that exceed the largest resolution
    bucket.

  * :class:`GridPlan` — the paper's two levels stacked in ONE compiled
    engine (§IV batch-level x row-wise segmentation): shard_map over a
    2-D mesh splits the micro-batch over the "data" axis *and* the image
    rows over the "model" axis simultaneously, so each model-row of
    devices runs the band-plane program on its batch shard with
    per-layer halo exchange along "model" only (halo_exchange never
    crosses the "data" axis — see runtime/collectives).  Activations
    follow the composed 2-D specs from runtime.sharding
    (fcn_activation_specs with both axes set).  This is the full-pod
    shape: a (data=N, model=M) mesh serves N batch shards of M-banded
    planes per step.

    Module-level pipelining (paper C4) stays host-side — HostPipeline /
    MicroBatcher overlap preprocess, device compute, and postprocess
    around whichever plan is active.

Plans are frozen, hashable dataclasses: the serving engine LRU keys on
``(bucket_hw, batch, plan, precision, model)`` and a mesh, precision, or
model change is a new compiled engine, never silent reuse.  ``model`` is
the paper's versatility axis (models/fcn/heads.MODEL_ZOO): every
detection head compiles through the same assembler -> microcode path,
and the factory's ``make_model(hw, precision, model)`` builds whichever
head a request routes to.  ``precision`` is the
paper's numerics axis (docs/plans.md "Precision modes"): ``"f32"`` runs
plain float convs, ``"bfp"`` runs BFP-quantized convs with FP16
data-pool storage and the Pallas kernels where the backend compiles
them — the factory's ``make_model(hw, precision)`` builds the matching
model, and the bfp parameter cache holds the f32 parameters run through
the paper's Fig. 4 normalization (BN fold + BFP weight roundtrip), so
both precisions share one underlying weight set and accuracy-parity
gates compare like with like.

Compiled engines are ASYNC: calling one returns un-materialized device
arrays (JAX async dispatch), so the serving dispatch stage can submit
the next batch while this one's H2D/compute/D2H run; materialization
(``np.asarray``) is the completion stage's job (launch/batching.py).
On accelerator backends the padded input stack's buffer is donated back
to XLA (:func:`_donate_argnums`).
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
import threading
import time
from typing import Any, Callable, Dict, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch.batching import LRUCache
from repro.models.fcn.heads import DEFAULT_MODEL, check_model
from repro.runtime.collectives import halo_exchange
from repro.runtime.sharding import fcn_activation_specs, mesh_axis_sizes


@dataclasses.dataclass(frozen=True)
class SingleDevice:
    """Run the whole (bucket, batch) shape on the default device."""


@dataclasses.dataclass(frozen=True)
class DataParallel:
    """Split the batch over ``mesh`` axis ``axis`` (paper batch level)."""

    mesh: Mesh
    axis: str = "data"


@dataclasses.dataclass(frozen=True)
class RowBand:
    """Split image rows into bands over ``mesh`` axis ``axis`` (paper
    §IV.B).  ``bands`` must equal the axis size (0 = take it from the
    mesh); per-layer halo widths are derived from each layer's kernel."""

    mesh: Mesh
    axis: str = "model"
    bands: int = 0


@dataclasses.dataclass(frozen=True)
class GridPlan:
    """Batch over ``data_axis`` x rows over ``model_axis`` in one
    shard_map (paper §IV batch level + row-wise segmentation stacked).
    ``bands`` must equal the model-axis size (0 = take it from the
    mesh); batch sizes must be a multiple of the data-axis size."""

    mesh: Mesh
    data_axis: str = "data"
    model_axis: str = "model"
    bands: int = 0


ExecutionPlan = Union[SingleDevice, DataParallel, RowBand, GridPlan]

#: execution precisions the engine LRU keys on: plain float vs the
#: paper's BFP-quantized datapath with FP16 data-pool storage
PRECISIONS = ("f32", "bfp")


def check_precision(precision: str) -> str:
    if precision not in PRECISIONS:
        raise ValueError(
            f"unknown precision {precision!r}; expected one of {PRECISIONS}"
        )
    return precision


class _BandCtx:
    """Halo-exchange hook handed to FCNEngine for row-banded execution
    (keeps core/ free of collective imports)."""

    def __init__(self, axis_name: str, n_bands: int):
        self.axis_name = axis_name
        self.n_bands = n_bands

    def exchange(self, x, halo: int):
        return halo_exchange(
            x, self.axis_name, halo, axis=1, axis_size=self.n_bands
        )


def _donate_argnums() -> Tuple[int, ...]:
    """Donation slots for compiled engines: the padded input stack
    (arg 1) is built fresh per batch and never reused by the scheduler,
    so on accelerator backends XLA may overwrite its buffer in place —
    with async pipelined dispatch each in-flight batch owns its own
    donated slot, so overlap never aliases live data.  CPU XLA cannot
    donate and would warn on every call, so donation is gated off
    there."""
    return (1,) if jax.default_backend() in ("gpu", "tpu") else ()


def plan_batch_multiple(plan: ExecutionPlan) -> int:
    """Batch sizes compiled for ``plan`` must be a multiple of this."""
    if isinstance(plan, DataParallel):
        return mesh_axis_sizes(plan.mesh).get(plan.axis, 1)
    if isinstance(plan, GridPlan):
        return mesh_axis_sizes(plan.mesh).get(plan.data_axis, 1)
    return 1


def plan_bands(plan: ExecutionPlan) -> int:
    """Number of row bands a plan splits the image plane into (1 for
    non-banded plans)."""
    if isinstance(plan, RowBand):
        return plan.bands or mesh_axis_sizes(plan.mesh).get(plan.axis, 1)
    if isinstance(plan, GridPlan):
        return plan.bands or mesh_axis_sizes(plan.mesh).get(
            plan.model_axis, 1
        )
    return 1


def band_height_unit(plan: ExecutionPlan, deepest_stride: int) -> int:
    """Heights compiled for a row-banded plan (RowBand or GridPlan) must
    be a multiple of this: every band must divide evenly through the
    whole stride pyramid (``H % (bands * deepest_stride) == 0``)."""
    return plan_bands(plan) * deepest_stride


def row_band_height_unit(plan: RowBand, deepest_stride: int) -> int:
    """Back-compat alias for :func:`band_height_unit`."""
    return band_height_unit(plan, deepest_stride)


def plan_kind(plan: ExecutionPlan) -> str:
    """The planner-side kind string for a plan instance — the key the
    telemetry CostBook and runtime/planner.PLAN_KINDS share."""
    if isinstance(plan, DataParallel):
        return "data_parallel"
    if isinstance(plan, RowBand):
        return "row_band"
    if isinstance(plan, GridPlan):
        return "grid"
    return "single_device"


def describe_plan(plan: ExecutionPlan) -> str:
    if isinstance(plan, DataParallel):
        n = mesh_axis_sizes(plan.mesh).get(plan.axis, 1)
        return f"data_parallel[{plan.axis}={n}]"
    if isinstance(plan, RowBand):
        n = plan.bands or mesh_axis_sizes(plan.mesh).get(plan.axis, 1)
        return f"row_band[{plan.axis}={n}]"
    if isinstance(plan, GridPlan):
        sizes = mesh_axis_sizes(plan.mesh)
        dn = sizes.get(plan.data_axis, 1)
        mn = plan.bands or sizes.get(plan.model_axis, 1)
        return f"grid[{plan.data_axis}={dn},{plan.model_axis}={mn}]"
    return "single_device"


class EngineFactory:
    """Compiles (bucket_hw, batch, plan, precision) -> engine callable,
    with the model/param caches and the compiled-engine LRU behind one
    lock.

    ``make_model(hw, precision)`` builds the STD model for one input
    plane at one execution precision (its parameters must be
    plane-invariant — fully convolutional — so one per-bucket param set
    serves every band plane derived from it).  Legacy single-argument
    ``make_model(hw)`` callables still work but pin the factory to
    ``"f32"``.  The compiled callable is ``fn(params, x, valid_q) ->
    (labels, converged)``: FCN forward, per-image valid-region masking,
    batched CC labeling (log-hop pointer jumping), and the per-image
    convergence flag the serving layer counts instead of swallowing.
    On TPU the CC tail routes through the Pallas tile-local kernel
    (``cc_pallas=None`` derives from the backend; force with
    True/False), and :meth:`boxes_fn` compiles the on-device compact
    box extraction the device postprocess path rides
    (docs/serving.md "Postprocess pipeline").

    Parameters are per-precision without being independent: the f32
    cache holds the deterministic PRNGKey(0) initialization, and the
    bfp cache holds those SAME parameters run through the paper's
    Fig. 4 normalization (BN fold + BFP weight roundtrip via the bfp
    model's ``normalize_weights``) — so f32-vs-bfp accuracy parity
    compares one weight set under two numerics, never two inits.

    With a telemetry ``book`` (runtime/telemetry.CostBook) every
    compiled engine is wrapped once, at compile time, to record its
    per-call wall keyed by (bucket_hw, batch, plan_kind) under
    ``stage="dispatch"`` — the non-blocking engine-call side of the
    measured-cost loop (engines return un-materialized arrays; the
    serving layer records the dispatch-through-materialization
    ``stage="step"`` wall the planner's MeasuredCost overlay reads).
    The wrapper lives inside the LRU, so cache hits return the identical
    callable.
    """

    def __init__(
        self,
        make_model: Callable[..., Any],
        *,
        score_thr: float = 0.5,
        link_thr: float = 0.5,
        capacity: int = 16,
        book: Any = None,
        cc_pallas: Any = None,
        engine_bytes_budget: int = 0,
        device: Any = None,
    ):
        self.make_model = make_model
        # make_model generations: legacy (hw), precision-aware
        # (hw, precision), model-aware (hw, precision, model).
        # Unintrospectable callables are treated as model-aware (they
        # can ignore the extras).
        try:
            n_params = len([
                p for p in inspect.signature(make_model).parameters.values()
                if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
                or p.kind == p.VAR_POSITIONAL
            ])
        except (TypeError, ValueError):
            n_params = 3
        self._make_model_arity = min(n_params, 3)
        self.score_thr = score_thr
        self.link_thr = link_thr
        self.book = book
        # parameters committed to ``device`` pull every engine call that
        # uses them onto it (one replica per chip); None = default device
        self.device = device
        # Pallas tile-local CC kernel only beats the jnp while_loop where
        # it actually compiles (TPU Mosaic); elsewhere the interpreter
        # would be orders of magnitude slower than XLA
        self.cc_pallas = (jax.default_backend() == "tpu"
                          if cc_pallas is None else bool(cc_pallas))
        # model/param caches are LRU-bounded like the engines: oversize
        # inputs clamp to an open-ended set of padded shapes (bucket_hw),
        # so unbounded dicts would leak a parameter tree per shape
        self._models = LRUCache(capacity)
        self._params = LRUCache(capacity)
        # the engine LRU can evict by planned activation bytes instead of
        # (only) entry count: plan_fn puts each engine with
        # weight = memplan peak bytes x batch, so a byte budget keeps the
        # RESIDENT FOOTPRINT bounded rather than the engine count —
        # engine_bytes_budget=0 keeps the pure count rule
        self._engines = LRUCache(capacity, byte_budget=engine_bytes_budget)
        self._memplans = LRUCache(capacity)
        self._lock = threading.Lock()
        self.stats: Dict[str, Any] = {"compiled": [], "engine_memory": []}
        self._mem_measured: Dict[Any, Dict[str, Any]] = {}

    def _build_model(self, hw: Tuple[int, int], precision: str, model: str):
        if self._make_model_arity < 3 and model != DEFAULT_MODEL:
            raise ValueError(
                f"make_model {self.make_model!r} is not model-aware; a "
                f"model-zoo factory needs make_model(hw, precision, "
                f"model) to build {model!r} engines"
            )
        if self._make_model_arity < 2:
            if precision != "f32":
                raise ValueError(
                    f"make_model {self.make_model!r} takes only (hw); a "
                    f"precision-aware factory needs make_model(hw, "
                    f"precision) to build {precision!r} engines"
                )
            return self.make_model(hw)
        if self._make_model_arity < 3:
            return self.make_model(hw, precision)
        return self.make_model(hw, precision, model)

    # -- model / param caches --------------------------------------------------
    def model(self, hw: Tuple[int, int], precision: str = "f32",
              model: str = DEFAULT_MODEL):
        hw = tuple(hw)
        check_precision(precision)
        check_model(model)
        with self._lock:
            m = self._models.get((hw, precision, model))
            if m is None:
                m = self._build_model(hw, precision, model)
                self._models.put((hw, precision, model), m)
            return m

    def params(self, hw: Tuple[int, int], precision: str = "f32",
               model: str = DEFAULT_MODEL):
        """Parameters for one plane — deterministic (PRNGKey(0)), so an
        LRU-evicted entry rebuilds identically.  The bfp entry is the
        f32 entry run through the bfp model's ``normalize_weights``
        (paper Fig. 4: BN fold + BFP weight normalization) — one weight
        set under both numerics.  Per model: heads differ in parameter
        trees, so the cache keys on (hw, precision, model)."""
        hw = tuple(hw)
        check_precision(precision)
        check_model(model)
        model_obj = self.model(hw, precision, model)
        raw = self.params(hw, "f32", model) if precision != "f32" else None
        with self._lock:
            p = self._params.get((hw, precision, model))
            if p is None:
                p = (model_obj.init_params(jax.random.PRNGKey(0))
                     if precision == "f32"
                     else model_obj.normalize_weights(raw))
                if self.device is not None:
                    p = jax.device_put(p, self.device)
                self._params.put((hw, precision, model), p)
            return p

    def memplan(self, hw: Tuple[int, int], precision: str = "f32",
                model: str = DEFAULT_MODEL):
        """The static memory plan (core.memplan.MemPlan) of the program
        assembled at ``hw`` — cached per (hw, precision, model).  Byte
        accounting follows the precision's compute dtype: f32 activations
        are 4 bytes, bfp serving stores fp16 between layers (2)."""
        from repro.core.memplan import plan_program

        hw = tuple(hw)
        check_precision(precision)
        check_model(model)
        key = (hw, precision, model)
        plan = self._memplans.get(key)
        if plan is None:
            prog = self.model(hw, precision, model).program
            plan = plan_program(
                prog, dtype_bytes=2 if precision == "bfp" else 4
            )
            self._memplans.put(key, plan)
        return plan

    def engine_weight_bytes(self, hw: Tuple[int, int], batch: int,
                            precision: str = "f32",
                            model: str = DEFAULT_MODEL) -> int:
        """Planned activation footprint of one compiled engine — the
        byte weight its LRU entry carries."""
        return int(self.memplan(hw, precision, model).peak_bytes) * int(batch)

    def measure_engine_memory(self, hw: Tuple[int, int], batch: int,
                              plan: "ExecutionPlan", precision: str = "f32",
                              model: str = DEFAULT_MODEL) -> Dict[str, Any]:
        """AOT-compile one engine shape and read the backend's buffer
        assignment (launch/hlo_analysis.lowered_memory): temp / argument
        / output bytes.  Explicit opt-in — it compiles outside the
        serving engine cache, so a bench calling it pays one extra
        compile per shape.  Results are memoized and appended to
        ``stats["engine_memory"]`` (the metrics_snapshot gauge source)."""
        from repro.launch.hlo_analysis import lowered_memory

        hw = tuple(hw)
        key = (hw, int(batch), plan, precision, model)
        got = self._mem_measured.get(key)
        if got is not None:
            return got
        model_obj = self.model(hw, precision, model)
        params = self.params(hw, precision, model)
        c0 = model_obj.program.input_shape_chw[0]
        x_sds = jax.ShapeDtypeStruct((int(batch), hw[0], hw[1], c0),
                                     jnp.float32)
        vq_sds = jax.ShapeDtypeStruct((int(batch), 2), jnp.int32)
        raw = self._compile(hw, int(batch), plan, precision, model)
        stats = lowered_memory(raw, params, x_sds, vq_sds)
        row = {
            "hw": hw, "batch": int(batch), "plan": describe_plan(plan),
            "precision": precision, "model": model,
            "planned_peak_bytes": self.engine_weight_bytes(
                hw, batch, precision, model),
            **stats,
        }
        self._mem_measured[key] = row
        self.stats.setdefault("engine_memory", []).append(row)
        return row

    def deepest_stride(self, hw: Tuple[int, int], precision: str = "f32",
                       model: str = DEFAULT_MODEL) -> int:
        """Deepest cumulative stride of the program assembled at ``hw``
        (architecture property — plane-independent for divisible planes)."""
        prog = self.model(tuple(hw), precision, model).program
        return max(hw[0] // max(h, 1) for h, _, _ in prog.addr_shapes.values())

    # -- engines ---------------------------------------------------------------
    def plan_fn(self, hw: Tuple[int, int], batch: int,
                plan: ExecutionPlan, precision: str = "f32",
                model: str = DEFAULT_MODEL) -> Callable:
        """The compiled engine for one (bucket, batch, plan, precision,
        model) key — a precision or model change is a different engine,
        never a cache hit on the other numerics or head."""
        check_precision(precision)
        check_model(model)
        key = (tuple(hw), int(batch), plan, precision, model)
        fn = self._engines.get(key)
        if fn is not None:
            return fn
        fn = self._compile(tuple(hw), int(batch), plan, precision, model)
        if self.book is not None:
            fn = self._timed(fn, tuple(hw), int(batch), plan_kind(plan),
                             precision, model)
        self.stats["compiled"].append(
            {"hw": tuple(hw), "batch": int(batch),
             "plan": describe_plan(plan), "precision": precision,
             "model": model}
        )
        self._engines.put(key, fn, weight=self.engine_weight_bytes(
            hw, batch, precision, model))
        return fn

    def _timed(self, fn: Callable, hw, batch: int, kind: str,
               precision: str = "f32",
               model: str = DEFAULT_MODEL) -> Callable:
        """Record each engine call's wall into the telemetry book.
        This measures the DISPATCH side only — engines return pending
        arrays, so blocking here would serialize the async pipeline."""
        def timed(params, x, valid_q):
            t0 = time.perf_counter()
            out = fn(params, x, valid_q)
            self.book.record_step(hw, batch, kind,
                                  time.perf_counter() - t0,
                                  stage="dispatch", precision=precision,
                                  model=model)
            return out

        # AOT lowering stays available through the wrapper: a caller that
        # compiles ``lower(...).compile()`` warms the same executable the
        # timed calls then run
        timed.lower = fn.lower
        return timed

    def _tail(self, model_obj, out, valid_q):
        """The model's serving tail: named maps -> (*payload, converged).
        Zoo models carry a DetectionHead that owns the tail (CC labeling
        for segmentation heads, valid-region masking for regression
        heads); headless legacy models get the PixelLink CC tail."""
        head = getattr(model_obj, "head", None)
        if head is not None:
            return head.tail(self, out, valid_q)
        return self._label_tail(out["score"], out["links"], valid_q)

    def label_tail(self, score, links, valid_q):
        """Public CC-tail entry point for DetectionHead.tail
        implementations (the shared log-hop labeling machinery)."""
        return self._label_tail(score, links, valid_q)

    def _label_tail(self, score, links, valid_q):
        """Batched CC labeling tail -> (labels, converged) with the
        per-image (N,) convergence flag (iters stay internal)."""
        from repro.models.fcn import postprocess as pp

        h, w = score.shape[1:]
        mask = (
            (jnp.arange(h)[None, :, None] < valid_q[:, 0, None, None])
            & (jnp.arange(w)[None, None, :] < valid_q[:, 1, None, None])
        )
        if self.cc_pallas:
            from repro.kernels.cc_label import cc_label_pallas

            labels, _, converged = cc_label_pallas(
                score, links, self.score_thr, self.link_thr,
                valid_mask=mask, return_stats=True,
            )
        else:
            labels, _, converged = pp.cc_label_batched(
                score, links, self.score_thr, self.link_thr,
                valid_mask=mask, return_stats=True,
            )
        return labels, converged

    def boxes_fn(self, hw: Tuple[int, int], batch: int,
                 capacity: int) -> Callable:
        """Compiled on-device box extraction for one (bucket, batch)
        shape: ``fn(labels (N, h, w) int32) -> (rows, counts)`` with
        ``rows`` (N, capacity + 1, 6) and ``counts`` (N,) — the compact
        D2H payload of the device postprocess path (postprocess
        ``boxes_from_labels_batched_jax``).  Cached in the engine LRU
        alongside the plan fns (distinct key namespace)."""
        from repro.models.fcn import postprocess as pp

        key = ("boxes", tuple(hw), int(batch), int(capacity))
        fn = self._engines.get(key)
        if fn is not None:
            return fn
        fn = jax.jit(functools.partial(
            pp.boxes_from_labels_batched_jax, capacity=int(capacity)
        ))
        self.stats.setdefault("boxes_compiled", []).append(
            {"hw": tuple(hw), "batch": int(batch),
             "capacity": int(capacity)}
        )
        self._engines.put(key, fn)
        return fn

    def _compile(self, hw, batch, plan, precision: str = "f32",
                 model: str = DEFAULT_MODEL) -> Callable:
        if isinstance(plan, SingleDevice):
            return self._compile_single(hw, precision, model)
        if isinstance(plan, DataParallel):
            return self._compile_data_parallel(hw, batch, plan, precision,
                                               model)
        if isinstance(plan, RowBand):
            return self._compile_row_band(hw, plan, precision, model)
        if isinstance(plan, GridPlan):
            return self._compile_grid(hw, batch, plan, precision, model)
        raise TypeError(f"unknown execution plan {plan!r}")

    def _compile_single(self, hw, precision: str = "f32",
                        model: str = DEFAULT_MODEL) -> Callable:
        model_obj = self.model(hw, precision, model)

        def run(params, x, valid_q):
            out = model_obj.apply(params, x)
            return self._tail(model_obj, out, valid_q)

        return jax.jit(run, donate_argnums=_donate_argnums())

    def _compile_data_parallel(self, hw, batch, plan,
                               precision: str = "f32",
                               model: str = DEFAULT_MODEL) -> Callable:
        n = mesh_axis_sizes(plan.mesh).get(plan.axis)
        if n is None:
            raise ValueError(
                f"mesh {plan.mesh.axis_names} has no axis {plan.axis!r}"
            )
        if batch % n:
            raise ValueError(
                f"batch {batch} not divisible by {plan.axis}={n}; round "
                f"with plan_batch_multiple()"
            )
        model_obj = self.model(hw, precision, model)
        specs = fcn_activation_specs(batch_axis=plan.axis)
        head = getattr(model_obj, "head", None)
        # per-payload out specs: rank-3 payloads (label/score planes)
        # shard like labels, rank-4 (vector maps) like links
        ranks = getattr(head, "payload_ranks", (3,))
        payload_specs = tuple(
            specs["labels"] if r == 3 else specs["links"] for r in ranks
        )

        def shard(params, x, valid_q):
            out = model_obj.apply(params, x)
            return self._tail(model_obj, out, valid_q)

        return jax.jit(jax.shard_map(
            shard, mesh=plan.mesh,
            in_specs=(P(), specs["image"], P(plan.axis)),
            out_specs=(*payload_specs, P(plan.axis)), check_vma=False,
        ), donate_argnums=_donate_argnums())

    def _compile_row_band(self, hw, plan, precision: str = "f32",
                          model: str = DEFAULT_MODEL) -> Callable:
        n = mesh_axis_sizes(plan.mesh).get(plan.axis)
        if n is None:
            raise ValueError(
                f"mesh {plan.mesh.axis_names} has no axis {plan.axis!r}"
            )
        bands = plan.bands or n
        if bands != n:
            raise ValueError(
                f"bands={plan.bands} must equal mesh axis {plan.axis}={n}"
            )
        return self._compile_banded(plan.mesh, hw, bands, plan.axis,
                                    precision=precision, model=model)

    def _compile_banded(self, mesh, hw, bands: int, model_axis: str,
                        batch_axis=None, precision: str = "f32",
                        model: str = DEFAULT_MODEL) -> Callable:
        """The shared row-banded engine: each device runs the SAME
        program assembled at the band plane, and every spatial layer
        halo-exchanges its own boundary rows along ``model_axis``
        (FCNEngine._spatial_banded), so outputs are exact per band.
        With ``batch_axis`` the batch dim is sharded too (GridPlan);
        halo exchange still moves along ``model_axis`` only."""
        W = hw[1]
        band_h = self._band_height(hw, bands, precision, model)
        model_obj = self.model(hw, precision, model)
        band_model = (model_obj.for_plane((band_h, W))
                      if hasattr(model_obj, "for_plane")
                      else self._build_model((band_h, W), precision, model))
        ctx = _BandCtx(model_axis, bands)
        specs = fcn_activation_specs(
            batch_axis=batch_axis, rows_axis=model_axis
        )
        head = getattr(model_obj, "head", None)
        # the shard body returns the head's named maps; rank-3 maps
        # (per-pixel scalars) shard like score, rank-4 like links
        maps = getattr(head, "maps", (("score", 3), ("links", 4)))
        map_specs = tuple(
            specs["score"] if r == 3 else specs["links"] for _, r in maps
        )

        def shard(params, x):
            out = band_model.apply(params, x, band_ctx=ctx)
            return tuple(out[n] for n, _ in maps)

        sm = jax.shard_map(
            shard, mesh=mesh,
            in_specs=(P(), specs["image"]),
            out_specs=map_specs, check_vma=False,
        )

        # the tail needs whole planes, so it runs in a second shard_map
        # over the batch axis only (rows gathered; replicated without a
        # batch axis) — a Pallas (Mosaic) CC kernel cannot be partitioned
        # automatically by the compiler
        n_out = len(getattr(head, "payload_ranks", (3,))) + 1
        tail = jax.shard_map(
            lambda valid_q, *m: self._tail(
                model_obj, dict(zip((n for n, _ in maps), m)), valid_q),
            mesh=mesh,
            in_specs=(P(batch_axis),) * (len(maps) + 1),
            out_specs=(P(batch_axis),) * n_out, check_vma=False,
        )

        def run(params, x, valid_q):
            return tail(valid_q, *sm(params, x))

        return jax.jit(run, donate_argnums=_donate_argnums())

    def _band_height(self, hw, bands: int, precision: str = "f32",
                     model: str = DEFAULT_MODEL) -> int:
        """Validated per-band height for splitting plane ``hw`` into
        ``bands`` rows: the band must divide evenly through the whole
        stride pyramid so every device's local rows stay integral at the
        deepest scale (``H % (bands * deepest_stride) == 0``)."""
        H, _ = hw
        if H % bands:
            raise ValueError(f"H={H} not divisible into {bands} bands")
        band_h = H // bands
        deepest = self.deepest_stride(hw, precision, model)
        if band_h % deepest:
            raise ValueError(
                f"band height {band_h} must be a multiple of the deepest "
                f"cumulative stride {deepest} (H={H}, bands={bands})"
            )
        return band_h

    def _compile_grid(self, hw, batch, plan: GridPlan,
                      precision: str = "f32",
                      model: str = DEFAULT_MODEL) -> Callable:
        """DataParallel x RowBand composed in one shard_map: batch over
        ``data_axis``, rows over ``model_axis``, per-layer halo exchange
        along ``model_axis`` only."""
        sizes = mesh_axis_sizes(plan.mesh)
        dn = sizes.get(plan.data_axis)
        mn = sizes.get(plan.model_axis)
        for ax, n in ((plan.data_axis, dn), (plan.model_axis, mn)):
            if n is None:
                raise ValueError(
                    f"mesh {plan.mesh.axis_names} has no axis {ax!r}"
                )
        if plan.data_axis == plan.model_axis:
            raise ValueError(
                f"grid axes must differ, got {plan.data_axis!r} twice"
            )
        if batch % dn:
            raise ValueError(
                f"batch {batch} not divisible by {plan.data_axis}={dn}; "
                f"round with plan_batch_multiple()"
            )
        bands = plan.bands or mn
        if bands != mn:
            raise ValueError(
                f"bands={plan.bands} must equal mesh axis "
                f"{plan.model_axis}={mn}"
            )
        return self._compile_banded(
            plan.mesh, hw, bands, plan.model_axis,
            batch_axis=plan.data_axis, precision=precision, model=model,
        )

    # -- introspection ---------------------------------------------------------
    @property
    def engines(self) -> LRUCache:
        return self._engines

    def __len__(self) -> int:
        return len(self._engines)
