"""One cell, once: build the served system from the cell's files, make
the configuration's weights and the seed's images, warm the engines the
traffic can form, drive the window through ``STDService.submit``, then
check the served answers against the plain reference.

Everything that belongs to one configuration, traffic mix or metric is
data found by name:

* ``BENCHMARK.json`` ``workloads[]``: the cell -> configuration + mix;
* ``configs[].file``: the configuration (model fields, deployment,
  reference module, weights seed and head calibration, limits of the
  check);
* ``chipbench/traffic/<mix>.json``: loop, clients or rate, sizes, pool;
* ``chipbench/metrics/<metric>.py``: ``read(ctx)`` for each metric;
* ``chipbench/references/<reference>.py``: the plain float32 model.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import sys
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import check, loadgen, yardstick
from chipbench.compile_probe import CompileProbe

ROOT = Path(__file__).resolve().parents[1]
#: requests whose answers are compared with the reference, per run
SAMPLE = 16
#: how long the drain waits for requests still open at the window's end:
#: a mix offered above what the chip serves leaves a backlog of
#: (offered - served) x seconds, which a slowed host drains for minutes
DRAIN_S = 150.0
#: images per reference call (one compiled shape per plane)
REF_BLOCK = 8
#: seconds of the window that a traced run records (its last ones)
TRACE_S = 2.0


class CellError(RuntimeError):
    """The cell cannot be run as its files describe it."""


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    root: Path
    chips: int
    config: Dict
    mix: Dict
    metrics: List[Dict]          # BENCHMARK.json entries this cell reports

    @property
    def model_fields(self) -> Dict:
        return self.config["model"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve a workload name to its configuration, mix and metrics."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    mix = load_json(root / "chipbench" / "traffic" / f"{w['traffic']}.json")
    metrics = []
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if name in m.get("workloads", [name]):
                metrics.append(dict(m, kind=kind))
    return Cell(name, root, int(w["chips"]), config, mix, metrics)


def load_module(path: Path):
    """A benchmark file found by name (a metric reader, a reference)."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}", path)
    if spec is None or not path.is_file():
        raise CellError(f"missing {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_metric(root: Path, name: str, ctx) -> Optional[float]:
    """``chipbench/metrics/<name>.py``'s ``read(ctx)``; None where the
    reader finds nothing to read."""
    return load_module(root / "chipbench" / "metrics" / f"{name}.py").read(ctx)


class Served:
    """The system under test for one cell: an ``STDService`` built from
    the configuration's fields and deployment, with the hooks the
    harness needs around its stages (request tags, label capture, trace
    annotations); the hooks only record, they change nothing served."""

    def __init__(self, cell: Cell, *, config_override: Optional[Dict] = None):
        import jax
        from repro.core.interpreter import BFPConfig
        from repro.launch.serve import STDService
        from repro.models.fcn.pixellink import STDConfig

        fields = dict(cell.model_fields)
        if config_override:
            fields.update(config_override)
        kw = {k: tuple(v) if isinstance(v, list) else v
              for k, v in fields.items() if k != "bfp"}
        if fields.get("bfp") is not None:
            kw["bfp"] = BFPConfig(**fields["bfp"])
        dep = cell.config["deployment"]
        self.fields = fields
        self.precision = dep["precision"]
        self.svc = STDService(config=STDConfig(**kw),
                              buckets=tuple(dep["buckets"]),
                              max_batch=int(dep["max_batch"]),
                              precision=self.precision)
        self.ref = load_module(cell.root / "chipbench" / "references"
                               / f"{cell.config['reference']}.py")
        self.arch = {k: fields[k] for k in ("backbone", "width", "merge_ch")}
        self.labels: Dict[int, np.ndarray] = {}
        self.current = -1
        self._tags: Dict[int, int] = {}      # id(padded image) -> request
        self._make = None
        self.fwd = None                       # the reference, jitted
        self._annotate = jax.profiler.TraceAnnotation
        self._hook()

    def _hook(self):
        """Wrap the service's request stages.  The originals are looked
        up on the class at each call, so a test can plant a fault in the
        program underneath the hooks."""
        svc, cls, ann = self.svc, type(self.svc), self._annotate

        def preprocess(img):
            out = cls.preprocess(svc, img)
            self._tags[id(out[0])] = self.current
            return out

        def mb_infer(key, payloads):
            with ann("chipbench.dispatch"):
                raw = cls._mb_infer(svc, key, payloads)
                # raw = (pending, (hw, padded batch, plan kind, t0))
                with ann("chipbench.batch", live=len(payloads),
                         padded=int(raw[1][1])):
                    return raw

        def mb_finalize(key, raw):
            with ann("chipbench.complete"):
                return cls._mb_finalize(svc, key, raw)

        def mb_post(payload, out):
            self.labels[self._tags.pop(id(payload[0]), -1)] = out
            with ann("chipbench.post"):
                return cls._mb_post(svc, payload, out)

        svc.preprocess = preprocess
        svc._mb_infer = mb_infer
        svc._mb_finalize = mb_finalize
        svc._mb_post = mb_post

    def submit(self, req: int, img: np.ndarray):
        self.current = req
        with self._annotate("chipbench.submit"):
            return self.svc.submit(img)

    def buckets_for(self, pool) -> List[tuple]:
        """The (H, W) buckets the service pads the pool's images to."""
        pre = type(self.svc).preprocess
        return sorted({tuple(pre(self.svc, img)[0].shape[:2])
                       for img in pool})

    def set_weights(self, seed: int, buckets, calib: np.ndarray,
                    shares: Dict[str, float]) -> Any:
        """Weights from the seed, made on the device in one jitted call
        in the type they are served in, handed to the service's
        parameter cache for every bucket.  The head is calibrated on
        ``calib`` to the configuration's text and link shares.  Returns
        the reference's own float32 weights (what the program was
        given, before it normalised them)."""
        import jax

        svc = self.svc
        model = svc.factory.model(tuple(buckets[0]), self.precision,
                                  svc.model_name)
        ref, arch, bfp = self.ref, self.arch, self.precision == "bfp"

        key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                                 seed >> 32)
        if self._make is None:
            def make(key, calib):
                raw = ref.calibrate(ref.init_params(key, arch), calib, arch,
                                    shares["text_share"],
                                    shares["link_share"])
                prog = ref.to_program(raw)
                return raw, (model.normalize_weights(prog) if bfp else prog)

            want = jax.eval_shape(svc.factory.model(
                tuple(buckets[0]), "f32", svc.model_name).init_params, key)
            got = jax.eval_shape(
                lambda k: ref.to_program(make(k, calib)[0]), key)
            if jax.tree.structure(want) != jax.tree.structure(got) or any(
                    a.shape != b.shape for a, b in zip(
                        jax.tree.leaves(want), jax.tree.leaves(got))):
                raise CellError("the reference's weights do not map onto "
                                "the served model's parameter tree")
            self._make = jax.jit(make)
        raw, served = self._make(key, calib)
        for hw in buckets:
            cache = svc.factory._params
            cache.put((tuple(hw), "f32", svc.model_name), ref.to_program(raw))
            cache.put((tuple(hw), self.precision, svc.model_name), served)
        jax.block_until_ready(served)
        return raw

    def warm(self, buckets, batches) -> None:
        """Compile (or load) every (bucket, batch) engine the traffic
        can form, through the service's own synchronous path."""
        for b in batches:
            for hw in buckets:
                self.svc.infer_labels(
                    np.zeros((b, hw[0], hw[1], 3), np.float32),
                    [tuple(hw)] * b)

    def counters(self) -> Dict[str, float]:
        """The program's own counters and spans, summed so far."""
        snap = self.svc.metrics_snapshot()
        book, kw = self.svc.book, {"stage": "postprocess",
                                   "model": self.svc.model_name}
        keys = book.step_keys(**kw)
        pp_s = sum(book.step_total(*k, **kw) for k in keys)
        pp_n = sum(book.step_count(*k, **kw) for k in keys)
        return {
            "batches": sum(snap.get(f"std_mb_flush_{r}", 0.0)
                           for r in ("full", "timeout", "drain")),
            "batch_items": snap.get("std_mb_batch_items", 0.0),
            "dispatch_s": snap.get("std_mb_dispatch_busy_s", 0.0),
            "postprocess_s": pp_s,
            "postprocess_n": pp_n,
        }


@dataclasses.dataclass
class Window:
    """What one measured window left for the metric readers."""

    seconds: float
    setup_s: float
    t0: float
    reqs: loadgen.Requests
    counters: Dict[str, float]           # deltas over the window
    batches: List[Dict]                  # batches formed in the window
    compiles: tuple                      # CompileProbe.since over window
    trace: Optional[Dict] = None         # chipbench.trace.reduce(...)
    convs: Optional[list] = None         # layer list at the bucket plane
    fields: Optional[Dict] = None
    peak: Optional[Dict] = None
    latencies_ms: Optional[List[float]] = None


def calibration_image(mix: Dict, pool, seed: int,
                      stride: int) -> np.ndarray:
    """The head's calibration input: one scene of the mix drawn from the
    weights' seed, square at the pool's smallest side (down to a
    multiple of the network's stride), so it holds no padding."""
    side = min(min(img.shape[:2]) for img in pool) // stride * stride
    rng = np.random.default_rng(seed)
    return loadgen.synthetic_image(side, side, rng,
                                   int(mix["max_instances"]))[None]


def run_window(served: Served, cell: Cell, seed: int, seconds: float, *,
               t_start: float, trace_dir: Optional[Path] = None,
               pool=None, log=print) -> Window:
    """Set up (weights, pool, warm engines), then one measured window
    and its drain."""
    import jax

    mix = cell.mix
    pool = loadgen.image_pool(mix, seed) if pool is None else pool
    buckets = served.buckets_for(pool)
    t = time.perf_counter()
    # the deployment serves one network, the same in every run; the
    # run's seed draws the images and the arrivals (the work is fixed)
    wseed = int(cell.config["weights_seed"])
    calib = calibration_image(mix, pool, wseed, served.ref.STRIDE)
    served.raw = served.set_weights(wseed, buckets, calib,
                                    cell.config["calibration"])
    t_weights = time.perf_counter() - t
    served.warm(buckets, mix["batches"])
    log(f"setup pool+start={t - t_start:.4f}s weights={t_weights:.4f}s "
        f"warm={time.perf_counter() - t - t_weights:.4f}s "
        f"buckets={buckets} batches={mix['batches']}")
    served.labels.clear()
    served._tags.clear()
    served.pool = pool
    probe = CompileProbe()
    svc = served.svc
    svc.start_batched()
    c0 = served.counters()
    snap = probe.snapshot()

    def submit(req, slot):
        return served.submit(req, pool[slot])

    timer = None
    t0 = time.perf_counter()
    if trace_dir is not None:
        # the window's last seconds only: a trace of the whole window
        # would be hundreds of MB, and writing it out happens after the
        # window has closed
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        timer = threading.Timer(
            seconds - min(TRACE_S, seconds / 2), jax.profiler.start_trace,
            (str(trace_dir),), {"profiler_options": opts})
        timer.start()
    setup_s = t0 - t_start
    if mix["loop"] == "closed":
        reqs = loadgen.closed_loop(submit, len(pool), int(mix["clients"]),
                                   t0, seconds)
    elif mix["loop"] == "open":
        offsets = loadgen.arrival_offsets(float(mix["rate"]), seconds, seed)
        reqs = loadgen.open_loop(submit, len(pool), offsets, t0)
    else:
        raise CellError(f"unknown loop {mix['loop']!r}")
    left = t0 + seconds - time.perf_counter()
    if left > 0:
        time.sleep(left)
    compiles = probe.since(snap)
    c1 = served.counters()
    if timer is not None:
        timer.join()
        jax.profiler.stop_trace()
    reqs.wait_all(DRAIN_S)
    svc.stop_batched()
    batches = svc.stats["batching"]["batches"]
    hw = buckets[-1]
    return Window(
        seconds=seconds, setup_s=setup_s, t0=t0, reqs=reqs,
        counters={k: c1[k] - c0[k] for k in c0},
        batches=batches[int(c0["batches"]):int(c1["batches"])],
        compiles=compiles,
        convs=served.ref.layer_list(served.arch, hw),
        fields=served.fields)


def failures(reqs: loadgen.Requests) -> int:
    """Requests that raised or never resolved."""
    bad = 0
    for f in reqs.futures:
        if not f.done() or f.exception() is not None:
            bad += 1
    return bad


def compare(served: Served, win: Window, seed: int, *,
            free: bool = True) -> Dict[str, float]:
    """The served answers of a sample of finished requests against the
    reference: the widest decision gap, and boxes that differ from their
    own label map.  The sample is drawn from the seed and holds the
    largest image of the pool."""
    import jax

    reqs, pool = win.reqs, served.pool
    done = [i for i in range(len(reqs.due))
            if reqs.futures[i].done() and reqs.futures[i].exception() is None
            and i in served.labels]
    if not done:
        return {"decision_gap": 1.0, "box_mismatches": 0, "sampled": 0}
    rng = np.random.default_rng(seed)
    big = max(done, key=lambda i: pool[reqs.slot[i]].shape[0]
              * pool[reqs.slot[i]].shape[1])
    rest = [i for i in done if i != big]
    pick = [big] + sorted(rng.choice(rest, min(SAMPLE - 1, len(rest)),
                                     replace=False).tolist())
    thr = served.svc.head.score_thr, served.svc.head.link_thr
    boxes = {i: reqs.futures[i].result() for i in pick}
    labels = {i: np.asarray(served.labels[i]) for i in pick}
    raw, arch, ref = served.raw, served.arch, served.ref
    if free:
        # free the program's state before the reference runs
        served.svc = None
        served.labels = {}
        gc.collect()
    if served.fwd is None:
        served.fwd = jax.jit(lambda p, x: ref.forward(p, x, arch))
    fwd = served.fwd
    gap, mismatches = 0.0, 0
    by_plane: Dict[tuple, List[int]] = {}
    for i in pick:
        by_plane.setdefault(labels[i].shape, []).append(i)
    for hw, group in sorted(by_plane.items()):
        for j in range(0, len(group), REF_BLOCK):
            block = group[j:j + REF_BLOCK]
            # the padded plane the service ran, zeros past the image
            x = np.zeros((REF_BLOCK, hw[0] * 4, hw[1] * 4, 3), np.float32)
            for k, i in enumerate(block):
                img = pool[reqs.slot[i]]
                x[k, :img.shape[0], :img.shape[1]] = img
            score, links = map(np.asarray, fwd(raw, x))
            for k, i in enumerate(block):
                img = pool[reqs.slot[i]]
                vq = (img.shape[0] // 4, img.shape[1] // 4)
                gap = max(gap, check.decision_gap(score[k], links[k],
                                                  labels[i], vq, *thr))
                want = check.decode_boxes(labels[i], vq)
                got = [dict(b, box=tuple(b["box"])) for b in boxes[i]]
                mismatches += got != want
    return {"decision_gap": gap, "box_mismatches": mismatches,
            "sampled": len(pick)}


def judge(cell: Cell, got: Dict[str, float], failed: int):
    """Each compared number beside its limit, and whether all hold."""
    checks = {
        "decision_gap": {"value": got["decision_gap"],
                         "limit": cell.config["limits"]["decision_gap"]},
        "box_mismatches": {"value": got["box_mismatches"], "limit": 0},
        "failed": {"value": failed, "limit": 0},
    }
    return checks, all(c["value"] <= c["limit"] for c in checks.values())


def memory_peak_bytes() -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


def run_cell(cell: Cell, seed: int, seconds: float, device: Dict, *,
             t_start: float, trace_dir: Optional[Path] = None,
             log=print, config_override: Optional[Dict] = None) -> Dict:
    """One run of one cell: the result line as a dict.  ``device`` is
    what JAX reported (the caller has checked the platform)."""
    from chipbench import trace as trace_lib

    peak = yardstick.peaks(device["kind"])
    served = Served(cell, config_override=config_override)
    win = run_window(served, cell, seed, seconds, t_start=t_start,
                     trace_dir=trace_dir, log=log)
    win.peak = peak
    reqs = win.reqs
    give_up = time.perf_counter()
    win.latencies_ms, unfinished = loadgen.latencies_ms(reqs, give_up)
    failed = failures(reqs)
    compiles, hits, _, fresh = win.compiles
    log(f"window compiles={compiles} cache_loads={hits} "
        f"fresh={dict(fresh)}")
    late = [(s - d) * 1e3 for s, d in zip(reqs.sent, reqs.due)]
    log(f"generator lateness_ms p50={loadgen.percentile(late, 50):.4f} "
        f"p99={loadgen.percentile(late, 99):.4f} max={max(late):.4f}")
    sizes: Dict[int, int] = {}
    for b in win.batches:
        sizes[b["n"]] = sizes.get(b["n"], 0) + 1
    log(f"batches live_sizes={dict(sorted(sizes.items()))} "
        f"attempted={len(reqs.due)} unfinished={unfinished} "
        f"failed={failed} setup_s={win.setup_s:.4f}")
    dev = dict(device, memory_peak_bytes=memory_peak_bytes())
    if trace_dir is not None:
        win.trace = trace_lib.reduce(trace_dir, win, log=log)
        dev["busy_s"] = win.trace["busy_s"]
        dev["window_s"] = win.trace["window_s"]
    kind = "per_layer" if trace_dir is not None else "end_to_end"
    metrics = {}
    for m in cell.metrics:
        if m["kind"] != kind:
            continue
        v = read_metric(cell.root, m["name"], win)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    got = compare(served, win, seed)
    checks, correct = judge(cell, got, failed)
    log(f"sampled {got['sampled']} answers")
    for name, c in checks.items():
        log(f"check {name} value={c['value']} limit={c['limit']}")
    out = {"correct": correct, "attempted": len(reqs.due), "failed": failed,
           "metrics": metrics, "device": dev}
    if trace_dir is not None:
        out["breakdown"] = win.trace["breakdown"]
    out["checks"] = checks
    return out
