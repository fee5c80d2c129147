#!/usr/bin/env python3
"""Chip smoke test: serve the paper's deployed scene-text detector on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the multi-chip phase only

One chip: ``configs/pixellink_std.RESNET50`` (ResNet-50, 512x512, full
width, random seeded weights) is served through ``STDService.submit`` at
``precision="f32"`` and ``"bfp"`` with buckets (256, 512), ``max_batch``
8 and host postprocess.  Checks:

  * every request comes back, and each one's boxes equal what the
    synchronous path returns for the same batch (same executable);
  * the f32 maps pass ``precision_parity_gate`` (eps 0.05) against a
    ``mode="reference"`` engine of the same weights run under
    ``jax.default_matmul_precision("highest")``, and the bfp maps pass it
    against the f32 maps;
  * the Pallas CC kernel's boxes on the f32 maps equal the NumPy
    union-find oracle's;
  * each compiled engine holds the Pallas kernels it should, as
    ``tpu_custom_call`` instructions: the CC tail's, plus the Winograd
    and BFP kernels on bfp; and the served window runs no fresh backend
    compile (only persistent-cache loads, if any).

Four chips (``--chips 4``): the same requests served through
``STDService.submit`` with ``plan=DataParallel`` over 4 chips and
``plan=GridPlan`` on a 2x2 (data x model) mesh, box for box against a
``SingleDevice`` service; then four one-chip replicas behind
``launch/router.Router``, each pinned to its own chip, against an
unpinned service.

Earlier lines report compile seconds per engine, persistent-cache
entries, hits and writes, the fresh compiles by function, max |delta|
per precision and boxes per request.  The
last line is ``{"ok": true, "device": {...}}`` only when every check
passed on a TPU; otherwise the script exits non-zero without it.  One
process drives the chips; it starts no other.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import threading
import time
from collections import Counter
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

EPS = 0.05                 # precision_parity_gate budget (never widened)
WAIT_MS = 60_000.0         # flush only on full batches: fixed composition
GATE_BATCH = 2             # requests whose planes the parity gates compare
SEED = 0                   # request images


def log(*parts) -> None:
    print(*parts, flush=True)


class Checks:
    """Named pass/fail results; every failure is printed as it happens."""

    def __init__(self):
        self.results = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        ok = bool(ok)
        self.results.append((name, ok))
        log(f"check {name}: {'pass' if ok else 'FAIL'}"
            + (f" ({detail})" if detail else ""))
        return ok

    @property
    def ok(self) -> bool:
        return bool(self.results) and all(ok for _, ok in self.results)


class CompileProbe:
    """Backend compile requests, persistent-cache hits and writes, from
    JAX's own monitoring events (one set of listeners per process).  JAX
    times a cache load as a backend compile too; a compile whose thread
    saw a cache hit between its start and end was a load, every other one
    is fresh and is counted under its function's name in ``fresh``."""

    _instance = None
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __new__(cls):
        if cls._instance is None:
            import jax.monitoring as mon

            self = super().__new__(cls)
            self.compiles = self.hits = self.writes = 0
            self.fresh = Counter()
            self._lock = threading.Lock()
            self._local = threading.local()
            mon.register_scalar_listener(self._start)
            mon.register_event_duration_secs_listener(self._end)
            mon.register_event_listener(self._event)
            cls._instance = self
        return cls._instance

    def _start(self, event, value, **kw):
        if event == self.COMPILE:
            self._local.hit = False

    def _end(self, event, duration_secs, fun_name="?", **kw):
        if event == self.COMPILE:
            with self._lock:
                self.compiles += 1
                if not getattr(self._local, "hit", False):
                    self.fresh[fun_name] += 1

    def _event(self, event, **kw):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
                self._local.hit = True
            elif event == "/jax/compilation_cache/cache_misses":
                self.writes += 1

    def snapshot(self):
        """(compiles, hits, writes, fresh compiles by function name)."""
        with self._lock:
            return self.compiles, self.hits, self.writes, Counter(self.fresh)

    def since(self, snap):
        """Compiles, hits, writes and fresh compiles by name after ``snap``."""
        now = self.snapshot()
        return (*(a - b for a, b in zip(now[:3], snap[:3])),
                now[3] - snap[3])


def device_info() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def base_config(width: float):
    from repro.configs.pixellink_std import RESNET50

    return dataclasses.replace(RESNET50, width=width)


def request_images(n: int, sides, seed: int):
    from repro.data.images import RequestStream

    lo, hi = sides
    return RequestStream(n, seed=seed, hw_range=((lo, hi), (lo, hi))).images()


def pallas_kernels(hlo_text: str) -> dict:
    """Pallas TPU kernels in a compiled program: the name of each
    ``tpu_custom_call`` instruction (its kernel's jitted wrapper, e.g.
    ``local_spread_converge``) -> count."""
    names = re.findall(r'%([\w.-]+) = [^\n]*custom_call_target='
                       r'"tpu_custom_call"', hlo_text)
    return dict(Counter(re.sub(r"\.\d+$", "", n) for n in names))


def compile_engine(svc, hw, batch, plan, tag: str) -> dict:
    """AOT-compile one served engine (the executable its calls then run);
    report seconds, backend compiles, cache hits and the Pallas TPU
    kernels the program holds, which it returns."""
    import jax
    import jax.numpy as jnp

    probe = CompileProbe()
    fn = svc.factory.plan_fn(hw, batch, plan, svc.precision, svc.model_name)
    params = svc.factory.params(hw, svc.precision, svc.model_name)
    x = jax.ShapeDtypeStruct((batch, hw[0], hw[1], 3), jnp.float32)
    vq = jax.ShapeDtypeStruct((batch, 2), jnp.int32)
    snap = probe.snapshot()
    t0 = time.perf_counter()
    text = fn.lower(params, x, vq).compile().as_text()
    dt = time.perf_counter() - t0
    _, hits, _, fresh = probe.since(snap)
    kernels = pallas_kernels(text)
    log(f"compile {tag} bucket={hw[0]}x{hw[1]} batch={batch} "
        f"seconds={dt:.3f} cache_hits={hits} "
        f"fresh_compiles={fresh.total()} "
        f"tpu_custom_call={'tpu_custom_call' in text} kernels={kernels}")
    return kernels


def serve(svc, images, check, tag: str):
    """Submit every image through the micro-batcher; return the per-request
    boxes.  Checks that the served window ran no fresh backend compile
    (a persistent-cache load is not one), and each request against the
    synchronous path on the same batch."""
    probe = CompileProbe()
    snap = probe.snapshot()
    svc.start_batched()
    try:
        futures = [svc.submit(img) for img in images]
        served = [f.result(timeout=900) for f in futures]
    finally:
        svc.stop_batched()
    compiles, hits, _, fresh = probe.since(snap)
    check(f"{tag}_no_compile_in_window", not fresh,
          f"backend_compiles={compiles} cache_hits={hits} "
          f"fresh={dict(fresh)}")
    log(f"served {tag} requests={len(served)} boxes_per_request="
        f"{[len(b) for b in served]}")
    # per bucket, FIFO: each batch took the next requests in submit order
    queues = {}
    for k, img in enumerate(images):
        queues.setdefault(svc.preprocess(img)[0].shape[:2], []).append(k)
    rerun, same = 0, True
    for batch in svc.stats["batching"]["batches"]:
        q = queues[batch["key"]]
        ids, queues[batch["key"]] = q[:batch["n"]], q[batch["n"]:]
        pre = [svc.preprocess(images[k]) for k in ids]
        labels = svc.infer_labels(np.stack([p[0] for p in pre]),
                                  [p[1] for p in pre])
        for j, (k, (x, valid, tr)) in enumerate(zip(ids, pre)):
            want = svc.postprocess(labels[j], valid, tr,
                                   bucket_hw=tuple(x.shape[:2]))
            same &= ([b["box"] for b in want]
                     == [b["box"] for b in served[k]])
        rerun += len(ids)
    check(f"{tag}_served_matches_sync", same and rerun == len(images),
          f"{rerun}/{len(images)} requests re-run")
    return served


def maps_for(model, params, x, precision=None):
    import jax

    fn = jax.jit(model.apply)
    if precision is None:
        out = fn(params, x)
    else:
        with jax.default_matmul_precision(precision):
            out = fn(params, x)
    return out["score"], out["links"]


def phase_single(args, check) -> None:
    import jax.numpy as jnp

    from benchmarks.serve_bench import precision_parity_gate
    from repro.kernels.cc_label import cc_label_pallas
    from repro.launch.serve import STDService
    from repro.models.fcn import postprocess as pp
    from repro.models.fcn.heads import DetectionModel
    from repro.runtime.executor import SingleDevice

    cfg = base_config(args.width)
    on_tpu = device_info()["platform"] == "tpu"
    images = request_images(args.requests, args.sides, SEED)
    svcs, served = {}, {}
    for prec in ("f32", "bfp"):
        svc = STDService(config=cfg, buckets=tuple(args.buckets),
                         max_batch=args.max_batch, max_wait_ms=WAIT_MS,
                         precision=prec, postprocess="host")
        svcs[prec] = svc
        hws = sorted({svc.preprocess(img)[0].shape[:2] for img in images})
        # Pallas lowers to a TPU custom call on the chip only; off it the
        # kernels run interpreted, as plain HLO
        want = set()
        if on_tpu:
            want = {"local_spread_converge"} | (
                {"winograd_tile_matmul", "bfp_matmul_quantized"}
                if prec == "bfp" else set())
        for hw in hws:
            kernels = compile_engine(svc, hw, args.max_batch,
                                     SingleDevice(), prec)
            check(f"{prec}_engine_{hw[0]}_pallas_kernels",
                  set(kernels) == want, f"want {sorted(want)}")
        served[prec] = serve(svc, images, check, prec)

    # -- parity gates on the first requests' padded planes ------------------
    f32 = svcs["f32"]
    pre = [f32.preprocess(img) for img in images[:GATE_BATCH]]
    hw = pre[0][0].shape[:2]
    pre = [p for p in pre if p[0].shape[:2] == hw]
    x = jnp.asarray(np.stack([p[0] for p in pre]))
    m_f32 = f32.factory.model(hw, "f32")
    p_f32 = f32.factory.params(hw, "f32")
    m_ref = DetectionModel(dataclasses.replace(m_f32.cfg, mode="reference"),
                           m_f32.head)
    bfp = svcs["bfp"]
    with ThreadPoolExecutor(3) as ex:
        jobs = {
            "ref": ex.submit(maps_for, m_ref, p_f32, x, "highest"),
            "f32": ex.submit(maps_for, m_f32, p_f32, x),
            "bfp": ex.submit(maps_for, bfp.factory.model(hw, "bfp"),
                             bfp.factory.params(hw, "bfp"), x),
        }
        maps = {k: j.result() for k, j in jobs.items()}
    for k, (s, l) in maps.items():
        want = (x.shape[0], hw[0] // 4, hw[1] // 4)
        check(f"{k}_maps_finite", s.shape == want
              and l.shape == want + (8,)
              and bool(jnp.isfinite(s).all() & jnp.isfinite(l).all()),
              f"score {tuple(s.shape)} links {tuple(l.shape)}")
    for tag, a, b in (("f32_vs_reference", "ref", "f32"),
                      ("bfp_vs_f32", "f32", "bfp")):
        d, boxes_equal = precision_parity_gate(*maps[a], *maps[b], eps=EPS)
        log(f"parity {tag} max_abs_delta={d:.6g} eps={EPS} "
            f"boxes_equal={boxes_equal}")
        check(f"parity_{tag}", 0 < d < EPS and boxes_equal)

    # -- the CC kernel on the chip against the NumPy oracle ------------------
    score, links = maps["f32"]
    h, w = score.shape[1:]
    valid = np.zeros(score.shape, bool)
    for i, (_, (vh, vw), _) in enumerate(pre):
        valid[i, :vh // 4, :vw // 4] = True
    labels = np.asarray(cc_label_pallas(score, links, f32.head.score_thr,
                                        f32.head.link_thr,
                                        valid_mask=jnp.asarray(valid)))
    same = True
    for i, (_, vhw, _) in enumerate(pre):
        got = pp.boxes_from_labels(f32.head._crop_q(labels[i], vhw))
        want = f32.head.reference_decode(
            {"score": np.asarray(score[i]), "links": np.asarray(links[i])},
            vhw)
        same &= (sorted(b["box"] for b in got)
                 == sorted(b["box"] for b in want))
    check("cc_kernel_matches_numpy_oracle", same,
          f"{len(pre)} planes {h}x{w}")


def phase_multi(args, check) -> None:
    import jax

    from repro.launch.mesh import make_mesh
    from repro.launch.router import Router, ServiceReplica
    from repro.launch.serve import STDService
    from repro.runtime.executor import DataParallel, GridPlan, SingleDevice

    devices = jax.devices()
    if len(devices) < 4:
        check("four_devices", False, f"{len(devices)} devices")
        return
    cfg = base_config(args.width)
    images = request_images(args.max_batch, args.sides, SEED)
    plans = {
        "single_device": SingleDevice(),
        "data_parallel4": DataParallel(make_mesh((4, 1), ("data", "model"))),
        "grid2x2": GridPlan(make_mesh((2, 2), ("data", "model"))),
    }
    svcs = {name: STDService(config=cfg, buckets=tuple(args.buckets),
                             max_batch=args.max_batch, max_wait_ms=WAIT_MS,
                             precision="f32", plan=plan)
            for name, plan in plans.items()}
    hws = sorted({svcs["single_device"].preprocess(img)[0].shape[:2]
                  for img in images})
    with ThreadPoolExecutor(len(plans)) as ex:
        list(ex.map(lambda name: [
            compile_engine(svcs[name], hw, args.max_batch, plans[name], name)
            for hw in hws], plans))
    boxes = {name: [[bx["box"] for bx in r]
                    for r in serve(svc, images, check, name)]
             for name, svc in svcs.items()}
    for name in ("data_parallel4", "grid2x2"):
        check(f"{name}_boxes_equal_single_device",
              boxes[name] == boxes["single_device"])

    # -- four one-chip replicas behind the router ---------------------------
    reps = [ServiceReplica(f"r{i}", STDService(
        config=cfg, buckets=tuple(args.buckets), max_batch=1,
        max_wait_ms=WAIT_MS, precision="f32", device=d))
        for i, d in enumerate(devices[:4])]
    router = Router(reps, policy="round_robin")
    router.start()
    try:
        futures = [router.submit(img) for img in images]
        served = [f.result(timeout=900) for f in futures]
    finally:
        router.stop()
    # committed parameters pull every engine call onto their device
    used = [sorted({str(d) for hw in hws for leaf in jax.tree.leaves(
        r.service.factory.params(hw, "f32")) for d in leaf.devices()})
        for r in reps]
    for r, u in zip(reps, used):
        log(f"replica {r.name} devices={u} "
            f"placed={router.stats['placed'][r.name]}")
    check("replicas_on_distinct_devices",
          all(len(u) == 1 for u in used)
          and len({u[0] for u in used}) == 4, str(used))
    # the same batch-1 engine, unpinned
    ref = STDService(config=cfg, buckets=tuple(args.buckets), max_batch=1,
                     precision="f32")
    want = [[bx["box"] for bx in ref(img)] for img in images]
    check("replica_boxes_equal_single_device",
          [[bx["box"] for bx in s] for s in served] == want)


def run(args) -> Checks:
    from repro.launch.compile_cache import cache_entries, use_compile_cache

    check = Checks()
    cache_dir = use_compile_cache()
    before = cache_entries(cache_dir)
    log(f"cache dir={cache_dir} entries_before={before}")
    probe = CompileProbe()
    snap = probe.snapshot()
    (phase_multi if args.chips == 4 else phase_single)(args, check)
    _, hits, writes, fresh = probe.since(snap)
    log(f"cache entries_before={before} "
        f"entries_after={cache_entries(cache_dir)} hits={hits} "
        f"writes={writes} fresh_compiles={fresh.total()}")
    # the fresh compiles the persistent cache does not keep: programs
    # under jax_persistent_cache_min_compile_time_secs, mostly eager ops
    log(f"fresh_compiles_by_function "
        f"{json.dumps(dict(fresh.most_common()), sort_keys=False)}")
    return check


def verdict(check: Checks, device: dict, chips: int) -> int:
    """Print the result line only for a TPU run whose checks all passed."""
    if device["platform"] != "tpu":
        log(f"no result: platform is {device['platform']!r}, not 'tpu'")
        return 2
    if device["count"] < chips:
        log(f"no result: {device['count']} devices, {chips} asked for")
        return 2
    if not check.ok:
        failed = [n for n, ok in check.results if not ok]
        log(f"no result: failed checks {failed}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the multi-chip phase")
    ap.add_argument("--width", type=float, default=1.0)
    ap.add_argument("--buckets", type=int, nargs="+", default=[256, 512])
    ap.add_argument("--sides", type=int, nargs=2, default=[300, 512])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    device = device_info()
    if device["platform"] != "tpu":
        return verdict(Checks(), device, args.chips)
    return verdict(run(args), device, args.chips)


if __name__ == "__main__":
    sys.exit(main())
