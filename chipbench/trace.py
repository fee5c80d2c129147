"""Profiler trace of a window's last seconds -> the numbers the device
readers take: busy time and idle share, each engine step (``jit_run``
module executions) with its live and padded batch, the summed time of
each Pallas kernel, the longest idle gaps by what the host was doing.

It reads the trace viewer's JSON (``<host>.trace.json.gz``, which the
profiler writes beside the ``.xplane.pb``): one process per plane
(``/device:TPU:0``, ``/host:CPU``), one thread per line (``XLA Ops``,
``XLA Modules``; a host thread each), complete events with ``ts`` and
``dur`` in microseconds.  Host spans are the harness's own
``chipbench.*`` annotations around its calls into the service.

A step's batch comes from the host: the harness annotates each dispatch
with its live and padded batch, and the dispatch's launch event carries
the ``run_id`` that its module execution on the device carries too.
The kernel launches counted inside each complete step must equal what
the model's layer list predicts; anything else raises.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

#: the engine program: the service's jitted ``run``
STEP = re.compile(r"^jit_run\b")
KERNELS = ("bfp_matmul_quantized", "winograd_tile_matmul")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
US = 1e-6
#: a step within this many microseconds of either end of the device's
#: record was cut by the trace
EDGE_US = 1.0


class TraceError(RuntimeError):
    """The trace does not hold what the reduction needs."""


def base_name(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``jit_run(4242)`` -> ``jit_run``."""
    return re.sub(r"(\.\d+)+$|\(\d+\)$", "", name)


def load(trace_dir) -> Dict:
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.trace.json.gz"), recursive=True))
    if not files:
        raise TraceError(f"no *.trace.json.gz under {trace_dir}")
    with gzip.open(files[-1], "rt") as f:
        return json.load(f)


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _overlap(a0, a1, spans) -> float:
    return sum(max(0.0, min(a1, b1) - max(a0, b0)) for b0, b1 in spans)


def align(dispatches, modules) -> Dict[int, Tuple[int, int]]:
    """run_id -> (live, padded) for the traced dispatches.

    ``dispatches``: the harness's ``chipbench.batch`` marks in time
    order, ``(t, live, padded)``; ``modules``: ``jit_run`` executions,
    ``(run_id, t0, name)``.  Within the window every execution is an
    engine step, so run ids count dispatches one by one; what is not
    known is which run id the first traced dispatch got (a few dispatched
    before the trace started still run in it).  A module's name carries
    its compiled program's fingerprint, one per padded batch, so the
    offset is the one under which fingerprints and padded batches pair
    one to one; among several (a run of equal batches), the one under
    which the most steps start after their dispatch's mark, then the
    smallest."""
    by_run = {r: (t0, name) for r, t0, name in modules}
    if not dispatches or not by_run:
        return {}
    best, best_key = None, None
    lo, hi = min(by_run) - len(dispatches), max(by_run)
    for first in range(lo, hi + 1):
        fp_to_b, b_to_fp, ok, n, causal = {}, {}, True, 0, 0
        for i, (t, live, padded) in enumerate(dispatches):
            m = by_run.get(first + i)
            if m is None:
                continue
            fp = m[1]
            if fp_to_b.setdefault(fp, padded) != padded or \
                    b_to_fp.setdefault(padded, fp) != fp:
                ok = False
                break
            n += 1
            causal += m[0] >= t
        if ok and n:
            key = (n, causal, -first)
            if best_key is None or key > best_key:
                best, best_key = first, key
    if best is None:
        raise TraceError("no alignment of dispatches to engine steps "
                         "pairs each compiled program with one batch")
    return {best + i: (live, padded)
            for i, (_, live, padded) in enumerate(dispatches)}


def reduce_events(trace: Dict,
                  want_calls: Optional[Dict[str, int]] = None) -> Dict:
    """The reduction proper (``reduce`` reads the file).  Each device's
    traced window runs from its first recorded operation to its last
    one's end (the host's record runs longer than the device's); an
    engine step the trace cut at either end is no step."""
    ev = trace["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in ev
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in ev
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    devices = sorted(p for p, n in procs.items()
                     if n.startswith("/device:TPU:"))
    if not devices:
        raise TraceError("no /device:TPU:* plane in the trace")
    ops = defaultdict(list)          # pid -> [(t0, t1, name)]
    modules = defaultdict(list)      # pid -> [(t0, t1, name, run_id)]
    spans: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    marks = []                       # (t, live, padded)
    for e in ev:
        if e.get("ph") != "X":
            continue
        t0 = float(e["ts"])
        t1 = t0 + float(e.get("dur", 0.0))
        line = threads.get((e["pid"], e.get("tid")), "")
        args = e.get("args") or {}
        if e["pid"] in devices:
            if line == OPS_LINE:
                ops[e["pid"]].append((t0, t1, e["name"]))
            elif line == MODULES_LINE and STEP.match(e["name"]):
                modules[e["pid"]].append((t0, t1, e["name"],
                                          int(args["run_id"])))
        elif e["name"] == "chipbench.batch":
            marks.append((t0, int(args["live"]), int(args["padded"])))
        elif e["name"].startswith("chipbench."):
            spans[e["name"]].append((t0, t1))
    marks.sort()

    busy, window, steps, kernels = 0.0, 0.0, [], defaultdict(float)
    by_name: Dict[str, float] = defaultdict(float)
    gaps_by: Dict[str, float] = defaultdict(float)
    for pid in devices:
        dev_ops = sorted(ops[pid])
        u = union([(a, b) for a, b, _ in dev_ops])
        busy += sum(b - a for a, b in u) * US
        for a, b, name in dev_ops:
            by_name[base_name(name)] += (b - a) * US
        if not u:
            raise TraceError(f"no operation recorded on {procs[pid]}")
        d0, d1 = u[0][0], u[-1][1]
        window += (d1 - d0) * US
        whole = [m for m in sorted(modules[pid])
                 if m[0] > d0 + EDGE_US and m[1] < d1 - EDGE_US]
        batch_of = align(marks, [(r, a, name) for a, _, name, r in whole])
        for a, b, name, run_id in modules[pid]:
            by_name[base_name(name)] += (b - a) * US
        for a, b, name, run_id in whole:
            inside = [(t, u_, n) for t, u_, n in dev_ops
                      if a <= t and u_ <= b and base_name(n) in KERNELS]
            calls = {k: sum(base_name(n) == k for _, _, n in inside)
                     for k in KERNELS}
            if want_calls is not None and calls != want_calls:
                raise TraceError(
                    f"step at {a} us runs kernels {calls}, the layer list "
                    f"predicts {want_calls}")
            if run_id in batch_of:
                live, padded = batch_of[run_id]
                steps.append({"seconds": (b - a) * US, "live": live,
                              "batch": padded})
                for t, u_, n in inside:
                    kernels[base_name(n)] += (u_ - t) * US
        # idle gaps inside the traced window, by the host span that
        # overlaps each gap most
        edges = [x for iv in u for x in iv]
        for g0, g1 in zip(edges[1::2], edges[2::2]):
            best, who = 0.0, "no host span"
            for name, sp in spans.items():
                o = _overlap(g0, g1, sp)
                if o > best:
                    best, who = o, name
            gaps_by[who] += (g1 - g0) * US
    n = len(devices)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy / n,
        "window_s": window / n,
        "steps": steps,
        "kernels": {k: v / n for k, v in kernels.items()},
        "breakdown": {
            "device_ops": [[k, v / n] for k, v in top],
            "idle_gaps": [[k, v / n] for k, v in sorted(
                gaps_by.items(), key=lambda kv: -kv[1])[:10]],
        },
    }


def reduce(trace_dir, win, log=print) -> Dict:
    """Reduce the window's trace; the kernel launches per step are held
    to what the served model's layer list predicts (BFP path only)."""
    from chipbench import yardstick

    want = (yardstick.kernel_calls(win.convs)
            if win.fields.get("bfp") is not None else None)
    out = reduce_events(load(trace_dir), want)
    log(f"trace window_s={out['window_s']:.4f} busy_s={out['busy_s']:.4f} "
        f"steps={len(out['steps'])} kernels={out['kernels']}")
    return out
