"""The program's own marks in a traced window: the engine step's device
time by named scope, the device's idle gaps by the program stage that
held the host, and the completion stage's fetch walls.

It reads the same trace viewer JSON as ``chipbench/trace.py`` (and its
steps: ``jit_run`` executions the trace did not cut), and two things the
program puts there itself:

* named scopes: each XLA op carries the scope path it was traced under
  in its ``tf_op`` arg (``jit(run)/w012.conv1x1/bfp_roundtrip/...``);
  an op counts for its innermost scope among the interpreter's per-word
  ``w<idx>.<kind>`` scopes and the inner ``bfp_roundtrip``,
  ``bfp_matmul_io``, ``cc_tail`` and ``boxes``, else for ``unscoped``
  (XLA's own copies and async starts carry none);
* spans: ``std.*`` host annotations from ``runtime/telemetry.span``
  (``std.dispatch``, ``std.complete.fetch``, ...).  A gap in the
  device's record goes to the span whose own time (its interval less
  the spans nested in it) overlaps it most; ``std.complete.wait`` is the
  host waiting on the device and claims no gap.

A program without scopes or spans yields ``unscoped`` and
``no program span`` only, and the readers built on it read nothing.
The result is computed once per window and kept in its ``trace`` dict:
``step_scopes`` and ``idle_by_stage`` join the run's ``breakdown``.
"""
from __future__ import annotations

import bisect
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from chipbench import trace as trace_lib

#: scopes nested inside a word's, which win over it
INNER = ("bfp_roundtrip", "bfp_matmul_io", "cc_tail", "boxes")
SCOPE = re.compile(r"(?<![\w.])(w\d{3,}\.([a-z0-9_]+)|"
                   + "|".join(INNER) + r")(?!\w)")
UNSCOPED = "unscoped"
NO_SPAN = "no program span"
#: spans in which the host waits on the device: they claim no gap
WAITS = ("std.complete.wait",)
FETCH = "std.complete.fetch"


def scope_of(path: str) -> str:
    """The innermost named scope of a scope path, or unscoped."""
    best = None
    for best in SCOPE.finditer(path):
        pass
    if best is None:
        return UNSCOPED
    return best.group(2) or best.group(1)




def _self_time(ops) -> List[Tuple[float, str]]:
    """``(t0, t1, label)`` -> ``(own time, label)``: each event's
    duration less that of the events nested in it (a ``while`` holds its
    body's ops; events never overlap partially)."""
    out, stack = [], []          # stack: [t0, t1, label, nested]
    for a, b, lab in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= a:
            t0, t1, l0, inner = stack.pop()
            out.append((t1 - t0 - inner, l0))
        if stack:
            stack[-1][3] += b - a
        stack.append([a, b, lab, 0.0])
    out.extend((t1 - t0 - inner, l0) for t0, t1, l0, inner in stack)
    return out


def _minus(iv: Tuple[float, float], holes) -> List[Tuple[float, float]]:
    a, b = iv
    out = []
    for h0, h1 in trace_lib.union(holes):
        if h0 > a:
            out.append((a, min(h0, b)))
        a = max(a, h1)
        if a >= b:
            break
    if a < b:
        out.append((a, b))
    return out


def span_own_intervals(spans) -> Dict[str, List[Tuple[float, float]]]:
    """``(thread, t0, t1, name)`` -> name -> the spans' own intervals:
    each span's interval less those of the spans nested in it on its
    thread."""
    own: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    by_thread = defaultdict(list)
    for th, a, b, name in spans:
        by_thread[th].append((a, b, name))
    for evs in by_thread.values():
        evs.sort(key=lambda e: (e[0], -e[1]))
        stack: List[list] = []   # [t0, t1, name, children]
        for a, b, name in evs + [(float("inf"), float("inf"), "")]:
            while stack and stack[-1][1] <= a:
                t0, t1, top, inner = stack.pop()
                own[top].extend(_minus((t0, t1), inner))
            if stack:
                stack[-1][3].append((a, b))
            stack.append([a, b, name, []])
    return own


def reduce_events(trace: Dict) -> Dict:
    """``step_scopes`` (device seconds per scope kind over the complete
    steps, largest first), ``step_s`` (their device time), ``scoped_s``
    (the part under a named scope), ``idle_by_stage`` (idle seconds by
    span), and ``fetch`` (the ``std.complete.fetch`` walls: seconds,
    count)."""
    ev = trace["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in ev
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in ev
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    devices = sorted(p for p, n in procs.items()
                     if n.startswith("/device:TPU:"))
    if not devices:
        raise trace_lib.TraceError("no /device:TPU:* plane in the trace")
    ops = defaultdict(list)          # pid -> [(t0, t1, scope)]
    modules = defaultdict(list)      # pid -> [(t0, t1)]
    spans = []                       # (thread, t0, t1, name)
    fetch_s, fetch_n = 0.0, 0
    for e in ev:
        if e.get("ph") != "X":
            continue
        t0 = float(e["ts"])
        t1 = t0 + float(e.get("dur", 0.0))
        line = threads.get((e["pid"], e.get("tid")), "")
        if e["pid"] in devices:
            if line == trace_lib.OPS_LINE:
                ops[e["pid"]].append(
                    (t0, t1, scope_of((e.get("args") or {}).get("tf_op", ""))))
            elif line == trace_lib.MODULES_LINE and \
                    trace_lib.STEP.match(e["name"]):
                modules[e["pid"]].append((t0, t1))
        elif e["name"].startswith("std."):
            spans.append(((e["pid"], e.get("tid")), t0, t1, e["name"]))
            if e["name"] == FETCH:
                fetch_s += (t1 - t0) * trace_lib.US
                fetch_n += 1
    # per span name, the time some thread spent in it: disjoint, sorted
    own = {k: trace_lib.union(v)
           for k, v in span_own_intervals(spans).items() if k not in WAITS}
    starts = {k: [a for a, _ in v] for k, v in own.items()}

    scopes: Dict[str, float] = defaultdict(float)
    gaps_by: Dict[str, float] = defaultdict(float)
    for pid in devices:
        dev_ops = sorted(ops[pid])
        u = trace_lib.union([(a, b) for a, b, _ in dev_ops])
        if not u:
            raise trace_lib.TraceError(
                f"no operation recorded on {procs[pid]}")
        d0, d1 = u[0][0], u[-1][1]
        edge = trace_lib.EDGE_US
        for a, b in modules[pid]:
            if not (a > d0 + edge and b < d1 - edge):
                continue                     # cut by the trace: no step
            inside = [o for o in dev_ops if a <= o[0] and o[1] <= b]
            for own_us, scope in _self_time(inside):
                scopes[scope] += own_us * trace_lib.US
        edges = [x for iv in u for x in iv]
        for g0, g1 in zip(edges[1::2], edges[2::2]):
            best, who = 0.0, NO_SPAN
            for name, iv in own.items():
                o = 0.0
                for a, b in iv[max(0, bisect.bisect_right(starts[name], g0)
                                   - 1):bisect.bisect_left(starts[name], g1)]:
                    o += max(0.0, min(g1, b) - max(g0, a))
                if o > best:
                    best, who = o, name
            gaps_by[who] += (g1 - g0) * trace_lib.US
    n = len(devices)
    step_s = sum(scopes.values()) / n
    return {
        "step_scopes": [[k, v / n] for k, v in sorted(
            scopes.items(), key=lambda kv: -kv[1])],
        "step_s": step_s,
        "scoped_s": step_s - scopes.get(UNSCOPED, 0.0) / n,
        "idle_by_stage": [[k, v / n] for k, v in sorted(
            gaps_by.items(), key=lambda kv: -kv[1])],
        "fetch": {"seconds": fetch_s, "count": fetch_n},
    }


def trace_dir():
    from chipbench.run import WORK

    return WORK / "trace"


def of(win) -> Optional[Dict]:
    """The reduction of the window's trace, computed on first use and
    kept in ``win.trace`` (its two lists also in the run's breakdown);
    None for an untraced window."""
    t = win.trace
    if not t:
        return None
    if "stages" not in t:
        out = reduce_events(trace_lib.load(trace_dir()))
        t["stages"] = out
        t["breakdown"]["step_scopes"] = out["step_scopes"]
        t["breakdown"]["idle_by_stage"] = out["idle_by_stage"]
        print(f"trace step_scopes={out['step_scopes']}\n"
              f"trace idle_by_stage={out['idle_by_stage']}",
              file=sys.stderr, flush=True)
    return t["stages"]


def scope_share(win, scope: str) -> Optional[float]:
    """Percent of the complete steps' device time under ``scope``; None
    where no op of the steps carries a named scope."""
    st = of(win)
    if not st or st["scoped_s"] <= 0:
        return None
    got = dict(st["step_scopes"]).get(scope, 0.0)
    return 100.0 * got / st["step_s"]
