"""Share of the engine step's device time spent in the Winograd 3x3
path's layout work in XLA (the pad, the tile gather, the input and
weight transforms, the padding to the kernel's blocks, the output's
untiling and crop): ops under the program's ``winograd_io`` scope over
the complete steps' device time (chipbench/stages).

``chipbench/stages`` counts an op for its innermost scope among the
ones it knows, which ``winograd_io`` is not one of (it reads as the
word's ``conv3x3``); so this reader sums the own time of the steps' ops
whose scope path holds ``winograd_io`` itself.  None where no op does
(a program without the scope)."""
from chipbench import stages
from chipbench import trace as trace_lib

SCOPE = "winograd_io"


def under(path: str) -> bool:
    return SCOPE in path.split("/")


def seconds_under(trace) -> float:
    """Device seconds of the complete steps' ops under the scope, per
    device: each op's own time (less the ops nested in it)."""
    ev = trace["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in ev
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in ev
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    devices = [p for p, n in procs.items() if n.startswith("/device:TPU:")]
    total = 0.0
    for pid in devices:
        ops, modules = [], []
        for e in ev:
            if e.get("ph") != "X" or e["pid"] != pid:
                continue
            t0 = float(e["ts"])
            t1 = t0 + float(e.get("dur", 0.0))
            line = threads.get((pid, e.get("tid")), "")
            if line == trace_lib.OPS_LINE:
                ops.append((t0, t1, under((e.get("args") or {})
                                          .get("tf_op", ""))))
            elif line == trace_lib.MODULES_LINE and \
                    trace_lib.STEP.match(e["name"]):
                modules.append((t0, t1))
        ops.sort()
        u = trace_lib.union([(a, b) for a, b, _ in ops])
        if not u:
            continue
        d0, d1 = u[0][0], u[-1][1]
        for a, b in modules:
            if not (a > d0 + trace_lib.EDGE_US and b < d1 - trace_lib.EDGE_US):
                continue                     # cut by the trace: no step
            inside = [o for o in ops if a <= o[0] and o[1] <= b]
            total += sum(own for own, hit in stages._self_time(inside)
                         if hit) * trace_lib.US
    return total / max(len(devices), 1)


def read(win):
    st = stages.of(win)
    if not st or st["step_s"] <= 0:
        return None
    if "winograd_io_s" not in win.trace:
        win.trace["winograd_io_s"] = seconds_under(
            trace_lib.load(stages.trace_dir()))
    got = win.trace["winograd_io_s"]
    if got <= 0:
        return None
    return 100.0 * got / st["step_s"]
