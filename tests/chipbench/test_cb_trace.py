"""The trace reduction, on a hand-built trace and on a trimmed trace
recorded on the chip (``data/r50_steps.trace.json.gz``: a few engine
steps of ``r50-photo-sat``, bfp, with the harness's host spans), against
values worked out by hand."""
import gzip
import json
from pathlib import Path

import pytest

import _cb_paths  # noqa: F401
from chipbench import trace

DATA = Path(__file__).parent / "data"


def meta(pid, name, tid=None, thread=None):
    if tid is None:
        return {"ph": "M", "pid": pid, "name": "process_name",
                "args": {"name": name}}
    return {"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
            "args": {"name": thread}}


def x(pid, tid, name, ts, dur, **args):
    e = {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
         "dur": dur}
    if args:
        e["args"] = {k: str(v) for k, v in args.items()}
    return e


def hand_trace(kernels_in_step2=2):
    """Two steps on one TPU (5-45 and 55-95 us) between a fusion at 0-2
    and one at 96-99: ops 10-20, 25-40 in step 1 and 60-70, 72-90 in
    step 2.  The host marks each dispatch with its batch."""
    ev = [meta(1, "/device:TPU:0"), meta(1, "", 1, "XLA Ops"),
          meta(1, "", 2, "XLA Modules"), meta(2, "/host:CPU"),
          meta(2, "", 7, "python")]
    ev += [x(1, 2, "jit_run(77)", 5, 40, run_id=11),
           x(1, 2, "jit_run(77)", 55, 40, run_id=12)]
    ev += [x(1, 1, "fusion.1", 0, 2),
           x(1, 1, "bfp_matmul_quantized.1", 10, 10),
           x(1, 1, "fusion.3", 25, 15),
           x(1, 1, "bfp_matmul_quantized.1", 60, 10),
           x(1, 1, "winograd_tile_matmul", 72, 18),
           x(1, 1, "fusion.7", 96, 3)]
    if kernels_in_step2 == 1:
        ev[-2]["name"] = "fusion.9"
    for k, t in enumerate((0, 50)):
        ev += [x(2, 7, "chipbench.dispatch", t, 4),
               x(2, 7, "chipbench.batch", t + 2.5, 0.5, live=3 + k,
                 padded=4)]
    ev += [x(2, 7, "chipbench.post", 42, 12)]
    return {"traceEvents": ev}


def test_hand_trace():
    want = {"bfp_matmul_quantized": 1, "winograd_tile_matmul": 0}
    with pytest.raises(trace.TraceError, match="layer list predicts"):
        trace.reduce_events(hand_trace(), want)
    out = trace.reduce_events(hand_trace(kernels_in_step2=1), want)
    assert out["window_s"] == pytest.approx(99e-6)        # 0 .. 99 us
    assert out["busy_s"] == pytest.approx((2 + 10 + 15 + 10 + 18 + 3)
                                          * 1e-6)
    assert out["steps"] == [
        {"seconds": pytest.approx(40e-6), "live": 3, "batch": 4},
        {"seconds": pytest.approx(40e-6), "live": 4, "batch": 4}]
    assert out["kernels"]["bfp_matmul_quantized"] == pytest.approx(20e-6)
    gaps = dict(out["breakdown"]["idle_gaps"])
    # idle: 2-10 (dispatch 0-4 overlaps), 20-25, 40-60 (post 42-54
    # overlaps most), 70-72, 90-96
    assert gaps["chipbench.dispatch"] == pytest.approx(8e-6)
    assert gaps["chipbench.post"] == pytest.approx(20e-6)
    assert gaps["no host span"] == pytest.approx(13e-6)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["jit_run"] == pytest.approx(80e-6)
    assert ops["fusion"] == pytest.approx(38e-6)


def test_a_step_cut_by_the_trace_is_no_step():
    tr = hand_trace(kernels_in_step2=1)
    tr["traceEvents"] = [e for e in tr["traceEvents"]
                         if e.get("name") != "fusion.1"]      # d0 = 10
    out = trace.reduce_events(tr, {"bfp_matmul_quantized": 1,
                                   "winograd_tile_matmul": 0})
    assert [s["live"] for s in out["steps"]] == [4]


def test_recorded_trace():
    """Three whole steps of ``r50-photo-sat`` (run ids 176-178: batches
    1, 4 and 8 from their dispatch marks), worked out by hand from the
    file: the ops' union, each module's duration, the kernel events
    inside each module."""
    tr = json.loads(gzip.decompress(
        (DATA / "r50_steps.trace.json.gz").read_bytes()))
    out = trace.reduce_events(tr, {"bfp_matmul_quantized": 40,
                                   "winograd_tile_matmul": 17})
    assert out["window_s"] == pytest.approx(859963.70375e-6
                                            - 445127.89625e-6)
    assert out["busy_s"] == pytest.approx(155015.765858e-6)
    assert out["steps"] == [
        {"seconds": pytest.approx(9605.98875e-6), "live": 1, "batch": 1},
        {"seconds": pytest.approx(40515.400078e-6), "live": 3, "batch": 4},
        {"seconds": pytest.approx(90508.551094e-6), "live": 5, "batch": 8}]
    assert out["kernels"]["bfp_matmul_quantized"] == pytest.approx(
        (2832.625702 + 11335.632734 + 22691.884844) * 1e-6)
    assert out["kernels"]["winograd_tile_matmul"] == pytest.approx(
        (435.49836 + 1516.412188 + 2957.599218) * 1e-6)
    names = [n for n, _ in out["breakdown"]["device_ops"]]
    assert {"jit_run", "bfp_matmul_quantized",
            "winograd_tile_matmul"} <= set(names)
    assert out["breakdown"]["idle_gaps"][0][0] == "chipbench.complete"
    with pytest.raises(trace.TraceError, match="layer list predicts"):
        trace.reduce_events(tr, {"bfp_matmul_quantized": 39,
                                 "winograd_tile_matmul": 17})
