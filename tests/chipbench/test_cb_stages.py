"""The program-mark reduction (chipbench/stages.py) and the readers on
it (``quantize_share``, ``cc_tail_share``, ``fetch_ms``): a hand-built
trace worked out by hand, a trace recorded on the chip with the
program's spans and scopes (``data/r50_spans.trace.json.gz``), and one
recorded before the program had them (``data/r50_steps.trace.json.gz``),
on which the readers must read nothing and raise nothing."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import _cb_paths
from chipbench import harness, stages, trace
from test_cb_trace import meta, x

DATA = Path(__file__).parent / "data"
ROOT = Path(_cb_paths.ROOT)


def op(name, ts, dur, scope=None):
    return x(1, 1, name, ts, dur, **({"tf_op": scope} if scope else {}))


def hand_trace():
    """Device ops 0-2, steps 5-45 and 55-95, 96-99; inside step 1 a BFP
    round trip (10 us), the matmul kernel (5), an unscoped copy (5) and
    the CC tail's ``while`` (30-44) holding two body ops (4 + 4); inside
    step 2 a round trip (10) and the Winograd kernel (18).  Idle gaps:
    2-10, 44-60, 70-72, 90-96.  Host threads: dispatch (7), completion
    (8), post (9)."""
    ev = [meta(1, "/device:TPU:0"), meta(1, "", 1, "XLA Ops"),
          meta(1, "", 2, "XLA Modules"), meta(2, "/host:CPU"),
          meta(2, "", 7, "mb-dispatch"), meta(2, "", 8, "mb-complete"),
          meta(2, "", 9, "mb-post")]
    ev += [x(1, 2, "jit_run(77)", 5, 40, run_id=11),
           x(1, 2, "jit_run(77)", 55, 40, run_id=12)]
    ev += [op("fusion.1", 0, 2, "jit(run)/w000.conv_strided/conv"),
           op("fusion.2", 10, 10,
              "jit(run)/w001.conv1x1/bfp_roundtrip/reduce_max"),
           op("bfp_matmul_quantized.1", 20, 5,
              "jit(run)/w001.conv1x1/pallas_call"),
           op("copy.3", 25, 5),
           op("while.4", 30, 14, "jit(run)/cc_tail/while"),
           op("fusion.5", 31, 4, "jit(run)/cc_tail/while/body/add"),
           op("fusion.6", 36, 4, "jit(run)/cc_tail/while/body/max"),
           op("fusion.7", 60, 10,
              "jit(run)/w003.conv3x3/bfp_roundtrip/convert_element_type"),
           op("winograd_tile_matmul", 72, 18,
              "jit(run)/w003.conv3x3/pallas_call"),
           op("fusion.8", 96, 3, "jit(run)/w009.sigmoid/logistic")]
    ev += [x(2, 7, "std.dispatch", 0, 9, batch=0, live=2),
           x(2, 7, "std.dispatch.prepare", 0, 6, batch=0),
           x(2, 7, "std.dispatch.call", 6, 2, batch=0),
           x(2, 8, "chipbench.complete", 40, 22),
           x(2, 8, "std.complete", 40, 22, batch=0),
           x(2, 8, "std.complete.wait", 40, 18, batch=0),
           x(2, 8, "std.complete.fetch", 58, 3, batch=0),
           x(2, 9, "std.post", 50, 3, req=0, batch=0),
           x(2, 9, "std.post.decode", 50, 2, req=0, batch=0),
           x(2, 9, "std.gc", 53, 3, gen=0),
           x(2, 8, "std.complete", 89, 8, batch=1),
           x(2, 8, "std.complete.wait", 90, 6, batch=1),
           x(2, 8, "std.complete.fetch", 96, 1, batch=1)]
    return {"traceEvents": ev}


def test_step_scopes_by_innermost_scope_and_own_time():
    out = stages.reduce_events(hand_trace())
    got = dict(out["step_scopes"])
    assert got == pytest.approx({"bfp_roundtrip": 20e-6, "conv3x3": 18e-6,
                                 "cc_tail": 14e-6, "conv1x1": 5e-6,
                                 "unscoped": 5e-6})
    # the while's own 6 us plus its body's 8: its body counted once
    assert out["step_s"] == pytest.approx(62e-6)
    assert out["scoped_s"] == pytest.approx(57e-6)
    assert [k for k, _ in out["step_scopes"]][0] == "bfp_roundtrip"


def test_idle_by_stage_and_the_wait_claims_nothing():
    out = stages.reduce_events(hand_trace())
    got = dict(out["idle_by_stage"])
    # 2-10: prepare 4 us against call 2, dispatch's own 1; 44-60: the
    # wait overlaps 14 us but claims nothing, the gc pause (3) beats the
    # fetch (2), the decode (2) and the post's own time (1); 70-72 has
    # no span; 90-96 only the wait
    assert got == pytest.approx({"std.gc": 16e-6,
                                 "std.dispatch.prepare": 8e-6,
                                 "no program span": 8e-6})
    assert "std.complete.wait" not in got
    assert out["fetch"] == {"seconds": pytest.approx(4e-6), "count": 2}
    # the gaps are trace.py's: their sum is the record less its busy time
    base = trace.reduce_events(hand_trace())
    assert sum(got.values()) == pytest.approx(base["window_s"]
                                              - base["busy_s"])


def test_a_gap_under_only_the_wait_has_no_program_span():
    tr = hand_trace()
    # leave only the waits around 44-60 and 90-96
    tr["traceEvents"] = [e for e in tr["traceEvents"]
                         if e.get("name") not in (
                             "std.gc", "std.post", "std.post.decode",
                             "std.complete.fetch", "std.complete")]
    got = dict(stages.reduce_events(tr)["idle_by_stage"])
    assert got == pytest.approx({"no program span": (16 + 2 + 6) * 1e-6,
                                 "std.dispatch.prepare": 8e-6})


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """A window whose trace directory holds the given trace."""
    def make(tr):
        (tmp_path / "t.trace.json.gz").write_bytes(
            gzip.compress(json.dumps(tr).encode()))
        monkeypatch.setattr(stages, "trace_dir", lambda: tmp_path)
        return SimpleNamespace(trace={"breakdown": {"device_ops": []}})
    return make


def test_readers_on_the_hand_trace(traced):
    win = traced(hand_trace())
    q = harness.read_metric(ROOT, "quantize_share", win)
    assert q == pytest.approx(100 * 20 / 62)
    assert harness.read_metric(ROOT, "cc_tail_share", win) == \
        pytest.approx(100 * 14 / 62)
    assert harness.read_metric(ROOT, "fetch_ms", win) == pytest.approx(2e-3)
    bd = win.trace["breakdown"]
    assert list(bd) == ["device_ops", "step_scopes", "idle_by_stage"]
    assert dict(bd["idle_by_stage"])["std.gc"] == pytest.approx(16e-6)


def test_readers_read_nothing_on_a_program_without_marks(traced):
    tr = json.loads(gzip.decompress(
        (DATA / "r50_steps.trace.json.gz").read_bytes()))
    out = stages.reduce_events(tr)
    base = trace.reduce_events(tr)
    assert [k for k, _ in out["step_scopes"]] == ["unscoped"]
    assert out["scoped_s"] == 0.0
    # each of the three steps' ops, counted once: no more than the steps
    assert 0 < out["step_s"] <= sum(s["seconds"] for s in base["steps"])
    assert [k for k, _ in out["idle_by_stage"]] == ["no program span"]
    assert out["idle_by_stage"][0][1] == pytest.approx(
        base["window_s"] - base["busy_s"])
    win = traced(tr)
    for name in ("quantize_share", "cc_tail_share", "fetch_ms"):
        assert harness.read_metric(ROOT, name, win) is None, name
    assert harness.read_metric(
        ROOT, "quantize_share", SimpleNamespace(trace=None)) is None


def test_scope_of():
    assert stages.scope_of("jit(run)/w012.conv1x1/mul") == "conv1x1"
    assert stages.scope_of("jit(run)/w012.conv1x1/bfp_matmul_io/"
                           "reshape") == "bfp_matmul_io"
    assert stages.scope_of("jit(run)/cc_tail/while/body/w001.x") == "x"
    assert stages.scope_of("jit(run)/neww012.conv1x1") == "unscoped"
    assert stages.scope_of("") == "unscoped"



def test_recorded_trace_with_spans_and_scopes():
    """Three batch-8 steps of ``r50-photo-sat`` recorded on the chip
    with this program's spans and scopes (run ids 543-545; ops keep only
    their ``tf_op``): the step by scope and the idle by stage, as the
    reduction read them from the file.  The 120 ms gap before run 544
    overlaps the post pool's decodes most, while the completion stage
    waits on a step dispatched long before."""
    tr = json.loads(gzip.decompress(
        (DATA / "r50_spans.trace.json.gz").read_bytes()))
    out = stages.reduce_events(tr)
    assert dict(out["step_scopes"]) == pytest.approx({
        "conv1x1": 0.14259677390599895, "bfp_matmul_io": 0.03946260398599935,
        "conv3x3": 0.03092915929600317, "bfp_roundtrip": 0.030850531639996733,
        "cc_tail": 0.027148563516004424, "unscoped": 0.009359470073996212,
        "conv_strided": 0.005896842577999922, "pool": 0.0005718463280000724,
        "upsample": 0.0004079601560008012})
    assert out["scoped_s"] / out["step_s"] >= 0.95
    idle = dict(out["idle_by_stage"])
    assert [k for k, _ in out["idle_by_stage"][:3]] == [
        "std.post.decode", "std.preprocess", "no program span"]
    assert idle["std.post.decode"] == pytest.approx(0.12006414367599995)
    assert "std.complete.wait" not in idle
    assert out["fetch"] == {"seconds": pytest.approx(0.010102900999999954),
                            "count": 4}
    base = trace.reduce_events(tr, {"bfp_matmul_quantized": 40,
                                    "winograd_tile_matmul": 17})
    assert [s["batch"] for s in base["steps"]] == [8, 8, 8]
    assert out["step_s"] <= sum(s["seconds"] for s in base["steps"])
    assert sum(idle.values()) == pytest.approx(base["window_s"]
                                               - base["busy_s"])
