"""The served path's own measurement (runtime/telemetry.span, the
MicroBatcher's and STDService's stage spans, the interpreter's named
scopes): the span helper's contract with a stand-in annotation, one
request's ``std.*`` chain in a real profiler trace of a tiny served
model on the CPU, and the scopes in the compiled tiny engine's HLO."""
import gc
import glob
import gzip
import json
import re
import sys
import threading
from collections import defaultdict, deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import telemetry
from repro.runtime.telemetry import (
    CostBook, current_span, span, unwatch_gc, watch_gc,
)


class FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation and records its use."""

    enabled = True
    log: list = []

    def __init__(self, name, **args):
        self.name, self.args, self.meta = name, dict(args), {}
        self.entered = self.exited = False
        FakeAnnotation.log.append(self)

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        self.entered = True
        return self

    def __exit__(self, *exc):
        self.exited = True

    def set_metadata(self, **args):
        self.meta.update(args)


@pytest.fixture
def fake(monkeypatch):
    FakeAnnotation.log = []
    FakeAnnotation.enabled = True
    monkeypatch.setattr(telemetry, "_annotation", FakeAnnotation)
    return FakeAnnotation


class TestSpan:
    def test_opens_a_named_trace_annotation(self, fake):
        with span("std.dispatch", batch=3, live=2) as sp:
            ann = fake.log[-1]
            assert ann.entered and not ann.exited
        assert (ann.name, ann.args) == ("std.dispatch", {"batch": 3,
                                                         "live": 2})
        assert ann.exited and sp.seconds >= 0.0

    def test_no_annotation_without_a_profiler(self, fake):
        fake.enabled = False
        with span("std.post", req=1) as sp:
            pass
        assert fake.log == [] and sp.seconds >= 0.0

    def test_named_series_fed(self):
        book = CostBook(warmup=0)
        with span("std.complete.fetch", book=book,
                  series="mb_complete_fetch_s") as sp:
            pass
        snap = book.snapshot()
        assert snap["std_mb_complete_fetch_s_count"] == 1.0
        assert snap["std_mb_complete_fetch_s_sum"] == sp.seconds

    def test_step_series_fed(self):
        book = CostBook(warmup=0)
        with span("std.dispatch.call", book=book, series=dict(
                hw=(64, 64), batch=2, kind="single_device",
                stage="dispatch", precision="bfp")) as sp:
            pass
        assert book.step_count((64, 64), 2, "single_device",
                               stage="dispatch", precision="bfp") == 1
        assert book.step_total((64, 64), 2, "single_device",
                               stage="dispatch",
                               precision="bfp") == sp.seconds

    def test_no_series_when_none_given(self):
        book = CostBook(warmup=0)
        with span("std.post", book=book, req=0):
            pass
        assert book.snapshot() == {}

    def test_nesting_inherits_req_and_batch(self, fake):
        assert current_span() is None
        with span("std.complete", batch=7, req=4, live=2) as outer:
            with span("std.complete.fetch") as inner:
                assert current_span() is inner
            with span("std.gc", batch=9):
                pass
            assert current_span() is outer
        assert current_span() is None
        by_name = {a.name: a.args for a in fake.log}
        assert by_name["std.complete.fetch"] == {"batch": 7, "req": 4}
        assert by_name["std.gc"] == {"batch": 9, "req": 4}

    def test_exception_closes_the_span(self, fake):
        with pytest.raises(ValueError):
            with span("std.dispatch") as sp:
                raise ValueError("engine failed")
        assert fake.log[-1].exited and sp.seconds is not None
        assert current_span() is None

    def test_note_reaches_the_trace(self, fake):
        with span("std.dispatch", batch=1) as sp:
            sp.note(padded=8, plan="single_device")
        assert fake.log[-1].meta == {"padded": 8, "plan": "single_device"}
        assert sp.args == {"batch": 1, "padded": 8, "plan": "single_device"}

    def test_gc_pauses_are_spans_and_reach_the_sink(self, fake):
        sink = deque()
        watch_gc(sink)
        try:
            gc.collect()
        finally:
            unwatch_gc(sink)
        assert len(sink) >= 1 and all(s >= 0.0 for s in sink)
        gcs = [a for a in fake.log if a.name == "std.gc"]
        assert gcs and gcs[-1].args == {"gen": 2} and gcs[-1].exited
        n = len(sink)
        gc.collect()
        assert len(sink) == n, "the hook outlived unwatch_gc"
        assert telemetry._gc_hook not in gc.callbacks


class TestSpanThreads:
    N_THREADS = 16

    def _hammer(self, fn):
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            ts = [threading.Thread(target=fn) for _ in range(self.N_THREADS)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in ts)

    def test_request_ids_stay_unique_under_concurrent_submits(self, fake):
        from repro.launch.batching import MicroBatcher

        per = 100
        with MicroBatcher(lambda k, ps: ps, max_batch=8,
                          max_wait_ms=1) as mb:
            futs = []

            def client():
                futs.extend(mb.submit("a", i) for i in range(per))

            self._hammer(client)
            for f in futs:
                f.result(timeout=30)
        reqs = [a.args["req"] for a in fake.log if a.name == "std.enqueue"]
        assert sorted(reqs) == list(range(self.N_THREADS * per))
        marks = [a.args for a in fake.log if a.name == "std.batch"]
        assert sum(m["n"] for m in marks) == self.N_THREADS * per
        assert sorted(m["batch"] for m in marks) == list(range(len(marks)))

    def test_gc_pauses_pair_up_across_threads(self, fake):
        sink = deque()
        watch_gc(sink)
        try:
            self._hammer(lambda: [gc.collect(0) for _ in range(20)])
        finally:
            unwatch_gc(sink)
        closed = [a for a in fake.log if a.name == "std.gc" and a.exited]
        # a collect() called while another runs returns without one
        assert len(sink) == len(closed) > 0
        assert all(a.exited for a in fake.log if a.name == "std.gc")
        assert telemetry._gc_open == {}


# -- the tiny served path ------------------------------------------------------

def tiny_service(max_batch=2, max_wait_ms=5.0):
    from repro.core.interpreter import BFPConfig
    from repro.launch.serve import STDService
    from repro.models.fcn.pixellink import STDConfig

    cfg = STDConfig(name="tiny", backbone="resnet50", width=0.125,
                    image_size=(64, 64), merge_ch=(16, 16, 8),
                    upsample_mode="fused", bfp=BFPConfig(),
                    storage_fp16=True)
    return STDService(config=cfg, buckets=(64,), max_batch=max_batch,
                      max_wait_ms=max_wait_ms, precision="bfp")


def host_spans(trace_dir):
    path = glob.glob(f"{trace_dir}/**/*.trace.json.gz", recursive=True)[0]
    with gzip.open(path, "rt") as f:
        ev = json.load(f)["traceEvents"]
    return [e for e in ev if e.get("ph") == "X"
            and e["name"].startswith("std.")]


@pytest.fixture(scope="module")
def served_trace(tmp_path_factory):
    """Four requests in two full batches of two, under the profiler."""
    svc = tiny_service(max_wait_ms=10_000.0)
    svc.infer_labels(np.zeros((2, 64, 64, 3), np.float32), [(64, 64)] * 2)
    imgs = [np.random.default_rng(i).random((56, 48, 3)).astype(np.float32)
            for i in range(4)]
    d = tmp_path_factory.mktemp("trace")
    svc.start_batched()
    try:
        with jax.profiler.trace(str(d)):
            futs = [svc.submit(im) for im in imgs]
            for f in futs:
                f.result(timeout=300)
    finally:
        svc.stop_batched()
    return svc, host_spans(d)


class TestServedSpans:
    def test_one_request_chain_shares_its_req(self, served_trace):
        _, spans = served_trace
        per_req = defaultdict(set)
        for e in spans:
            if "req" in e.get("args", {}):
                per_req[int(e["args"]["req"])].add(e["name"])
        assert sorted(per_req) == [0, 1, 2, 3]
        for names in per_req.values():
            assert {"std.preprocess", "std.enqueue", "std.post",
                    "std.post.decode"} <= names

    def test_batch_spans_carry_the_batch_of_the_request(self, served_trace):
        _, spans = served_trace
        marks = {int(e["args"]["batch"]): e["args"] for e in spans
                 if e["name"] == "std.batch"}
        assert len(marks) == 2
        by_batch = defaultdict(set)
        for e in spans:
            if "batch" in e.get("args", {}):
                by_batch[int(e["args"]["batch"])].add(e["name"])
        for bid, mark in marks.items():
            assert {"std.dispatch", "std.dispatch.prepare",
                    "std.dispatch.call", "std.complete",
                    "std.complete.wait", "std.complete.fetch",
                    "std.post"} <= by_batch[bid]
            first, n = int(mark["first_req"]), int(mark["n"])
            assert n == 2 and mark["reason"] == "full"
            for e in spans:
                if e["name"] == "std.post" and \
                        first <= int(e["args"]["req"]) < first + n:
                    assert int(e["args"]["batch"]) == bid
        disp = [e["args"] for e in spans if e["name"] == "std.dispatch"]
        assert all(a["live"] == "2" and a["padded"] == "2"
                   and a["plan"] == "single_device" for a in disp)

    def test_stage_series_in_the_snapshot(self, served_trace):
        svc, _ = served_trace
        snap = svc.metrics_snapshot()
        for name in ("mb_prepare_s", "mb_complete_wait_s",
                     "mb_complete_fetch_s", "mb_dispatch_s",
                     "mb_complete_s"):
            assert snap[f"std_{name}_count"] >= 2.0, name
        assert snap["std_mb_complete_fetch_s_sum"] > 0.0
        assert snap["std_mb_dispatch_busy_s"] > 0.0
        assert svc.book.step_count((64, 64), 2, "single_device",
                                   stage="dispatch", precision="bfp") >= 1


# -- named scopes in the compiled engine ----------------------------------------

WORD = re.compile(r"(^|/)w\d{3}\.[a-z0-9_]+(/|$)")
INNER = ("bfp_roundtrip", "bfp_matmul_io", "cc_tail")


def op_names(hlo_text):
    """op_name of each instruction of the program (parameters,
    constants and reducer bodies left out), None where it has none."""
    reducers = set(re.findall(r"to_apply=%?([\w.\-]+)", hlo_text))
    comp, out = None, []
    for line in hlo_text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if m:
            comp = m.group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(", line)
        if not m or comp in reducers or m.group(1) in ("parameter",
                                                       "constant"):
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        out.append(name.group(1) if name else None)
    return out


def marked(fn, mark):
    def wrapped(*a, **k):
        with jax.named_scope(mark):
            return fn(*a, **k)
    return wrapped


@pytest.fixture(scope="module")
def engine_hlo():
    """The compiled tiny bfp engine, traced with the BFP round trip and
    the CC labeling wrapped in marker scopes of their own."""
    from repro.core import bfp as bfp_lib
    from repro.models.fcn import postprocess as pp
    from repro.runtime.executor import SingleDevice

    mp = pytest.MonkeyPatch()
    mp.setattr(bfp_lib, "roundtrip", marked(bfp_lib.roundtrip, "mark_rt"))
    mp.setattr(pp, "cc_label_batched", marked(pp.cc_label_batched,
                                              "mark_cc"))
    try:
        svc = tiny_service()
        fn = svc.factory.plan_fn((64, 64), 2, SingleDevice(), "bfp",
                                 svc.model_name)
        params = svc.factory.params((64, 64), "bfp", svc.model_name)
        text = fn.lower(
            params, jax.ShapeDtypeStruct((2, 64, 64, 3), jnp.float32),
            jax.ShapeDtypeStruct((2, 2), jnp.int32)).compile().as_text()
    finally:
        mp.undo()
    return op_names(text)


class TestEngineScopes:
    def test_every_roundtrip_op_under_bfp_roundtrip(self, engine_hlo):
        rt = [n for n in engine_hlo if n and "mark_rt" in n]
        assert rt, "no BFP round trip in the engine"
        assert all(re.search(r"/w\d{3}\.conv[\w]*/bfp_roundtrip/mark_rt", n)
                   for n in rt), [n for n in rt if "bfp_roundtrip" not in n]

    def test_the_tail_under_cc_tail(self, engine_hlo):
        cc = [n for n in engine_hlo if n and "mark_cc" in n]
        assert cc and all("cc_tail/mark_cc" in n for n in cc)
        assert not any(WORD.search(n) for n in cc)

    def test_nearly_every_instruction_is_scoped(self, engine_hlo):
        scoped = [n for n in engine_hlo
                  if n and (WORD.search(n) or any(s in n for s in INNER))]
        assert len(scoped) >= 0.95 * len(engine_hlo)

    def test_word_kinds(self, engine_hlo):
        kinds = {m.group(1) for n in engine_hlo if n
                 for m in re.finditer(r"w\d{3}\.([a-z0-9_]+)", n)}
        assert {"conv1x1", "conv3x3", "conv_strided", "pool", "upsample",
                "sigmoid"} <= kinds


def test_winograd_layout_work_under_winograd_io():
    """Everything the 3x3 path does in XLA around its kernel (the pad,
    the tile gather, the input and weight transforms, the output's
    untiling) carries the ``winograd_io`` scope; the kernel call does
    not."""
    from repro.kernels.winograd_conv import ops as wops

    def conv(x, w, b):
        with jax.named_scope("w001.conv3x3"):
            return wops.winograd_conv2d(x, w, b, relu=True, interpret=True)

    shapes = [jax.ShapeDtypeStruct(s, jnp.float32)
              for s in ((2, 16, 16, 8), (3, 3, 8, 16), (16,))]
    names = [n for n in op_names(jax.jit(conv).lower(*shapes).compile()
                                 .as_text()) if n]
    io = [n for n in names if "/winograd_io/" in n]
    for op in ("/pad", "/gather", "/dot_general", "/transpose"):
        assert any(n.endswith(op) for n in io), op
    kernel = [n for n in names if "winograd_tile_matmul" in n]
    assert kernel and not any("winograd_io" in n for n in kernel)
    assert all("winograd_io" in n or "winograd_tile_matmul" in n
               or n.endswith("jit(winograd_conv2d)")
               for n in names if "winograd_conv2d" in n)
