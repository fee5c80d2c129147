"""The check must fail a broken timed path.  A run of the tiny cell
with its look for a chip skipped, and a fault planted in the served
program underneath the harness: half of each batch left out, an answer
altered where it is produced (label map, or boxes after decode), a
stale answer in place of the batch's own.  And the control: the same
path at the precision step below the configuration's (7-bit, int8, BFP
mantissas in place of 10) reads past the limit.  Each must come out not
correct; the sound path must come out correct."""
import time

import numpy as np
import pytest

import _cb_paths  # noqa: F401
import _cb_tiny
from chipbench import harness
from repro.launch.serve import STDService

SEEDS = (100, 101)


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return harness.load_cell("tiny-sat", _cb_tiny.write(
        tmp_path_factory.mktemp("checkout")))


@pytest.fixture(scope="module")
def served(cell):
    return harness.Served(cell)


def judged(served, cell, seed):
    win = harness.run_window(served, cell, seed, 0.5,
                             t_start=time.perf_counter(), log=lambda *a: 0)
    got = harness.compare(served, win, seed, free=False)
    return harness.judge(cell, got, harness.failures(win.reqs))


def half_batch(orig):
    def infer(self, key, payloads):
        keep = len(payloads) - len(payloads) // 2
        left_out = [(np.zeros_like(x), v, t) for x, v, t in payloads[keep:]]
        return orig(self, key, list(payloads[:keep]) + left_out)
    return "_mb_infer", infer


def labels_altered(orig):
    def finalize(self, key, raw):
        return [np.roll(out, 1, axis=0) for out in orig(self, key, raw)]
    return "_mb_finalize", finalize


def boxes_altered(orig):
    def post(self, payload, out):
        boxes = orig(self, payload, out)
        return [dict(b, box=(b["box"][0] + 1,) + tuple(b["box"][1:]))
                for b in boxes]
    return "_mb_post", post


def stale(orig):
    last = {}

    def finalize(self, key, raw):
        outs = orig(self, key, raw)
        prev = last.get(len(outs), outs)
        last[len(outs)] = outs
        return prev
    return "_mb_finalize", finalize


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_path_is_correct(served, cell, seed):
    checks, correct = judged(served, cell, seed)
    assert correct, checks


@pytest.mark.parametrize("fault", [half_batch, labels_altered,
                                   boxes_altered, stale])
def test_fault_is_not_correct(served, cell, fault, monkeypatch):
    name = {half_batch: "_mb_infer", labels_altered: "_mb_finalize",
            boxes_altered: "_mb_post", stale: "_mb_finalize"}[fault]
    attr, broken = fault(getattr(STDService, name))
    monkeypatch.setattr(STDService, attr, broken)
    checks, correct = judged(served, cell, SEEDS[0])
    assert not correct, checks


def test_control_is_not_correct(cell):
    bfp = dict(cell.model_fields["bfp"], mantissa_bits=7)
    control = harness.Served(cell, config_override={"bfp": bfp})
    for seed in SEEDS:
        checks, correct = judged(control, cell, seed)
        assert not correct, checks
        assert checks["decision_gap"]["value"] > _cb_tiny.LIMIT
