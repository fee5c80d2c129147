"""Rehearsal of the harness on the CPU with a tiny cell written into a
temporary checkout: the cell, a new traffic mix and a new metric are
found by name with no code edit; the warmed window compiles nothing;
the open-loop clock times from the due time; ``run.py`` gives no
result without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time
from concurrent.futures import Future
from pathlib import Path

import numpy as np
import pytest

import _cb_paths
import _cb_tiny
from chipbench import harness, loadgen

NEW_METRIC = '''
def read(win):
    return float(len(win.reqs.done))
'''


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    root = _cb_tiny.write(tmp_path_factory.mktemp("checkout"))
    # a later PR's metric: one new file and one new entry, nothing else
    (root / "chipbench" / "metrics" / "answered.py").write_text(NEW_METRIC)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["end_to_end"].append(
        {"name": "answered", "unit": "requests", "better": "higher",
         "bound": 0.05, "source": "host_clock", "workloads": ["tiny-sat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    lines = []
    cell = harness.load_cell("tiny-sat", root)
    out = harness.run_cell(cell, 100, 1.0, _cb_tiny.DEVICE,
                           t_start=time.perf_counter(), log=lines.append)
    return out, lines


def test_cell_and_new_metric_found_by_name(rehearsal):
    out, _ = rehearsal
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"images_per_s", "setup_s", "answered"}
    assert out["metrics"]["answered"]["value"] >= out["attempted"] - 4
    assert list(out)[-1] == "checks"
    assert out["checks"]["decision_gap"]["limit"] == _cb_tiny.LIMIT


def test_warmed_window_compiles_nothing(rehearsal):
    _, lines = rehearsal
    assert "window compiles=0 cache_loads=0 fresh={}" in lines


def test_open_loop_times_from_the_due_time():
    stall = 0.2

    def submit(req, slot):
        # request 0 stalls the sender; requests 1.. are sent late
        if req == 0:
            time.sleep(stall)
        f = Future()
        f.set_result(None)
        return f

    t0 = time.perf_counter()
    offsets = loadgen.arrival_offsets(100.0, 0.1, seed=7)
    reqs = loadgen.open_loop(submit, 4, offsets, t0)
    lat, unfinished = loadgen.latencies_ms(reqs, time.perf_counter())
    assert unfinished == 0 and len(lat) == len(offsets) == 10
    for i in range(1, len(offsets)):
        late = (reqs.sent[i] - reqs.due[i]) * 1e3
        assert late > 0
        assert lat[i] >= late           # the wait behind the stall counts


def test_arrivals_are_the_same_work_for_every_seed():
    a = loadgen.arrival_offsets(50.0, 10.0, seed=1)
    b = loadgen.arrival_offsets(50.0, 10.0, seed=2)
    assert len(a) == len(b) == 500 and a[0] == b[0] == 0.0
    ga, gb = np.diff(a), np.diff(b)
    assert not np.allclose(ga, gb)                  # another order
    assert np.allclose(np.sort(ga), np.sort(gb))    # the same gaps
    mix = {"size": {"min": 320, "max": 512, "step": 8}, "pool": 8,
           "max_instances": 2}
    sizes = [sorted(im.shape for im in loadgen.image_pool(mix, s))
             for s in (1, 2)]
    assert sizes[0] == sizes[1]


def test_run_gives_no_result_without_a_tpu(tmp_path):
    # a checkout of the benchmark's own files, so the run's cache
    # directory lands in the test's temporary tree
    shutil.copy(Path(_cb_paths.ROOT) / "BENCHMARK.json", tmp_path)
    shutil.copytree(Path(_cb_paths.ROOT) / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "r50-photo-sat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=120)
    assert r.returncode != 0
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    assert "not 'tpu'" in r.stderr
