"""The ``pixellink_vgg16`` configuration and its cell on the CPU: the
cell's files load through the harness with its traffic and metrics; a
tiny cell derived from the configuration file (width 0.125, a 64 plane,
BFP, closed loop) drives its window through ``submit`` and its answers
pass the check; ``winograd_io_share`` reads the Winograd path's layout
work on a hand-built trace and nothing on a program without the scope.

The tiny cell's limit (0.015) sits between the program's and the
control's decision gaps at this size, on the CPU: 0.0027-0.0056 over
seeds 100-111 against 0.0333-0.0475 with 7-bit mantissas over seeds
100-105."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import _cb_paths
import _cb_tiny
from chipbench import harness
from test_cb_stages import DATA, hand_trace, op, traced  # noqa: F401

ROOT = Path(_cb_paths.ROOT)
CONFIG = ROOT / "chipbench" / "configs" / "pixellink_vgg16.json"
LIMIT = 0.015
SEEDS = (100, 101)


def write(root: Path) -> Path:
    """The tiny checkout of ``_cb_tiny`` with its configuration derived
    from the VGG-16 file."""
    _cb_tiny.write(root)
    cfg = json.loads(CONFIG.read_text())
    cfg["name"] = cfg["model"]["name"] = "tiny"
    cfg["model"].update(width=0.125, image_size=[64, 64],
                        merge_ch=[16, 16, 8])
    cfg["deployment"] = {"precision": "bfp", "buckets": [64], "max_batch": 2}
    cfg["limits"] = {"decision_gap": LIMIT}
    (root / "chipbench" / "configs" / "tiny.json").write_text(
        json.dumps(cfg))
    return root


def test_the_cell_loads_with_its_traffic_and_metrics():
    cell = harness.load_cell("vgg16-photo-sat")
    model = cell.model_fields
    assert (model["backbone"], model["width"]) == ("vgg16", 1.0)
    assert model["bfp"]["mantissa_bits"] == 10 and cell.chips == 1
    assert cell.config["deployment"] == {"precision": "bfp",
                                         "buckets": [256, 512],
                                         "max_batch": 8}
    assert cell.mix["loop"] == "open" and cell.mix["rate"] > 0
    assert cell.mix["batches"] == [1, 2, 4, 8]
    assert {m["name"] for m in cell.metrics} == {
        "images_per_s", "setup_s", "step_mfu", "device_idle",
        "winograd_roofline", "dispatch_ms", "quantize_share",
        "cc_tail_share", "fetch_ms", "winograd_io_share"}
    r50 = {m["name"] for m in harness.load_cell("r50-photo-sat").metrics}
    assert "winograd_io_share" in r50 and "bfp_matmul_roofline" in r50


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    cell = harness.load_cell("tiny-sat", write(
        tmp_path_factory.mktemp("checkout")))
    assert cell.model_fields["backbone"] == "vgg16"
    return cell, harness.Served(cell)


@pytest.mark.parametrize("seed", SEEDS)
def test_tiny_vgg16_cell_is_correct(tiny, seed):
    cell, served = tiny
    win = harness.run_window(served, cell, seed, 0.5, t_start=0.0,
                             log=lambda *a: 0)
    assert win.reqs.due
    got = harness.compare(served, win, seed, free=False)
    checks, correct = harness.judge(cell, got, harness.failures(win.reqs))
    assert correct and checks["failed"]["value"] == 0, checks


def test_tiny_vgg16_control_is_not_correct(tiny):
    cell, _ = tiny
    bfp = dict(cell.model_fields["bfp"], mantissa_bits=7)
    control = harness.Served(cell, config_override={"bfp": bfp})
    win = harness.run_window(control, cell, SEEDS[0], 0.5, t_start=0.0,
                             log=lambda *a: 0)
    got = harness.compare(control, win, SEEDS[0], free=False)
    checks, correct = harness.judge(cell, got, harness.failures(win.reqs))
    assert not correct and got["decision_gap"] > LIMIT, checks


def with_winograd_io():
    """The hand trace with the Winograd path's layout work in step 2
    (3 us) and one such op outside every step."""
    tr = hand_trace()
    path = "jit(run)/w003.conv3x3/jit(winograd_conv2d)/winograd_io/"
    tr["traceEvents"] += [op("fusion.9", 91, 3, path + "transpose"),
                          op("fusion.10", 99, 1, path + "pad")]
    return tr


def test_winograd_io_share_on_a_hand_trace(traced):  # noqa: F811
    got = harness.read_metric(ROOT, "winograd_io_share",
                              traced(with_winograd_io()))
    # the steps' device time is the hand trace's 62 us and these 3
    assert got == pytest.approx(100 * 3 / 65)


@pytest.mark.parametrize("data", ["r50_steps", "r50_spans"])
def test_winograd_io_share_reads_nothing_without_the_scope(traced, data):  # noqa: F811
    tr = json.loads(gzip.decompress(
        (DATA / f"{data}.trace.json.gz").read_bytes()))
    assert harness.read_metric(ROOT, "winograd_io_share", traced(tr)) is None
    assert harness.read_metric(ROOT, "winograd_io_share",
                               SimpleNamespace(trace=None)) is None
