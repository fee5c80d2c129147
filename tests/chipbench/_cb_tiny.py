"""A tiny cell written into a temporary checkout: the benchmark's own
metric readers and references, a width-0.125 PixelLink ResNet-50 on a
64 plane at BFP, and a closed-loop mix.  The limit of its check
(0.015) sits between the program's and the control's decision gaps at
this size, on the CPU: 0.0026-0.0065 over seeds 100-111 against
0.0278-0.0577 over seeds 100-105."""
import json
import shutil
from pathlib import Path

import _cb_paths

REPO = Path(_cb_paths.ROOT)
LIMIT = 0.015


def write(root: Path) -> Path:
    cb = root / "chipbench"
    for sub in ("metrics", "references"):
        shutil.copytree(REPO / "chipbench" / sub, cb / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (cb / "configs").mkdir()
    (cb / "traffic").mkdir()
    cfg = json.loads((REPO / "chipbench" / "configs"
                      / "pixellink_resnet50.json").read_text())
    cfg["name"] = cfg["model"]["name"] = "tiny"
    cfg["model"].update(width=0.125, image_size=[64, 64],
                        merge_ch=[16, 16, 8])
    cfg["deployment"] = {"precision": "bfp", "buckets": [64], "max_batch": 2}
    cfg["limits"] = {"decision_gap": LIMIT}
    (cb / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = {"why": "tiny", "size": {"min": 40, "max": 64, "step": 8},
           "max_instances": 2, "pool": 8, "batches": [1, 2]}
    (cb / "traffic" / "tiny_closed.json").write_text(
        json.dumps(dict(mix, loop="closed", clients=4)))
    bench = {
        "command": ["python3", "chipbench/run.py"], "paths": ["chipbench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "chipbench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [
            {"name": "tiny-sat", "config": "tiny", "traffic": "tiny_closed",
             "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "images_per_s", "unit": "images/s", "better": "higher",
             "bound": 0.05, "source": "host_clock", "workloads": ["tiny-sat"]},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
             "source": "host_clock"}],
        "per_layer": [],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
