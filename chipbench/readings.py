#!/usr/bin/env python3
"""Readings that the limits of the check are set from: the compared
numbers of the served path over many seeds in one process (engines
compiled once), or of the control, the same path at the precision step
below the configuration's (BFP mantissas of 7 bits, int8, in place of
10).  Not part of a benchmark run.

    python chipbench/readings.py --workload r50-photo-sat \\
        --seeds 101 102 103 --seconds 5 [--control]
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
#: the control: int8 BFP mantissas, the step a later PR would take to
#: halve the operand bytes
CONTROL_MANTISSA_BITS = 7


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from chipbench import run

    run.use_cache()
    from chipbench import harness

    cell = harness.load_cell(args.workload)
    dev = run.device_info()
    override = None
    if args.control:
        bfp = dict(cell.model_fields["bfp"],
                   mantissa_bits=CONTROL_MANTISSA_BITS)
        override = {"bfp": bfp}
    served = harness.Served(cell, config_override=override)
    t = T_START
    for seed in args.seeds:
        win = harness.run_window(served, cell, seed, args.seconds,
                                 t_start=t, log=run.log)
        got = harness.compare(served, win, seed, free=False)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": args.control, "device": dev,
                          "attempted": len(win.reqs.due),
                          "failed": harness.failures(win.reqs), **got}),
              flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
