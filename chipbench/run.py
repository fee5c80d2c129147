#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python chipbench/run.py --workload r50-photo-sat --seed 7 \\
        --seconds 10 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; both, and every metric's reader, are files found by name
(chipbench/harness.py).  The run builds the served system, makes the
configuration's weights (from its ``weights_seed``) and the images and
arrivals from ``--seed``, warms every engine the mix can form,
measures ``--seconds`` through ``STDService.submit`` (with ``--trace
1`` under the profiler), checks a sample of the served answers against
the plain reference, and prints, last on standard output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``, each compared number beside its limit (also the last lines
on standard error).

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  The persistent compilation cache lives
at ``<checkout>/.chipbench/jax_cache``, a fixed path inside the
checkout, so only a checkout's first run of a cell compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
WORK = ROOT / ".chipbench"


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_cache() -> Path:
    """The checkout's own compile cache, set before JAX is imported so
    that the program's own cache helper takes the same directory; the
    TPU runtime's logs go inside the checkout too."""
    cache = WORK / "jax_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    os.environ["TPU_LOG_DIR"] = str(WORK / "tpu_logs")
    import jax

    jax.config.update("jax_compilation_cache_dir", str(cache))
    # the eager init/normalisation programs are fast to compile but
    # many; keep them too, so a warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # one cell's programs are a few hundred MB; a size cap set for the
    # machine would evict the cell's own engines between its runs
    jax.config.update("jax_compilation_cache_max_size", -1)
    return cache


def device_info() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    use_cache()
    from chipbench import harness

    cell = harness.load_cell(args.workload)
    dev = device_info()
    if dev["platform"] != "tpu":
        log(f"no result: platform is {dev['platform']!r}, not 'tpu'")
        return 2
    need = int(cell.chips)
    if dev["count"] < need:
        log(f"no result: {dev['count']} devices, the cell asks for {need}")
        return 2
    trace_dir = None
    if args.trace:
        trace_dir = WORK / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
    result = harness.run_cell(cell, args.seed, args.seconds, dev,
                            t_start=T_START, trace_dir=trace_dir, log=log)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
