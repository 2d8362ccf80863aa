#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell, its configuration and its traffic
mix are read from BENCHMARK.json and the files it names, the driver
from `bench/drivers/<kind>.py` by the mix's kind; the per-layer
metrics of a traced run from `bench/metrics/<name>.py`.  With `--trace 0`
the result carries the cell's end-to-end metrics, with `--trace 1` its
per-layer metrics, read from a profiler trace of the window.  The last
lines on standard error, and the last key of the result line, are the
numbers compared for `correct`, each beside its limit.  A run that finds
no TPU, or fewer chips than the cell asks for, exits 3 and prints no
result.
"""

from __future__ import annotations

import argparse
import importlib
import pathlib
import shutil
import sys
import time

T_START = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    harness.setup_jax()
    try:
        devs = harness.require_devices(cell["workload"]["chips"])
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    driver = importlib.import_module(f"bench.drivers.{cell['mix']['kind']}")
    trace_dir = None
    if args.trace:
        trace_dir = ROOT / ".bench_trace" / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    out = driver.run_cell(cell, args.seed, args.seconds, devs, T_START,
                          str(trace_dir) if trace_dir else None)
    out["checks"].print_stderr()
    print(harness.result_line(**out), flush=True)
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
