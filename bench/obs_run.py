#!/usr/bin/env python3
"""Run a train cell with the program's own tracing on, and print what it
measured: device time by layer scope, the loop's host spans, the compile
counters, the idle gaps, and what the tracing costs.

    python3 bench/obs_run.py --workload <cell> --seed <n> \
        [--seconds 10] [--overhead-seconds 30] [--pairs 3]

From the root of a checkout, on the chip; it exits 3 without one.
`repro.obs` is enabled, its spans bridged to the profiler, before the
program is built, so the ``compile.*`` counters cover set-up.  After
set-up (the driver's, `bench/drivers/train.py`) one window of `--seconds`
runs under the profiler.  Then, with the profiler off, `--pairs` pairs of
windows of `--overhead-seconds` alternate obs off and on (off, on, on,
off, ...): the tokens per second of each is the tracing's cost.  The
result is one JSON line, its times in ms per train step.  `bench/run.py
--trace 1` reads the scopes alone: its driver runs with obs off.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time

T_START = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import harness, scope_reduce, trace_reduce  # noqa: E402
from bench.drivers import train as driver  # noqa: E402


def counters():
    from repro import obs
    reg = obs.get_registry()
    return {n: reg.get(n).value for n in obs.COMPILE_COUNTERS}


def traced_window(step, state, feed, seconds):
    """One window under the profiler; returns (state, steps, reduced trace
    of trace_reduce, scopes, host spans)."""
    import jax
    trace_dir = ROOT / ".bench_trace" / "obs_run"
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True)
    state, steps, _ = driver.window(step, state, feed, seconds,
                                    str(trace_dir))
    red = trace_reduce.reduce(trace_reduce.load_xplane(str(trace_dir)))
    pd = jax.profiler.ProfileData.from_file(
        scope_reduce.newest_xplane(str(trace_dir)))
    ops, host = scope_reduce.load(pd, scope_reduce.live_op_names())
    window = scope_reduce.window_of(host)
    got = scope_reduce.scopes(ops, window)
    spans = scope_reduce.spans(host, window)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return state, steps, red, got, spans


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="qwen3-0.6b-untied.train.s4096")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--overhead-seconds", type=float, default=30)
    ap.add_argument("--pairs", type=int, default=3)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    harness.setup_jax()
    try:
        devs = harness.require_devices(cell["workload"]["chips"])
    except harness.NoDevice as e:
        print(f"obs_run: {e}", file=sys.stderr)
        return 3
    from repro import obs
    obs.enable(trace=True, jax_annotate=True)
    c0 = counters()
    prog = driver.Program(cell)
    step, state, feed, loader, _ = driver.start(prog, args.seed)
    setup_s = time.perf_counter() - T_START
    c1 = counters()
    state, steps, red, got, spans = traced_window(step, state, feed,
                                                  args.seconds)
    c2 = counters()
    ms = 1e3 / steps
    out = {
        "workload": args.workload, "seed": args.seed,
        "device": harness.device_info(devs), "setup_s": setup_s,
        "compile_setup": scope_reduce.deltas(c0, c1),
        "compile_window": scope_reduce.deltas(c1, c2),
        "steps": steps, "window_s": red["window_s"],
        "busy_ms": red["busy_s"] * ms, "idle_share": red["idle_share"],
        "fused_ce_ms": trace_reduce.kernel_s(red, "fused_ce_") * ms,
        "scopes_ms": {k: {p: v * ms for p, v in ph.items()}
                      for k, ph in got["scopes"].items()},
        "unscoped_ms": got["unscoped_s"] * ms,
        "unscoped_ops_ms": [[k, v * ms] for k, v in got["unscoped_ops"]],
        "scoped_busy_ms": got["busy_s"] * ms,
        "spans_ms": {k: {"ms": v["s"] * ms, "n": v["n"]}
                     for k, v in spans.items()},
        "idle_gaps": red["idle_gaps"],
        "device_ops": red["device_ops"],
    }
    runs = []
    for mode in (["off", "on", "on", "off"] * args.pairs)[:2 * args.pairs]:
        if mode == "on":
            obs.enable(trace=True, jax_annotate=True)
        else:
            obs.disable()
        state, n, wall = driver.window(step, state, feed,
                                       args.overhead_seconds)
        runs.append([mode, n, wall, n * prog.tokens_per_step / wall])
    out["overhead_runs"] = runs
    loader.close()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
