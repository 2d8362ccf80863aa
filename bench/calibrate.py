#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--fault half_batch]

One process, one set-up: for each seed of `--seeds` the program's sound
run and the reference, compared as a benchmark run compares them; for
each seed of `--control-seeds` the control (the reference computed in
8-bit floating point, put in the program's place) compared with the f32
reference; with `--fault`, the program with that fault planted.  Each
reading is one JSON line on standard output; with `--textbook-eps` each
seed is read a second time against the reference with AdamW's eps
outside the root.  The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

CONTROL = "float8_e4m3fn"


def _say(**kw):
    print(json.dumps(kw), flush=True)


def half_batch(step_fn):
    """Fault: half of the batch left out, the mean taken over the rest."""
    def step(state, batch):
        return step_fn(state, {k: v[: v.shape[0] // 2]
                               for k, v in batch.items()})
    return step


def _leaves(run, ref):
    """Every reading behind the compared numbers, leaf by leaf."""
    return {k: [list(map(float, run[k])), list(map(float, ref[k]))]
            for k in ("losses", "first_grad", "change")}


def calibrate_train(cell, seeds, control_seeds, fault, textbook=False):
    from bench.drivers import train
    if fault == "half_batch":
        import repro.train as rt
        orig = rt.build_train_step

        def build(*a, **kw):
            i, s = orig(*a, **kw)
            return i, half_batch(s)
        rt.build_train_step = build
    prog = train.Program(cell)
    for seed in seeds:
        t = time.perf_counter()
        _, state, feed, loader, pr = train.start(prog, seed)
        loader.close()
        del state, feed
        train.free_device()
        t_ref = time.perf_counter()
        rr = train.reference_readings(prog, seed)
        kind = "fault:" + fault if fault else "program"
        _say(kind=kind, seed=seed, **train.compare(pr, rr),
             program_s=t_ref - t, reference_s=time.perf_counter() - t_ref,
             near_eps_share=list(map(float, rr["near_eps_share"])),
             **_leaves(pr, rr))
        if textbook:
            rb = train.reference_readings(prog, seed, eps_in_root=False)
            _say(kind=kind + ":textbook_eps", seed=seed,
                 **train.compare(pr, rb), **_leaves(pr, rb))
    for seed in control_seeds:
        rr = train.reference_readings(prog, seed)
        rc = train.reference_readings(prog, seed, compute=CONTROL)
        _say(kind="control", seed=seed, **train.compare(rc, rr),
             **_leaves(rc, rr))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default=None, choices=("half_batch",))
    ap.add_argument("--textbook-eps", action="store_true",
                    help="also read each seed against the reference with "
                         "eps outside the root")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    cell = harness.load_cell(args.workload)
    harness.setup_jax()
    try:
        harness.require_devices(cell["workload"]["chips"])
    except harness.NoDevice as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    calibrate_train(cell, seeds, control, args.fault, args.textbook_eps)
    return 0


if __name__ == "__main__":
    sys.exit(main())
