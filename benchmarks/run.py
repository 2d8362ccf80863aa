"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and, per part, writes a
machine-readable ``BENCH_<part>.json`` record list (see `--json-dir`)
so CI and notebooks consume results without re-parsing the CSV.

  Table 2 latency  -> bench_fused_ce.bench_latency   (CPU-feasible sizes)
  Table 2 memory   -> bench_fused_ce.bench_memory    (paper's exact sizes,
                                                      compile-only bytes)
  §4.2 throughput  -> bench_train.bench_train_throughput
  Online-topk      -> bench_train.bench_streaming_topk (serving twin)
  §Roofline        -> bench_roofline.bench_roofline_summary (dry-run)
  §3.2.1 windows   -> bench_autotune.bench_autotune (tuned vs heuristic
                                                     block plans)
  §5 serving       -> bench_serve.bench_serve (continuous vs fixed-group
                                               batching, logits-free check)
  §6 spec decode   -> bench_spec.bench_spec (speculative vs plain
                                             continuous, logits-free verify)
  §7 MTP           -> bench_mtp.bench_mtp (n-head fused training +
                                           self-speculative decoding)
  §8 paged KV      -> bench_paged.bench_paged (block-pool cache vs dense
                                               slabs, prefix reuse)
  §9 grad filter   -> bench_backward.bench_backward (skipped-tile
                                                     fraction, backward
                                                     wall-clock)
  §11 obs          -> bench_obs.bench_obs (Zipf+Poisson load replay;
                                           obs overhead + span coverage;
                                           writes BENCH_serve.json)
  §12 modes        -> bench_modes.bench_modes (loglikelihood eval vs
                                               dense oracle, beam COW
                                               fork accounting,
                                               constrained decoding)

Run:  PYTHONPATH=src python -m benchmarks.run \
          [--only lat,mem,train,topk,roof,tune,serve,spec,mtp,paged,bwd,obs,modes] \
          [--json-dir DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ALL_PARTS = ("lat,mem,train,topk,roof,tune,serve,spec,mtp,paged,quant,"
             "bwd,obs,modes")


def _runner(part):
    """Part name -> list of bench callables (imported lazily so one
    part's missing deps never block the others)."""
    if part == "lat":
        from benchmarks.bench_fused_ce import (bench_latency,
                                               bench_pallas_interpret)
        return [bench_latency, bench_pallas_interpret]
    if part == "mem":
        from benchmarks.bench_fused_ce import bench_memory
        return [bench_memory]
    if part == "train":
        from benchmarks.bench_train import bench_train_throughput
        return [bench_train_throughput]
    if part == "topk":
        from benchmarks.bench_train import bench_streaming_topk
        return [bench_streaming_topk]
    if part == "roof":
        from benchmarks.bench_roofline import bench_roofline_summary
        return [bench_roofline_summary]
    if part == "tune":
        from benchmarks.bench_autotune import bench_autotune
        return [bench_autotune]
    if part == "serve":
        from benchmarks.bench_serve import bench_serve
        return [bench_serve]
    if part == "spec":
        from benchmarks.bench_spec import bench_spec
        return [bench_spec]
    if part == "mtp":
        from benchmarks.bench_mtp import bench_mtp
        return [bench_mtp]
    if part == "paged":
        from benchmarks.bench_paged import bench_paged
        return [bench_paged]
    if part == "quant":
        from benchmarks.bench_quant import bench_quant
        return [bench_quant]
    if part == "bwd":
        from benchmarks.bench_backward import bench_backward
        return [bench_backward]
    if part == "obs":
        from benchmarks.bench_obs import bench_obs
        return [bench_obs]
    if part == "modes":
        from benchmarks.bench_modes import bench_modes
        return [bench_modes]
    raise ValueError(f"unknown bench part {part!r}")

# JSON filenames keep a stable human-facing alias per part.  "serve"
# maps to serve_modes because the canonical BENCH_serve.json is the
# regression-tracked load-replay trajectory written by bench_obs.
_JSON_NAME = {"bwd": "backward", "serve": "serve_modes"}


def write_part_json(json_dir, part, records) -> str:
    """Write one part's emitted rows as ``BENCH_<part>.json``."""
    os.makedirs(json_dir, exist_ok=True)
    path = os.path.join(json_dir,
                        f"BENCH_{_JSON_NAME.get(part, part)}.json")
    with open(path, "w") as f:
        json.dump({"part": part, "records": records}, f, indent=2)
        f.write("\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=ALL_PARTS)
    ap.add_argument("--json-dir", default=".",
                    help="directory for BENCH_<part>.json records "
                         "('' disables JSON output)")
    args = ap.parse_args()
    parts = [p for p in ALL_PARTS.split(",")
             if p in set(args.only.split(","))]

    print("name,us_per_call,derived")
    for part in parts:
        records = []

        def emit(name, us, derived="", _records=records):
            print(f"{name},{us:.1f},{derived}")
            sys.stdout.flush()
            _records.append({"name": name, "us_per_call": us,
                             "derived": derived})

        for fn in _runner(part):
            fn(emit)
        if args.json_dir:
            write_part_json(args.json_dir, part, records)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
