"""What every run shares: the checkout's files, the device, the compile
cache, the per-layer metric readers, the checks and the result line."""

from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import sys
from typing import Any, Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, spec: Optional[dict] = None) -> dict:
    """The cell `name` of BENCHMARK.json with its configuration and mix
    files read: {"workload", "config", "model", "mix", "end_to_end",
    "per_layer"}."""
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    model = load_json(ROOT / conf["file"])
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")

    def applies(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if applies(m) and m["moves"] in moved]
    return {"workload": cell, "config": conf, "model": model, "mix": mix,
            "end_to_end": e2e, "per_layer": layer}


def setup_jax():
    """The compile cache at its fixed path in the checkout, every program
    written to it however fast it compiled; the tuning cache off."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["REPRO_TUNING_CACHE"] = "off"
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def limits(workload: str) -> Dict[str, float]:
    """The limit of each number compared for `correct` in `workload`."""
    return load_json(BENCH / "limits" / f"{workload}.json")["limits"]


def require_devices(chips: int) -> List[Any]:
    """The first `chips` TPU devices; NoDevice when JAX has fewer."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoDevice(f"JAX finds no device: {e}") from e
    if not devs or devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX runs on {devs[0].platform if devs else None}")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX finds "
                       f"{len(devs)}")
    return devs[:chips]


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       "bench/peaks.json")
    return table[kind]


def read_layer_metrics(metrics: List[dict], ctx: dict) -> Dict[str, dict]:
    """Run the reader `bench/metrics/<name>.py` of each per-layer metric;
    a reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in metrics:
        path = BENCH / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        got = mod.read(ctx)
        if got is not None:
            got = dict(got)
            got.setdefault("unit", m["unit"])
            out[m["name"]] = got
    return out


class Checks:
    """The numbers compared for `correct`, each with its limit."""

    def __init__(self):
        self.items: Dict[str, dict] = {}

    def add(self, name: str, value: float, limit: float):
        self.items[name] = {"value": float(value), "limit": float(limit)}

    @property
    def correct(self) -> bool:
        return bool(self.items) and all(
            c["value"] == c["value"] and c["value"] <= c["limit"]
            for c in self.items.values())

    def print_stderr(self):
        for k, c in self.items.items():
            ok = c["value"] == c["value"] and c["value"] <= c["limit"]
            print(f"check {k} {c['value']!r} limit {c['limit']!r} "
                  f"{'ok' if ok else 'FAIL'}", file=sys.stderr, flush=True)


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, dict], device: dict,
                checks: Checks, breakdown: Optional[dict] = None) -> str:
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks.items
    return json.dumps(out)
