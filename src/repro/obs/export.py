"""Metric / trace serialization + the one shared report writer.

Two consumers want the same numbers: humans want a JSON report
(``launch/serve.py --metrics-json``, ``launch/train.py
--metrics-json``), and CI wants the regression-tracked
``BENCH_serve.json`` trajectory (``benchmarks/bench_obs.py``).  Both
funnel through `dump_json` — the unified writer behind ``--stats-json``
and ``--metrics-json`` — with ``"-"`` meaning stdout.  `write_trace`
exports a tracer's spans (``--trace-out``).
"""

from __future__ import annotations

import json
from typing import Any, Dict, Optional

from repro.obs.metrics import Registry
from repro.obs.trace import Tracer


def metrics_report(registry: Registry,
                   extra: Optional[Dict[str, Any]] = None
                   ) -> Dict[str, Any]:
    """Structured JSON report: every instrument's snapshot (+`extra`)."""
    out: Dict[str, Any] = {
        "schema": "repro.obs/1",
        "enabled": registry.enabled,
        "metrics": registry.snapshot(),
    }
    if extra:
        out.update(extra)
    return out


def dump_json(obj: Any, path: str, label: str = "report",
              tag: str = "obs") -> None:
    """THE report writer: pretty JSON to `path`, or stdout for ``"-"``.

    Shared by ``--stats-json`` / ``--metrics-json`` on both launchers
    and by the bench trajectory writer, so every machine-readable
    artifact the repo emits has one formatting and one code path."""
    text = json.dumps(obj, indent=1, sort_keys=True, default=str)
    if path == "-":
        print(text)
        return
    with open(path, "w", encoding="utf-8") as f:
        f.write(text + "\n")
    print(f"[{tag}] {label} written to {path}")


def write_trace(tracer: Tracer, path: str, fmt: str = "chrome",
                tag: str = "obs") -> int:
    """Export `tracer`'s spans: Chrome trace_event or JSONL."""
    if fmt == "chrome":
        n = tracer.export_chrome(path)
    elif fmt == "jsonl":
        n = tracer.export_jsonl(path)
    else:
        raise ValueError(f"unknown trace format {fmt!r} "
                         "(expected 'chrome' or 'jsonl')")
    print(f"[{tag}] {n} spans ({fmt}) written to {path}")
    return n
