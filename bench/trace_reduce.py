"""From a profiler trace of the window to the numbers the metrics read.

The trace is the `.xplane.pb` that `jax.profiler` writes.  It is first
read into plain events, `Event(plane, line, name, start_ns, dur_ns)`, so
the reduction below also runs on a small recorded excerpt (the tests'):

* the window is the host span ``bench.window`` that the driver opens
  around the measured loop;
* a device is a plane ``/device:TPU:<n>``; its operations are the events
  of its ``XLA Ops`` line, clipped to the window, named by their HLO
  instruction (the event's text up to `` = ``); a `while` or `call`
  event holds the events of its body, so the breakdown ranks operations
  by self time (their time less that of the events nested in them);
* busy time is the measure of the union of a device's operation
  intervals, idle share ``1 - busy / window``, averaged over devices;
* a kernel's time is the summed duration of the operations whose name
  holds the kernel's stable name (`fused_ce_fwd`, `sample_topk`, ...;
  under autodiff the program names them `jvp_fused_ce_fwd_` and so on);
* a collective is an operation named like an XLA collective; its exposed
  time is the part of the collectives' union during which no other
  operation of that device runs;
* an idle gap is a stretch of the window in which a device runs nothing;
  it is labelled by the innermost host span (other than the window) that
  covers its middle, which says what the host was doing.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

WINDOW = "bench.window"
DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE_RE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|psum|ppermute|send|recv)", re.I)


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def load_xplane(trace_dir: str) -> List[Event]:
    """Every event of the newest `.xplane.pb` under `trace_dir`."""
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    out = []
    for plane in pd.planes:
        dev = DEVICE_RE.match(plane.name)
        for line in plane.lines:
            if dev and line.name != OPS_LINE:
                continue
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def op_name(text: str) -> str:
    """The HLO instruction name of a device event (`%fusion.3 = ...` ->
    `fusion.3`)."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def _self_times(ivs: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Summed self time (ns) by name of properly nested intervals."""
    out: Dict[str, float] = collections.defaultdict(float)
    stack: List[List] = []           # [end, name, child time]
    for a, b, name in sorted(ivs, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][0] <= a:
            end, n, child = stack.pop()
            out[n] += child
        if stack:
            stack[-1][2] -= b - a
        stack.append([b, name, b - a])
    for end, n, child in stack:
        out[n] += child
    return out


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _measure(iv) -> float:
    return sum(b - a for a, b in iv)


def _intersect(a, b) -> List[Tuple[float, float]]:
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _clip(e: Event, w0: float, w1: float) -> Optional[Tuple[float, float]]:
    a, b = max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1)
    return (a, b) if a < b else None


def reduce(events: Iterable[Event], top: int = 10) -> Dict:
    """The reduced trace (all times in seconds, per chip where so said)."""
    events = list(events)
    wins = [e for e in events if e.name == WINDOW
            and not DEVICE_RE.match(e.plane)]
    if not wins:
        raise ValueError(f"the trace holds no {WINDOW!r} host span")
    win = max(wins, key=lambda e: e.dur_ns)
    w0, w1 = win.start_ns, win.start_ns + win.dur_ns
    by_dev: Dict[str, List[Event]] = collections.defaultdict(list)
    host: List[Event] = []
    for e in events:
        if DEVICE_RE.match(e.plane):
            if e.line == OPS_LINE:
                by_dev[e.plane].append(e)
        elif e is not win and e.dur_ns > 0:
            host.append(e)
    if not by_dev:
        raise ValueError("the trace holds no device operations")
    n = len(by_dev)
    op_s: Dict[str, float] = collections.defaultdict(float)
    self_s: Dict[str, float] = collections.defaultdict(float)
    busy = coll = exposed = 0.0
    gaps: List[Tuple[float, float]] = []
    for plane, evs in sorted(by_dev.items()):
        ops, colls, named = [], [], []
        for e in evs:
            iv = _clip(e, w0, w1)
            if iv is None:
                continue
            name = op_name(e.name)
            op_s[name] += (iv[1] - iv[0]) * 1e-9
            named.append((iv[0], iv[1], name))
            (colls if COLLECTIVE_RE.match(name) else ops).append(iv)
        for name, t in _self_times(named).items():
            self_s[name] += t * 1e-9
        u_all = _union(ops + colls)
        busy += _measure(u_all)
        u_coll, u_ops = _union(colls), _union(ops)
        coll += _measure(u_coll)
        exposed += _measure(u_coll) - _measure(_intersect(u_coll, u_ops))
        edges = [w0] + [x for iv in u_all for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    window_s = (w1 - w0) * 1e-9
    ranked = sorted(self_s.items(), key=lambda kv: -kv[1])
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": window_s, "devices": n,
        "busy_s": busy * 1e-9 / n,
        "idle_share": 1.0 - busy * 1e-9 / n / window_s,
        "collective_s": coll * 1e-9 / n,
        "collective_exposed_s": exposed * 1e-9 / n,
        "op_s": dict(op_s),
        "device_ops": [[k, v / n] for k, v in ranked[:top]],
        "idle_gaps": [[_label(host, (a + b) / 2), (b - a) * 1e-9]
                      for a, b in gaps[:top]],
    }


def _label(host: List[Event], t: float) -> str:
    best = None
    for e in host:
        if e.start_ns <= t <= e.start_ns + e.dur_ns and (
                best is None or e.dur_ns < best.dur_ns):
            best = e
    return best.name if best is not None else "host: no span"


def kernel_s(red: Dict, name: str) -> float:
    """Summed time of the operations whose instruction name holds kernel
    `name`, per chip."""
    return sum(v for k, v in red["op_s"].items() if name in k) \
        / red["devices"]


def kernel_count(red: Dict, name: str) -> int:
    return sum(1 for k in red["op_s"] if name in k)
