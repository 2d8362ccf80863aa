"""Device time by layer scope, and the program's host spans, from a
profiler trace of the window.

The program names its layers inside compiled programs: each layer
boundary opens one `jax.named_scope` of `SCOPES` (the program's
`repro.obs.SCOPES`) at its call site, and JAX writes the scope path into
every instruction's ``op_name`` metadata, fusions included:

    jit(step_fn)/jvp(blocks)/while/body/closed_call/attn/dot_general
    jit(step_fn)/transpose(jvp(blocks))/.../rematted_computation/mlp/...
    jit(step_fn)/optimizer/sub

An op's scope is the innermost component of its ``op_name`` that is in
`SCOPES`, transform wrappers (``jvp(...)``, ``transpose(...)``) taken
off; its phase is ``recompute`` under ``rematted_computation`` (what a
checkpoint recomputes in the backward pass), else ``backward`` under a
``transpose(...)``, else ``forward``.  An op with no scope, or with no
``op_name``, is unscoped.

A device event of the trace names its HLO instruction (`trace_reduce`'s
`op_name`); on the v5e its stats carry neither its module nor its
``op_name``.  Its module is the device's ``XLA Modules`` event that
covers it, and its ``op_name`` is looked up by module and instruction
in the HLO text of the process's live executables, which hold the step
the window ran.  Each op counts its self time (less that of the ops
nested in it, as `trace_reduce`'s breakdown), so the scopes and the
unscoped rest sum to the device's busy time.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from bench import trace_reduce as tr

SCOPES = ("embed", "blocks", "norm", "attn", "mlp", "moe", "loss",
          "optimizer")
REMAT = "rematted_computation"
PHASES = ("forward", "recompute", "backward")
MODULES_LINE = "XLA Modules"

_WRAPPED = re.compile(r"^(?!p?jit\()\w+\((.*)\)$")
_HLO_LINE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\bop_name="([^"]*)"')


class DeviceOp(NamedTuple):
    plane: str
    start_ns: float
    dur_ns: float
    op: str                 # HLO instruction name
    op_name: Optional[str]  # its op_name metadata, when known


def scope_of(op_name: Optional[str]) -> Tuple[Optional[str], str]:
    """(innermost scope of `SCOPES` or None, phase) of an op_name; of
    several op_names joined by ``;`` (a fusion's), the first."""
    if not op_name:
        return None, "forward"
    path = op_name.split(";", 1)[0]
    comps = []
    for c in path.split("/"):
        m = _WRAPPED.match(c)
        while m:
            c = m.group(1)
            m = _WRAPPED.match(c)
        comps.append(c)
    scope = next((c for c in reversed(comps) if c in SCOPES), None)
    if REMAT in comps:
        return scope, "recompute"
    return scope, "backward" if "transpose(" in path else "forward"


def hlo_op_names(text: str) -> Dict[str, str]:
    """Instruction name -> op_name of every instruction of an HLO text
    that carries one."""
    out = {}
    for line in text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def live_op_names() -> Dict[str, Dict[str, str]]:
    """Module name -> `hlo_op_names` of each live executable of the
    process (empty where the runtime lists none)."""
    import jax
    out: Dict[str, Dict[str, str]] = {}
    for ex in jax.devices()[0].client.live_executables():
        for mod in ex.hlo_modules():
            out.setdefault(mod.name, {}).update(hlo_op_names(mod.to_string()))
    return out


def newest_xplane(root: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return paths[-1] if paths else None


def load(pd, op_names: Dict[str, Dict[str, str]]
         ) -> Tuple[List[DeviceOp], List[tr.Event]]:
    """The device ops (with their op_name) and the host events of a
    `jax.profiler.ProfileData`; `op_names` is `live_op_names()`.  An
    op's module is the event of the device's ``XLA Modules`` line
    (``jit_step_fn(<fingerprint>)``) that covers its start."""
    ops: List[DeviceOp] = []
    host: List[tr.Event] = []
    for plane in pd.planes:
        if not tr.DEVICE_RE.match(plane.name):
            host += [tr.Event(plane.name, line.name, e.name,
                              float(e.start_ns), float(e.duration_ns))
                     for line in plane.lines for e in line.events]
            continue
        lines = {line.name: line for line in plane.lines}
        mods = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                       e.name.split("(", 1)[0])
                      for e in (lines[MODULES_LINE].events
                                if MODULES_LINE in lines else ()))
        starts = [m[0] for m in mods]
        for e in (lines[tr.OPS_LINE].events if tr.OPS_LINE in lines
                  else ()):
            t = float(e.start_ns)
            i = bisect.bisect_right(starts, t) - 1
            mod = mods[i][2] if i >= 0 and t <= mods[i][1] else ""
            name = tr.op_name(e.name)
            ops.append(DeviceOp(plane.name, t, float(e.duration_ns), name,
                                op_names.get(mod, {}).get(name)))
    return ops, host


def window_of(host: Iterable[tr.Event]) -> Tuple[float, float]:
    wins = [e for e in host if e.name == tr.WINDOW]
    if not wins:
        raise ValueError(f"the trace holds no {tr.WINDOW!r} host span")
    w = max(wins, key=lambda e: e.dur_ns)
    return w.start_ns, w.start_ns + w.dur_ns


def scopes(ops: Iterable[DeviceOp], window: Tuple[float, float],
           top: int = 10) -> Dict:
    """Device seconds per chip by scope and phase inside the window.

    ``scopes``: {scope: {phase: s}}; ``remat_s``: the recompute phase,
    scoped or not; ``unscoped_s`` and ``unscoped_ops`` (the largest by
    instruction): ops under no scope; ``busy_s``: every op's self time,
    the scoped and the unscoped."""
    w0, w1 = window
    by_plane: Dict[str, List[DeviceOp]] = collections.defaultdict(list)
    for o in ops:
        by_plane[o.plane].append(o)
    if not by_plane:
        raise ValueError("the trace holds no device operations")
    n = len(by_plane)
    acc: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: dict.fromkeys(PHASES, 0.0))
    bare: Dict[str, float] = collections.defaultdict(float)
    remat = 0.0
    for plane_ops in by_plane.values():
        ivs = []
        for i, o in enumerate(plane_ops):
            a, b = max(o.start_ns, w0), min(o.start_ns + o.dur_ns, w1)
            if a < b:
                ivs.append((a, b, i))
        for i, t in tr._self_times(ivs).items():
            o = plane_ops[i]
            scope, phase = scope_of(o.op_name)
            t *= 1e-9 / n
            if phase == "recompute":
                remat += t
            if scope is None:
                bare[o.op] += t
            else:
                acc[scope][phase] += t
    scoped = sum(sum(p.values()) for p in acc.values())
    unscoped = sum(bare.values())
    return {
        "scopes": {k: dict(v) for k, v in acc.items()},
        "remat_s": remat,
        "unscoped_s": unscoped,
        "unscoped_ops": [[k, v] for k, v in sorted(
            bare.items(), key=lambda kv: -kv[1])[:top]],
        "busy_s": scoped + unscoped,
    }


def spans(host: Iterable[tr.Event], window: Tuple[float, float],
          prefix: str = "train.") -> Dict[str, Dict[str, float]]:
    """{name: {"s", "n"}} of the host spans named `prefix`... that
    overlap the window, each clipped to it."""
    w0, w1 = window
    out: Dict[str, Dict[str, float]] = {}
    for e in host:
        if not e.name.startswith(prefix):
            continue
        a, b = max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1)
        if a < b:
            s = out.setdefault(e.name, {"s": 0.0, "n": 0})
            s["s"] += (b - a) * 1e-9
            s["n"] += 1
    return out


def deltas(before: Dict[str, float], after: Dict[str, float]
           ) -> Dict[str, float]:
    """Counter readings `after` less `before`, by name."""
    return {k: after[k] - before.get(k, 0.0) for k in after}


# where `bench/run.py --trace 1` writes the window's trace
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".bench_trace")
_CACHE: Dict[Tuple[str, float], Optional[Dict]] = {}


def of_run(trace: Dict, root: str = TRACE_ROOT) -> Optional[Dict]:
    """The `scopes` of the newest trace under `root` when it is the one
    `trace` (`trace_reduce.reduce`'s output) was reduced from, else
    None.  Read once per trace, for all the readers of a run."""
    path = newest_xplane(root)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = None
        import jax
        ops, host = load(jax.profiler.ProfileData.from_file(path),
                         live_op_names())
        try:
            window = window_of(host)
            if abs((window[1] - window[0]) * 1e-9
                   - trace["window_s"]) <= 1e-6:
                _CACHE[key] = scopes(ops, window)
        except ValueError:          # no window, or no device operations
            pass
    return _CACHE[key]


def ms_per_step(ctx: Dict, scope: Optional[str]) -> Optional[Dict]:
    """A reader's reading: the device time of `scope` per train step in
    ms, its phases beside it; with `scope` None, the recompute phase,
    each scope's part beside it.  None where no op of the window carries
    it (a program without the scopes)."""
    got = of_run(ctx["trace"])
    if got is None or not ctx["steps"]:
        return None
    if scope is None:
        parts = {k: v["recompute"] for k, v in got["scopes"].items()}
        total = got["remat_s"]
    else:
        parts = got["scopes"].get(scope, {})
        total = sum(parts.values())
    if total <= 0:
        return None
    ms = 1e3 / ctx["steps"]
    out = {k: v * ms for k, v in parts.items()}
    out.update(value=total * ms, unit="ms")
    return out
