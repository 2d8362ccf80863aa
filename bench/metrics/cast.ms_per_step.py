"""Device time of the ops under the program's `cast` scope (the master
weights cast to the compute dtype, and their gradient cast back) per
train step, per chip: forward and backward, each beside the sum, the
phases where XLA fused no cast into its neighbours left out.

The window's trace is read with the loaders of `bench/scope_reduce.py`,
whose scope tuple does not hold `cast`: an op is the cast's when `cast`
is the innermost of its op_name's components that is `cast` or one of
that tuple's scopes.  A cast fused into the op that consumes it takes
that op's op_name, and is counted with that op's layer.  The cast's ops
are converts and their fusions, which hold no other op, so their time
is their duration.  None on a program without the scope."""
import re

from bench import scope_reduce as sr

SCOPE = "cast"
_WRAPPED = re.compile(r"^(?!p?jit\()\w+\((.*)\)$")


def _bare(comp):
    m = _WRAPPED.match(comp)
    while m:
        comp = m.group(1)
        m = _WRAPPED.match(comp)
    return comp


def phase(op_name):
    """"forward" or "backward" of an op under `cast`, else None."""
    if not op_name:
        return None
    path = op_name.split(";", 1)[0]
    inner = next((c for c in map(_bare, reversed(path.split("/")))
                  if c == SCOPE or c in sr.SCOPES), None)
    if inner != SCOPE:
        return None
    return "backward" if "transpose(" in path else "forward"


def read(ctx):
    import jax
    path = sr.newest_xplane(sr.TRACE_ROOT)
    if path is None or not ctx["steps"]:
        return None
    ops, host = sr.load(jax.profiler.ProfileData.from_file(path),
                        sr.live_op_names())
    try:
        w0, w1 = sr.window_of(host)
    except ValueError:
        return None
    if abs((w1 - w0) * 1e-9 - ctx["trace"]["window_s"]) > 1e-6 or not ops:
        return None
    acc = {"forward": 0.0, "backward": 0.0}
    for o in ops:
        p = phase(o.op_name)
        a, b = max(o.start_ns, w0), min(o.start_ns + o.dur_ns, w1)
        if p and a < b:
            acc[p] += b - a
    total = sum(acc.values())
    if total <= 0:
        return None
    ms = 1e-6 / len({o.plane for o in ops}) / ctx["steps"]
    out = {k: v * ms for k, v in acc.items() if v > 0}
    out.update(value=total * ms, unit="ms")
    return out
