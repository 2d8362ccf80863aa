"""Device time of the fused_ce_fwd, _dh and _dw kernels per train step,
per chip."""
from bench.trace_reduce import kernel_s


def read(ctx):
    t = kernel_s(ctx["trace"], "fused_ce_")
    if t <= 0 or not ctx["steps"]:
        return None
    return {"value": 1e3 * t / ctx["steps"], "unit": "ms"}
