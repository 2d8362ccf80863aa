"""Device time of the flash_attn_fwd, _dkv and _dq kernels per train step,
per chip: the attention core, forward, recompute and backward (inside
the `attn` scope, so `attn.ms_per_step` counts them too).  None on a
program whose attention runs no such kernel."""
from bench.trace_reduce import kernel_s


def read(ctx):
    t = kernel_s(ctx["trace"], "flash_attn_")
    if t <= 0 or not ctx["steps"]:
        return None
    return {"value": 1e3 * t / ctx["steps"], "unit": "ms"}
