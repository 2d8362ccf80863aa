"""`fused_ce.ms_per_step` of the mixed-precision cell: the reader of
`bench/metrics/fused_ce.ms_per_step.py`, loaded by path, reads the
run."""
from bench import harness


def read(ctx):
    m = {"name": "fused_ce.ms_per_step", "unit": "ms"}
    return harness.read_layer_metrics([m], ctx).get(m["name"])
