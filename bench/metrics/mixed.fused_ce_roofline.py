"""`fused_ce_roofline` of the mixed-precision cell, where the loss's
operands are bf16, as its counts take them (`fused_ce_work`'s itemsize
2): the reader of `bench/metrics/fused_ce_roofline.py`, loaded by path,
reads the run."""
from bench import harness


def read(ctx):
    m = {"name": "fused_ce_roofline", "unit": "%"}
    return harness.read_layer_metrics([m], ctx).get(m["name"])
