"""`train.mfu` of the mixed-precision cell: the reader of
`bench/metrics/train.mfu.py`, loaded by path, reads the run."""
from bench import harness


def read(ctx):
    m = {"name": "train.mfu", "unit": "%"}
    return harness.read_layer_metrics([m], ctx).get(m["name"])
