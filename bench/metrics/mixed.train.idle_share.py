"""`train.idle_share` of the mixed-precision cell: the reader of
`bench/metrics/train.idle_share.py`, loaded by path, reads the run."""
from bench import harness


def read(ctx):
    m = {"name": "train.idle_share", "unit": "%"}
    return harness.read_layer_metrics([m], ctx).get(m["name"])
