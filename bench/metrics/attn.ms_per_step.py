"""Device time of the ops under the program's `attn` scope (projections,
q/k norm, rope and blockwise attention) per train step, per chip:
forward, recompute and backward, each beside the sum
(`bench/scope_reduce.py`)."""
from bench import scope_reduce


def read(ctx):
    return scope_reduce.ms_per_step(ctx, "attn")
