"""Device time of the ops under the program's `optimizer` scope
(gradient clipping and AdamW over the weights and both moments) per
train step, per chip (`bench/scope_reduce.py`)."""
from bench import scope_reduce


def read(ctx):
    return scope_reduce.ms_per_step(ctx, "optimizer")
