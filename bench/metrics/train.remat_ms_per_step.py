"""Device time of the ops that a checkpoint recomputes in the backward
pass (`rematted_computation` in their op_name), per train step, per
chip, each scope's share beside the sum (`bench/scope_reduce.py`)."""
from bench import scope_reduce


def read(ctx):
    return scope_reduce.ms_per_step(ctx, None)
