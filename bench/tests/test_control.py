"""The control comes out not correct: the reference, computed with 8-bit
floating-point operands and put in the program's place, read against the
f32 reference at the tiny cell's size, fails one of the numbers that a
sound program run passes (the limits of `test_faults.py`)."""

from __future__ import annotations

from bench.calibrate import CONTROL
from bench.drivers import train
from bench.tests.cells import tiny_cell
from bench.tests.test_faults import TRAIN_LIMITS


def _fails(nums, limits):
    return [k for k, lim in limits.items() if not nums[k] <= lim]


def test_train_control_fails():
    prog = train.Program(tiny_cell("train.s4096"))
    for seed in (1, 2, 3):
        ref = train.reference_readings(prog, seed)
        ctl = train.reference_readings(prog, seed, compute=CONTROL)
        assert _fails(train.compare(ctl, ref), TRAIN_LIMITS), seed

