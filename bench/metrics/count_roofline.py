"""Kernels: the count kernels' share of their roofline: the summed least
time of the window's executed batches (``bench/cost.py``: the bitmap
read once per level at the published HBM bandwidth) over the kernels'
summed device time."""
from bench.cost import COUNT_KERNELS, STEP_PROGRAM, floor_seconds


def read(run):
    t = run.trace
    if t is None:
        return None
    steps, secs = t.runs(STEP_PROGRAM), t.op_seconds(COUNT_KERNELS)
    if steps <= 0 or secs <= 0:
        return None
    return 100.0 * floor_seconds(run, steps) / secs
