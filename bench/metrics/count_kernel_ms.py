"""Kernels: device time of the Pallas kernels that read the postings
bitmap, over the window, per executed query step (``cooc_plan_*``
program runs in the same window)."""
from bench.cost import COUNT_KERNELS, STEP_PROGRAM


def read(run):
    t = run.trace
    if t is None:
        return None
    steps, secs = t.runs(STEP_PROGRAM), t.op_seconds(COUNT_KERNELS)
    if steps <= 0 or secs <= 0:
        return None
    return 1e3 * secs / steps
