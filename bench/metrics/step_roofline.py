"""Query step: the whole executed step's share of the same least time as
``count_roofline``, over the device time of the window's ``cooc_plan_*``
programs.  It bounds the count kernels' share, and still reads when a
kernel leaves the path."""
from bench.cost import STEP_PROGRAM, floor_seconds


def read(run):
    t = run.trace
    if t is None:
        return None
    steps, secs = t.runs(STEP_PROGRAM), t.module_seconds(STEP_PROGRAM)
    if steps <= 0 or secs <= 0:
        return None
    return 100.0 * floor_seconds(run, steps) / secs
