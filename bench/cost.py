"""What the query step has to read at least, and how its kernels and
programs are named in a trace.

The least bytes of one executed batch are the bitmap index read once per
BFS level: depth x V x ceil(capacity / 32) x 4 bytes, whichever count
method or kernel runs.  No operation bound is counted: no peak is
published for the VPU's popcount.  A layout that reads less than the
whole bitmap (hybrid or sparse postings, tiles skipped by scope) needs
this count redone.
"""

#: the Pallas kernels that read the postings bitmap, by the names their
#: operations carry in the trace
COUNT_KERNELS = ("level_step", "postings")
#: the executed query step, by its program's name
STEP_PROGRAM = "cooc_plan_"


def floor_bytes(run) -> float:
    """Bytes one executed batch has to read at least."""
    words = -(-run.capacity // 32)
    return (run.serving["depth"] * run.cell.config["corpus"]["vocab"]
            * words * 4.0)


def floor_seconds(run, steps: float) -> float:
    return steps * floor_bytes(run) / run.peaks["hbm_bytes_per_s"]
