"""Index: mean time of the benchmark's span around each
``server.ingest`` call in the window (waiting for the lane's lock, the
host padding, the scatter and the epoch bump)."""
import numpy as np


def read(run):
    blocks = run.window_blocks()
    if not blocks:
        return None
    return float(np.mean([(g.end - g.start) * 1e3 for g in blocks]))
