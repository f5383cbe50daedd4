"""Mean over the blocks ingested in the window: from the start of
``server.ingest`` to the first answer whose epoch includes the block."""
import numpy as np


def read(run):
    from bench.harness import first_visible
    ms = []
    for g in run.window_blocks():
        r = first_visible(run, g)
        if r is None:
            return None
        ms.append((r.done - g.start) * 1e3)
    return float(np.mean(ms)) if ms else None
