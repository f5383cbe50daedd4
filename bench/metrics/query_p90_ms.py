"""90th percentile of the latency of every request due in the window:
from the time it was due (open loop) or sent (closed loop, probes) to
its answer.  A request left unanswered counts as beyond every limit."""
import numpy as np


def read(run):
    w = run.window_requests()
    if not w:
        return None
    lat = np.asarray([(r.done - r.due) * 1e3 if r.answered else np.inf
                      for r in w])
    p90 = float(np.percentile(lat, 90))
    return p90 if np.isfinite(p90) else None
