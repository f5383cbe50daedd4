"""Query step: mean ``QueryResult.latency_ms``, from the engine's submit
to the results on the host (the step that served the request, and any
step of another plan it waited behind inside the engine)."""
import numpy as np


def read(run):
    w = [r.result.latency_ms for r in run.window_requests() if r.answered]
    return float(np.mean(w)) if w else None
