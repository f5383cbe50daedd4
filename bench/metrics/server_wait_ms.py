"""Front end: mean over answered requests of the server's latency
(``ServeResponse.latency_ms``, from enqueue to resolution) less the
engine's (``QueryResult.latency_ms``, from engine submit to results on
the host): the time a request spent queued and batching in the server."""
import numpy as np


def read(run):
    w = [r for r in run.window_requests() if r.answered]
    if not w:
        return None
    return float(np.mean([r.resp_latency_ms - r.result.latency_ms
                          for r in w]))
