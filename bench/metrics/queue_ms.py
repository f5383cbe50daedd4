"""Front end: mean over answered requests of ``QueryResult.queue_ms``,
the time the server held a request in its queue, from enqueue until its
batch left the queue (the batcher's linger included): the request's own
share of ``server_wait_ms``.  A program whose results carry no such
field reads nothing."""
import numpy as np


def read(run):
    w = [r.result.queue_ms for r in run.window_requests()
         if r.answered and hasattr(r.result, "queue_ms")]
    return float(np.mean(w)) if w else None
