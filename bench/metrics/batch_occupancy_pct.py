"""Engine: admitted requests per executed batch over ``q_batch``, from
the occupancy each answer carries (``QueryResult.batch_occupancy``).  A
batch of ``k`` answers ``k`` requests that each carry ``k``, so the
batches number ``sum(1 / k)`` over the requests."""


def read(run):
    occ = [r.result.batch_occupancy for r in run.window_requests()
           if r.answered and r.result.batch_occupancy > 0]
    if not occ:
        return None
    batches = sum(1.0 / k for k in occ)
    return 100.0 * len(occ) / batches / run.serving["q_batch"]
