"""From process start to the first request of the window: corpus,
index, transfer, server start and warm-up, compiles included."""


def read(run):
    return run.setup_seconds
