"""Peak device memory in use (``peak_bytes_in_use``) on the fullest
chip, read after the window, in GB (1e9 bytes)."""


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
