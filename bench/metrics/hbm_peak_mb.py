"""Peak device memory in use over set-up and window (the fullest chip's
``peak_bytes_in_use``), in 10^6 bytes."""


def read(run):
    return run.memory_peak_bytes / 1e6 if run.memory_peak_bytes else None
