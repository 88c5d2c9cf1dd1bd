"""Peak device memory in use over the run, ``peak_bytes_in_use`` of the
fullest chip, in GB (1e9 bytes).  Program counter of the runtime."""


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
