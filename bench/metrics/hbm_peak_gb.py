"""Peak device memory in use over set-up and window (weights, caches and
the fused step's temporaries), from the runtime's ``memory_stats``."""


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
