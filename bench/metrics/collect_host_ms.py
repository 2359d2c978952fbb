"""Host milliseconds per orchestrator tick spent in ``collect`` besides
waiting for the device: the tick phase ``collect_time_s`` less the
engine's ``collect_wait_time_s`` (the ``device_get`` that blocks on the
fused step), from the program's own counters over the window. None where
the program does not count the wait."""


def read(run):
    wait = run.phases.get("collect_wait_time_s")
    if wait is None or not run.ticks:
        return None
    return 1e3 * (run.phases.get("collect_time_s", 0.0) - wait) / run.ticks
