"""Host milliseconds per orchestrator tick, in every phase but ``collect``
(admit, fused-step dispatch, evict, memory sample), from the program's
own tick-phase timers (``Telemetry.phase_times``) over the window.
``collect`` is left out: with dispatch-ahead it mixes the wait for the
device with host work."""

HOST_PHASES = ("admit_time_s", "dispatch_time_s", "evict_time_s",
               "memory_sample_time_s", "prefill_time_s",
               "prefix_capture_time_s")


def read(run):
    if not run.ticks:
        return None
    return 1e3 * sum(run.phases.get(k, 0.0) for k in HOST_PHASES) \
        / run.ticks
