"""Shared by the fused-step readers: the traced window's fused-step
executions, each with its device seconds and the rows it carried."""


def executions(run, prefill=None):
    """The executions of one shape (``prefill`` True or False), or of
    both where every shape paired with its steps (else none)."""
    fused = run.trace and run.trace.get("fused")
    if not fused or (prefill is None and run.trace["unpaired"]):
        return []
    return [x for x in fused if prefill is None or x["prefill"] == prefill]


def least_seconds(run, x):
    """The least time the chip could take for the work ``x`` needs: the
    larger of its FLOPs at peak and its bytes at peak bandwidth."""
    w = run.family.step_work(run.cfg, x["rows"])
    return max(w["flops"] / run.peaks["bf16_flops_per_s"],
               w["bytes"] / run.peaks["hbm_bytes_per_s"])
