"""Model FLOP/s utilisation of the whole serving loop: the FLOPs that
the real tokens of the fused steps dispatched while tracing need
(``family.step_work``), over the traced window times the chip's bf16
peak. It reads no execution's duration, so it holds where the profiler
lost one."""


def read(run):
    steps = run.trace and run.trace["steps"]
    if not steps or not run.peaks:      # no peaks: not on a known chip
        return None
    flops = sum(run.family.step_work(run.cfg, s["rows"])["flops"]
                for s in steps)
    return 100.0 * flops / (run.trace["window_s"]
                            * run.peaks["bf16_flops_per_s"])
