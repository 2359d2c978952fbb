"""Model FLOP/s utilisation of the whole serving loop: the FLOPs that
the real tokens of the traced fused steps need (``family.step_work``),
over the traced window times the chip's bf16 peak."""
from bench.fused import executions


def read(run):
    x = executions(run)
    if not x:
        return None
    flops = sum(run.family.step_work(run.cfg, e["rows"])["flops"]
                for e in x)
    return 100.0 * flops / (run.trace["window_s"]
                            * run.peaks["bf16_flops_per_s"])
