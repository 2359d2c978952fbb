"""Device milliseconds per execution of the fused step in its
``[slots, 1]`` shape (a decode-only tick), from the profiler trace."""
from bench.fused import executions


def read(run):
    x = executions(run, prefill=False)
    return 1e3 * sum(e["dur_s"] for e in x) / len(x) if x else None
