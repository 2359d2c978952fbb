"""Device milliseconds per execution of the fused step in its
``[slots, chunk]`` shape (a tick that carries a prompt chunk), from the
profiler trace."""
from bench.fused import executions


def read(run):
    x = executions(run, prefill=True)
    return 1e3 * sum(e["dur_s"] for e in x) / len(x) if x else None
