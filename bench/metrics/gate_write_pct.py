"""Share of the fused step's device time spent in the write gate
(``write_gate``) and writing new K/V into the dual cache
(``cache_write``). Percent of the self time of the operations of every
fused-step execution in the traced window, each under its innermost
named scope (``bench/scopes.py``)."""
from bench.scopes import share


def read(run):
    return share(run, "gate_write_pct")
