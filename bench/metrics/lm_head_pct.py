"""Share of the fused step's device time spent in the tied LM head
(``lm_head``). Percent of the self time of the operations of every
fused-step execution in the traced window, each under its innermost
named scope (``bench/scopes.py``)."""
from bench.scopes import share


def read(run):
    return share(run, "lm_head_pct")
