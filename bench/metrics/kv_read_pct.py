"""Share of the fused step's device time spent reading the cache: the
``[gk|lk]`` concat and selection gather (``kv_read``) and ``attention``.
Percent of the self time of the operations of every fused-step execution
in the traced window, each under its innermost named scope
(``bench/scopes.py``)."""
from bench.scopes import share


def read(run):
    return share(run, "kv_read_pct")
