"""Share of the fused step's device time spent rewriting the cache tree:
the ragged scan's per-row select (``row_keep``) and the layer scan's
slicing and restacking of each layer's cache (``layer_scan``). Percent of
the self time of the operations of every fused-step execution in the
traced window, each under its innermost named scope
(``bench/scopes.py``)."""
from bench.scopes import share


def read(run):
    return share(run, "cache_rewrite_pct")
