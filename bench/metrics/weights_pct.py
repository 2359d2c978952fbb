"""Share of the fused step's device time spent on the layers' weights:
projections, norms and rotary (``proj``) and the ``mlp``. It grows as
the cache work around them shrinks. Percent of the self time of the
operations of every fused-step execution in the traced window, each
under its innermost named scope (``bench/scopes.py``)."""
from bench.scopes import share


def read(run):
    return share(run, "weights_pct")
