"""How ``correct`` is decided: served tokens against the float32 reference.

After the window, a sample of the requests that finished in it, drawn
from the seed and always holding the longest one, is run through the
configuration's plain reference (prompt plus served tokens, teacher
forced). At each position where a token was served, the *gap* is how far
the served token's reference logit lies below the reference's best
logit there; a sound greedy server reads a small gap, set by rounding
(its bfloat16 against the reference's float32) and by write gates that
sit near tau. The mean gap over the sample is held to the cell's limit
(``bench/limits/<cell>.json``), which ``PERF.md`` derives from readings.
The widest gap is reported beside it; a cell compares it only where its
limits name it, since on the chip the control's widest gap lies within
the reach of sound runs (a served token at a near tie) while its mean
lies many times above theirs.

``control`` reads the same gaps for the control: the reference computed
in the next precision below the served one (float8 e4m3 weights and
activations), taking at each position the token that the control puts
first.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List

import numpy as np

from bench.spec import BENCH_DIR

NUMBERS = ("max_logit_gap", "mean_logit_gap")


def limits(cell: str) -> Dict[str, float]:
    with open(os.path.join(BENCH_DIR, "limits", f"{cell}.json")) as f:
        return json.load(f)["limits"]


def sample(done: List, seed: int, tokens: int) -> List:
    """The longest finished request, then others in a seeded order, until
    ``tokens`` served tokens are held."""
    if not done:
        return []
    order = sorted(range(len(done)),
                   key=lambda i: -(len(done[i].prompt) + len(done[i].out)))
    rest = order[1:]
    np.random.default_rng(seed).shuffle(rest)
    picked, n = [], 0
    for i in [order[0]] + rest:
        if n >= tokens:
            break
        picked.append(done[i])
        n += len(done[i].out)
    return picked


def _pads(mix: Dict):
    return (mix["prompt"]["hi"] + mix["output"]["hi"],
            -(-mix["output"]["hi"] // 64) * 64)


def gaps(fam, cfg: Dict, mix: Dict, params, reqs: List, *,
         control: bool = False):
    """Per served position: reference best logit minus the reference logit
    of the served token; with ``control`` also minus that of the token the
    control puts first (else None)."""
    pad_to, at_pad = _pads(mix)
    served, ctl = [], []
    for r in reqs:
        p = len(r.prompt)
        g, c = fam.reference_gaps(
            cfg, params, list(r.prompt) + list(r.out[:-1]),
            list(range(p - 1, p - 1 + len(r.out))), r.out, pad_to=pad_to,
            at_pad=at_pad, control=control)
        served.append(g)
        if control:
            ctl.append(c)
    cat = lambda x: np.concatenate(x) if x else np.zeros((0,))
    return cat(served), (cat(ctl) if control else None)


def readings(g: np.ndarray) -> Dict[str, float]:
    """The numbers compared, from the per-position gaps."""
    if not g.size:
        return {k: float("inf") for k in NUMBERS}
    return {"max_logit_gap": float(g.max()),
            "mean_logit_gap": float(g.mean())}


def judge(g: np.ndarray, whole: bool, lims: Dict[str, float]) -> Dict:
    """``correct`` and each number compared beside its limit, from the
    per-position gaps ``g`` (a number without a limit in ``lims`` is
    reported, not compared)."""
    got = readings(g)
    checks = {k: {"value": v, "limit": lims.get(k)} for k, v in got.items()}
    checks["served_tokens_compared"] = {"value": int(g.size),
                                        "limit": lims["min_tokens_compared"]}
    correct = (whole and g.size >= lims["min_tokens_compared"]
               and all(np.isfinite(v) and v <= lims[k]
                       for k, v in got.items() if k in lims))
    return {"correct": bool(correct), "checks": checks}


def decide(fam, cfg: Dict, mix: Dict, seed: int, done: List,
           lims: Dict[str, float], *, vocab: int,
           control: bool = False) -> Dict:
    """``correct`` and the numbers compared, each beside its limit. With
    ``control`` also the control's verdict, judged by the same limits on
    the same sample (the benchmark's own runs never compute it)."""
    reqs = sample(done, seed, cfg["correctness"]["sample_tokens"])
    whole = all(len(r.out) == r.max_new and all(0 <= t < vocab
                                                 for t in r.out)
                for r in done)
    params = fam.make_weights(cfg, seed)
    g, ctl = gaps(fam, cfg, mix, params, reqs, control=control)
    del params
    out = judge(g, whole, lims)
    if ctl is not None:
        out["control"] = judge(ctl, True, lims)
    return out
