"""The trace reduction: busy union, fused-step executions paired with the
dispatched steps, idle gaps labelled by the host's tick phase."""
import json
import os

import pytest

from bench import tracing

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_and_gaps():
    busy = tracing.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert tracing.gaps(busy, (-1.0, 5.0)) == [(-1.0, 0.0), (2.0, 3.0),
                                               (4.0, 5.0)]
    assert tracing.clip(busy, (1.0, 3.5)) == [(1.0, 2.0), (3.0, 3.5)]


def test_label_picks_the_most_overlapping_span():
    spans = [("admit", 0.0, 1.1), ("fused_step", 1.1, 3.0)]
    assert tracing.label((1.0, 2.0), spans) == "fused_step"
    assert tracing.label((5.0, 6.0), spans) == "host (no span)"


def synthetic():
    """Two device ops per execution of a two-step window, and a foreign
    program between them."""
    host = [{"name": tracing.WINDOW, "t0": 0.0, "t1": 10.0, "plane": "h"}]
    mods, ops = [], []
    for t0, t1, name in [(1.0, 3.0, "jit_fn(7)"), (4.0, 4.5, "jit_other"),
                         (6.0, 7.0, "jit_fn(9)")]:
        mods.append({"name": name, "t0": t0, "t1": t1, "plane": "/device:0"})
        mid = (t0 + t1) / 2
        ops += [{"name": "fusion.1", "t0": t0, "t1": mid,
                 "plane": "/device:0"},
                {"name": "while.2", "t0": mid, "t1": t1,
                 "plane": "/device:0"}]
    return {"host": host, "modules": mods, "ops": ops}


def test_reduce_synthetic():
    steps = [{"prefill": True, "rows": []}, {"prefill": False, "rows": []}]
    spans = [("collect", 3.0, 6.0), ("fused_step", 7.0, 7.5)]
    red = tracing.reduce(synthetic(), "jit_fn", steps, spans)
    assert red["window_s"] == 10.0
    assert red["busy_s"] == pytest.approx(3.5)
    assert [f["dur_s"] for f in red["fused"]] == [2.0, 1.0]
    assert [f["prefill"] for f in red["fused"]] == [True, False]
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0] == ["fused_step", pytest.approx(3.0)]
    assert ["host (no span)", pytest.approx(1.0)] in gaps
    assert ["collect", pytest.approx(1.5)] in gaps
    assert red["breakdown"]["device_ops"][0][0] in ("fusion.1", "while.2")


def test_reduce_refuses_to_pair_unequal_lists():
    red = tracing.reduce(synthetic(), "jit_fn", [{"prefill": True}], [])
    assert red["fused"] is None and red["fused_executions"] == 2
