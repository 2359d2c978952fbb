"""The trace reduction: busy union, fused-step executions paired with the
dispatched steps, idle gaps labelled by the host's tick phase."""
import json
import os
import types

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


def shaped(lost):
    """A window of five fused steps, prefill-shape (program ``jit_fn(7)``,
    3 s) and decode-only (``jit_fn(9)``, 1 s) in turn, with the
    executions at the indices ``lost`` missing from the trace; and the
    steps the step log saw dispatched."""
    kinds = [True, False, True, False, False]
    host = [{"name": tracing.WINDOW, "t0": 0.0, "t1": 40.0, "plane": "h"}]
    mods, t = [], 1.0
    for i, pre in enumerate(kinds):
        d = 3.0 if pre else 1.0
        if i not in lost:
            mods.append({"name": "jit_fn(7)" if pre else "jit_fn(9)",
                         "t0": t, "t1": t + d, "plane": "/device:0"})
        t += d + 1.0
    steps = [{"prefill": pre, "rows": []} for pre in kinds]
    return {"host": host, "modules": mods, "ops": []}, steps


@pytest.mark.parametrize("lost,durations,unpaired", [
    # a lost decode execution: the prefill shape keeps its durations
    ((3,), {True: [3.0, 3.0]}, ["decode"]),
    # a loss in both shapes: no durations
    ((0, 4), {}, ["prefill", "decode"]),
], ids=["decode_lost", "both_lost"])
def test_reduce_refuses_to_pair_unequal_lists(lost, durations, unpaired):
    from bench.metrics import fused_step_roofline, step_decode_ms, \
        step_prefill_ms
    ev, steps = shaped(lost)
    red = tracing.reduce(ev, "jit_fn", steps, [])
    assert red["unpaired"] == unpaired
    assert red["fused_executions"] == 5 - len(lost)
    got = {}
    for f in red["fused"] or []:
        got.setdefault(f["prefill"], []).append(f["dur_s"])
    assert got == durations
    run = types.SimpleNamespace(trace=red)
    pre = step_prefill_ms.read(run)
    assert pre == (pytest.approx(3000.0) if True in durations else None)
    assert step_decode_ms.read(run) is None
    # the whole step's roofline needs every shape paired
    assert fused_step_roofline.read(run) is None


def test_reduce_pairs_within_each_shape():
    ev, steps = shaped(())
    red = tracing.reduce(ev, "jit_fn", steps, [])
    assert red["unpaired"] == []
    assert red["shape_counts"] == {"prefill": [2, 2], "decode": [3, 3]}
    assert [(f["prefill"], f["dur_s"]) for f in red["fused"]] == [
        (True, 3.0), (False, 1.0), (True, 3.0), (False, 1.0), (False, 1.0)]
    # programs that do not match the step log's shapes in number pair
    # with nothing
    red = tracing.reduce(synthetic(), "jit_fn", [{"prefill": True}], [])
    assert red["fused"] is None and red["fused_executions"] == 2
