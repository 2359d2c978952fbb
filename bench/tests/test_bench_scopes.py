"""The fused step's device time by named scope, idle gaps labelled by the
program's own annotations (``bench/scopes.py``), and the collect
host-time reader, on synthetic events and on a small chip excerpt."""
import json
import os
import types

import pytest

from bench import scopes, tracing
from bench.metrics import collect_host_ms

HERE = os.path.dirname(os.path.abspath(__file__))
PLANE = "/device:TPU:0"
STEP = "jit(fn)/fused_step/position_scan/while/body/closed_call"
LAYER = STEP + "/layer_scan/while/body/closed_call"


def op(hlo, t0, t1, op_name=None, plane=PLANE):
    """A device operation event, and its op_name as the compiled
    program's text gives it."""
    return ({"name": hlo, "t0": t0, "t1": t1, "plane": plane}, op_name)


def synthetic():
    """Two executions of the fused program in a window [0, 20]: the first
    a ``while`` (the position scan) over scoped leaves, the second one
    leaf; a foreign program between them, and an execution that ends
    past the window."""
    host = [{"name": tracing.WINDOW, "t0": 0.0, "t1": 20.0, "plane": "h"}]
    mods = [{"name": n, "t0": a, "t1": b, "plane": PLANE}
            for n, a, b in [("jit_fn(1)", 1.0, 6.0), ("jit_other", 7.0, 8.0),
                            ("jit_fn(2)", 9.0, 10.0),
                            ("jit_fn(1)", 19.0, 21.0)]]
    ops = [
        op("while.1", 1.0, 6.0, STEP + "/while"),       # self 0.5
        op("fusion.2", 1.0, 2.0, LAYER + "/proj/dot_general"),
        op("fusion.3", 2.0, 3.5, LAYER + "/attention/mlp/exp"),
        op("fusion.4", 3.5, 4.0, STEP + "/row_keep/select_n"),
        op("fusion.5", 4.0, 5.0, LAYER + "/cache_write/scatter"),
        op("copy.6", 5.0, 5.5, "reduce_sum"),           # no scope
        op("fusion.7", 7.0, 8.0, LAYER + "/proj/dot"),  # foreign program
        op("fusion.8", 9.0, 10.0, "jit(fn)/fused_step/add"),
        op("fusion.9", 19.0, 21.0, LAYER + "/mlp/dot"),  # past the window
        op("fusion.10", 11.0, 12.0, LAYER + "/mlp/dot"),  # no execution
    ]
    names = {o["name"]: n for o, n in ops if n}
    return {"host": host, "modules": mods, "ops": [o for o, _ in ops]}, names


def test_scope_of_takes_the_innermost_named_scope():
    assert scopes.scope_of(LAYER + "/attention/mlp/exp") == "mlp"
    assert scopes.scope_of(STEP + "/while") == "position_scan"
    assert scopes.scope_of("reduce_sum") == scopes.UNSCOPED
    assert scopes.scope_of(None) == scopes.UNSCOPED


def test_self_times_count_a_while_without_its_body():
    ev, _ = synthetic()
    got = {o["name"]: s for o, s in scopes.self_times(ev["ops"][:6])}
    assert got["while.1"] == pytest.approx(0.5)
    assert got["fusion.3"] == pytest.approx(1.5)
    assert sum(got.values()) == pytest.approx(5.0)   # the while's span


def test_scope_seconds_from_leaf_self_time_inside_the_fused_step():
    ev, names = synthetic()
    steps = [{"prefill": True}, {"prefill": False}]
    sc = scopes.scope_seconds(ev, "jit_fn", (0.0, 20.0), names, steps)
    assert sc["paired"] and sc["executions"] == 2
    pre = sc["shapes"]["prefill"]
    assert pre["executions"] == 1
    assert pre["scopes"] == pytest.approx(
        {"position_scan": 0.5, "proj": 1.0, "mlp": 1.5, "row_keep": 0.5,
         "cache_write": 1.0, scopes.UNSCOPED: 0.5})
    assert pre["fused_leaf_s"] == pytest.approx(5.0)
    assert pre["top_ops"]["proj"] == [("fusion.2", pytest.approx(1.0))]
    assert sc["shapes"]["decode"]["scopes"] == {"fused_step": 1.0}
    # neither the foreign program's op, nor one outside every execution,
    # nor an execution that runs past the window counts
    assert sc["fused_leaf_s"] == pytest.approx(6.0)
    assert sc["scopes"]["proj"] == pytest.approx(1.0)


def test_unpaired_executions_are_told_apart_by_program_name():
    ev, names = synthetic()
    sc = scopes.scope_seconds(ev, "jit_fn", (0.0, 20.0), names,
                              [{"prefill": True}])
    assert not sc["paired"]
    assert set(sc["shapes"]) == {"jit_fn(1)", "jit_fn(2)"}


def test_shares_add_their_scopes_over_the_total():
    sh = scopes.shares({"row_keep": 2.0, "layer_scan": 3.0, "proj": 1.0,
                        "mlp": 1.0, "fused_step": 0.5, "kv_read": 2.5})
    assert sh["cache_rewrite_pct"] == pytest.approx(50.0)
    assert sh["weights_pct"] == pytest.approx(20.0)
    assert sh["kv_read_pct"] == pytest.approx(25.0)
    assert sh["catch_all_pct"] == pytest.approx(5.0)
    assert sh["lm_head_pct"] == 0.0
    assert scopes.shares({})["weights_pct"] is None


def test_idle_gaps_labelled_only_by_program_span_names():
    """Idle stretches of the synthetic window: [12, 19], then [0, 1],
    [6, 7], [8, 9] and [10, 11]. A gap takes the program annotation that
    covers it most, the innermost on a tie; an annotation whose name no
    program span uses labels nothing."""
    ev, _ = synthetic()
    ev["host"] += [
        {"name": "PjitFunction(fn)", "t0": 0.0, "t1": 1.0, "plane": "h"},
        {"name": "fused_step", "t0": 5.5, "t1": 9.5, "plane": "h"},
        {"name": "fused_call", "t0": 8.0, "t1": 9.0, "plane": "h"},
        {"name": "collect", "t0": 9.9, "t1": 13.0, "plane": "h"},
        {"name": "collect_wait", "t0": 10.0, "t1": 13.0, "plane": "h"},
    ]
    names = {"fused_step", "fused_call", "collect", "collect_wait"}
    got = scopes.idle_gaps(ev, (0.0, 20.0), names)
    assert [n for n, _, _ in got] == ["collect_wait", scopes.NO_SPAN,
                                      "fused_step", "fused_call",
                                      "collect_wait"]
    assert [d for _, d, _ in got] == pytest.approx([7.0, 1.0, 1.0, 1.0,
                                                    1.0])
    assert [t for _, _, t in got] == pytest.approx([12.0, 0.0, 6.0, 8.0,
                                                    10.0])


def test_op_name_from_the_compiled_text():
    assert scopes.hlo_op("%fusion.3 = bf16[8] fusion(...)") == "fusion.3"
    assert scopes.hlo_op("while.2") == "while.2"
    text = ('  %fusion.3 = bf16[8]{0} fusion(%p), kind=kLoop, '
            'metadata={op_name="jit(fn)/fused_step/sample/argmax" '
            'stack_frame_id=3}\n'
            '  ROOT %tuple.9 = (bf16[8]{0}) tuple(%fusion.3)\n'
            '  ROOT %while.2 = s32[] while(%t), body=%b, '
            'metadata={op_name="jit(fn)/fused_step/position_scan/while"}')
    assert scopes.op_names(text) == {
        "fusion.3": "jit(fn)/fused_step/sample/argmax",
        "while.2": "jit(fn)/fused_step/position_scan/while"}


def test_collect_host_ms_reads_the_wait_counter():
    run = types.SimpleNamespace(ticks=4.0, phases={
        "collect_time_s": 0.010, "collect_wait_time_s": 0.006})
    assert collect_host_ms.read(run) == pytest.approx(1.0)
    # a program that does not count the wait reads nothing
    run.phases = {"collect_time_s": 0.010}
    assert collect_host_ms.read(run) is None
    run.phases, run.ticks = {"collect_wait_time_s": 0.0}, 0.0
    assert collect_host_ms.read(run) is None


def excerpt():
    """A few decode-only fused executions recorded on a TPU v5e chip
    (qwen3-0.6b, reasoning traffic), as plain events: every program
    execution and every step dispatched while tracing, the device
    operations of the first executions, and the program's annotations."""
    with open(os.path.join(HERE, "data", "fused_excerpt.json")) as f:
        d = json.load(f)
    ns = 1e-9
    plane = "/device:TPU:0"
    ev = {"host": [{"name": n, "t0": a * ns, "t1": b * ns, "plane": "h"}
                   for n, a, b in d["host"]],
          "modules": [{"name": n, "t0": a * ns, "t1": b * ns,
                       "plane": plane} for n, a, b in d["modules"]],
          "ops": [{"name": h, "t0": a * ns, "t1": b * ns, "plane": plane}
                  for h, a, b in d["ops"]]}
    return d, ev


def test_chip_excerpt_pairs_executions_with_dispatched_steps():
    d, ev = excerpt()
    red = tracing.reduce(ev, d["program"], d["steps"], [])
    assert red["fused_executions"] == len(d["steps"]) > 0
    assert red["fused"] is not None
    sc = scopes.scope_seconds(ev, d["program"], scopes.window(ev),
                              d["op_names"], d["steps"])
    assert sc["paired"]


def test_chip_excerpt_leaf_time_is_under_named_scopes():
    d, ev = excerpt()
    sc = scopes.scope_seconds(ev, d["program"], scopes.window(ev),
                              d["op_names"], d["steps"])
    total = sc["fused_leaf_s"]
    assert sc["shapes"]["decode"]["executions"] == d["op_executions"] > 0
    named = total - sc["scopes"].get(scopes.CATCH_ALL, 0.0) \
        - sc["scopes"].get(scopes.UNSCOPED, 0.0)
    assert named >= 0.95 * total > 0
    # the executions' leaf time adds up to their device time
    execs = scopes.executions(ev, d["program"], scopes.window(ev))
    busy = sum(x["t1"] - x["t0"] for x in execs[:d["op_executions"]])
    assert total == pytest.approx(busy, rel=0.01)
