"""The fused step's device time by named scope, and idle gaps labelled by
the program's own annotations, from a profiler trace.

The program names each piece of its fused step with ``jax.named_scope``
(:data:`SCOPES`); XLA keeps the name stack in each operation's ``op_name``
metadata. A device operation event of the profiler carries only the
operation's HLO name (on a TPU v5e its stats hold no ``op_name``), which
:func:`op_names` maps to its ``op_name`` through the compiled program's
text. The reduction takes, on the trace's own clock:

* ``scopes``: per shape of the fused step (``prefill``: the executions
  that carried a prompt chunk, ``decode``: the others), the seconds of
  each scope, counted as the self time of the device operations inside
  the fused step's executions in the window (an operation's duration
  less that of the operations nested in it, so a ``while`` does not
  count its body again), each under its innermost scope;
* ``fused_leaf_s``: the total of those seconds, per shape and in all;
* ``shares``: the :data:`SHARES` metrics, percent of those seconds, which
  a traced run reports as per-layer metrics (``bench/metrics/``);
* ``idle_gaps``: the longest idle stretches of the device, each labelled
  by the program's annotation that covers it most (the innermost on a
  tie), among the names its tick-lane spans use; the annotations are
  ``jax.profiler.TraceAnnotation`` events on the trace's own clock, so
  no host clock is shifted onto it.

``python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>``
runs a cell's ``--trace 1`` run (``bench/harness.py``) and prints this
reduction of its trace as one JSON line, beside the run's result.
"""
from __future__ import annotations

import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

if __name__ == "__main__":      # run as a script: the checkout's root
    sys.path[:0] = [os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))]

from bench import tracing  # noqa: E402

# the named scopes of the fused step, as the program names them; the
# innermost one an operation's op_name holds is its scope
SCOPES = ("embed", "proj", "write_gate", "cache_write", "kv_read",
          "attention", "mlp", "lm_head", "row_keep", "layer_scan",
          "position_scan", "sample", "fused_step")
CATCH_ALL = "fused_step"
UNSCOPED = "unscoped"
NO_SPAN = "host (no span)"

# the device-trace metrics over the scope seconds of a window (every
# execution of the fused step in it): name -> the scopes they add up
SHARES = {"cache_rewrite_pct": ("row_keep", "layer_scan"),
          "kv_read_pct": ("kv_read", "attention"),
          "gate_write_pct": ("write_gate", "cache_write"),
          "weights_pct": ("proj", "mlp"),
          "lm_head_pct": ("lm_head",)}

_INSTR = re.compile(r'^\s*(?:ROOT )?%([^\s=]+) = .*?op_name="([^"]*)"')


def scope_of(op_name: Optional[str]) -> str:
    """The innermost of :data:`SCOPES` in an op_name path, else
    :data:`UNSCOPED`."""
    if op_name:
        for part in reversed(op_name.split("/")):
            if part in SCOPES:
                return part
    return UNSCOPED


def op_names(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> its op_name, from a compiled program's
    ``as_text()`` (instruction names are unique in a module)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def hlo_op(name: str) -> str:
    """An operation event's HLO instruction name: its name up to the
    first space (``%fusion.3 = bf16[8] fusion(...)`` -> ``fusion.3``)."""
    return name.split(" ", 1)[0].lstrip("%")


def self_times(ops: Sequence[Dict]) -> List[Tuple[Dict, float]]:
    """Each operation with its self time: its duration less that of the
    operations nested directly in it (same device plane)."""
    out = []
    for plane in sorted({o["plane"] for o in ops}):
        evs = sorted((o for o in ops if o["plane"] == plane),
                     key=lambda o: (o["t0"], -o["t1"]))
        stack: List[List] = []          # [event, time of its children]
        done = []
        for o in evs:
            while stack and not (o["t0"] >= stack[-1][0]["t0"]
                                 and o["t1"] <= stack[-1][0]["t1"]):
                done.append(stack.pop())
            if stack:
                stack[-1][1] += o["t1"] - o["t0"]
            stack.append([o, 0.0])
        done += stack
        out += [(o, max(0.0, o["t1"] - o["t0"] - kids)) for o, kids in done]
    return out


def scope_seconds(ev: Dict, program: str, w: Tuple[float, float],
                  names: Dict[str, str],
                  steps: Optional[List[Dict]] = None) -> Dict:
    """Seconds per scope of the fused step's executions inside ``w``.

    ``names`` maps an HLO op to its op_name (:func:`op_names`). ``steps``
    (the steps dispatched while tracing, in order, each with ``prefill``)
    gives each execution its shape (``tracing.by_shape``); where it
    cannot, executions are told apart by their program name."""
    execs = tracing.executions(ev, program, w)
    by = tracing.by_shape(execs, steps or [])
    paired = bool(by) and all(len(xs) == len(ss) for xs, ss in by.values())
    shape_of = {id(x): shape for shape, (xs, _) in by.items() for x in xs}
    for x in execs:
        shape_of.setdefault(id(x), x["name"])
    by_plane: Dict[str, List[Dict]] = {}
    for x in execs:
        by_plane.setdefault(x["plane"], []).append(x)
    out: Dict[str, Dict] = {}
    j: Dict[str, int] = {p: 0 for p in by_plane}
    inside = []
    for o in sorted(ev["ops"], key=lambda o: o["t0"]):
        xs = by_plane.get(o["plane"])
        if not xs:
            continue
        k = j[o["plane"]]
        while k < len(xs) and xs[k]["t1"] < o["t0"]:
            k += 1
        j[o["plane"]] = k
        if k < len(xs) and xs[k]["t0"] <= o["t0"] and o["t1"] <= xs[k]["t1"]:
            inside.append((o, xs[k]))
    owner = {id(o): x for o, x in inside}
    ops: Dict[str, Dict[str, Dict[str, float]]] = {}
    for o, self_s in self_times([o for o, _ in inside]):
        x = owner[id(o)]
        shape = out.setdefault(shape_of[id(x)],
                               {"executions": set(), "scopes": {}})
        shape["executions"].add(id(x))
        op = hlo_op(o["name"])
        sc = scope_of(names.get(op))
        shape["scopes"][sc] = shape["scopes"].get(sc, 0.0) + self_s
        per_op = ops.setdefault(shape_of[id(x)], {}).setdefault(sc, {})
        per_op[op] = per_op.get(op, 0.0) + self_s
    total: Dict[str, float] = {}
    for name, shape in out.items():
        shape["executions"] = len(shape["executions"])
        shape["fused_leaf_s"] = sum(shape["scopes"].values())
        # each scope's three costliest operations (HLO names), for reading
        # which fusion a scope's time sits in
        shape["top_ops"] = {
            sc: sorted(per_op.items(), key=lambda kv: -kv[1])[:3]
            for sc, per_op in ops[name].items()}
        for sc, s in shape["scopes"].items():
            total[sc] = total.get(sc, 0.0) + s
    return {"paired": paired, "executions": len(execs), "shapes": out,
            "scopes": total, "fused_leaf_s": sum(total.values())}


def shares(scopes: Dict[str, float]) -> Dict[str, Optional[float]]:
    """The :data:`SHARES` metrics (percent of the scope seconds), and the
    catch-all's and the unscoped operations' shares."""
    total = sum(scopes.values())
    names = dict(SHARES, catch_all_pct=(CATCH_ALL,),
                 unscoped_pct=(UNSCOPED,))
    return {m: (100.0 * sum(scopes.get(s, 0.0) for s in parts) / total
                if total else None) for m, parts in names.items()}


def label(gap: Tuple[float, float], anns: Sequence[Dict]) -> str:
    """The annotation covering ``gap`` most, the innermost (shortest) on
    a tie, else :data:`NO_SPAN`."""
    best, name = (0.0, 0.0), NO_SPAN
    for a in anns:
        ov = min(a["t1"], gap[1]) - max(a["t0"], gap[0])
        key = (ov, -(a["t1"] - a["t0"]))
        if ov > 0 and key > best:
            best, name = key, a["name"]
    return name


def idle_gaps(ev: Dict, w: Tuple[float, float], names: Iterable[str],
              top: int = 10) -> List[List]:
    """The ``top`` longest idle stretches of the first device in ``w``,
    each as [label, seconds, seconds from the window's start], labelled
    by :func:`label` among the trace's host annotations whose names are
    in ``names`` (the program's tick-lane span names)."""
    names = set(names)
    devices = sorted({e["plane"] for e in ev["ops"]})
    if not devices:
        return []
    busy = tracing.union(tracing.clip(
        [(e["t0"], e["t1"]) for e in ev["ops"] if e["plane"] == devices[0]],
        w))
    anns = [a for a in ev["host"] if a["name"] in names]
    idle = sorted(tracing.gaps(busy, w), key=lambda g: g[0] - g[1])[:top]
    return [[label(g, anns), g[1] - g[0], g[0] - w[0]] for g in idle]


def window(ev: Dict) -> Optional[Tuple[float, float]]:
    win = [e for e in ev["host"] if e["name"] == tracing.WINDOW]
    return (win[0]["t0"], win[0]["t1"]) if win else None


def reduce(ev: Dict, program: str, names: Dict[str, str],
           steps: Optional[List[Dict]],
           tick_names: Iterable[str]) -> Optional[Dict]:
    """The whole reduction of a traced window (None without one)."""
    w = window(ev)
    if w is None:
        return None
    sc = scope_seconds(ev, program, w, names, steps)
    gaps = idle_gaps(ev, w, tick_names)
    return dict(sc, shares=shares(sc["scopes"]), idle_gaps=gaps,
                longest_gap_cover=cover(ev, w, gaps[0]) if gaps else [])


def cover(ev: Dict, w: Tuple[float, float], gap: List) -> List[List]:
    """Every host event overlapping an :func:`idle_gaps` entry, as [name,
    seconds of overlap, start and end from the window's start]: what the
    host did while the device waited."""
    g0 = w[0] + gap[2]
    g1 = g0 + gap[1]
    out = [[a["name"], min(a["t1"], g1) - max(a["t0"], g0),
            a["t0"] - w[0], a["t1"] - w[0]]
           for a in ev["host"] if a["t0"] < g1 and a["t1"] > g0
           and a["name"] != tracing.WINDOW]
    return sorted(out, key=lambda c: -c[1])[:20]


def tick_span_names(tracer) -> set:
    return {s.name for s in tracer.spans if s.lane[0] == "tick"}


def fused_hlo_texts(eng, chunk: int) -> List[str]:
    """The compiled text of the engine's fused step in its two shapes
    (read from the persistent compilation cache after warm-up)."""
    import jax
    import jax.numpy as jnp
    b = eng.slots
    out = []
    for s in (chunk, 1):
        feed = (jax.ShapeDtypeStruct((b, s), jnp.int32),
                jax.ShapeDtypeStruct((b,), jnp.int32),
                jax.ShapeDtypeStruct((b,), jnp.int32),
                jax.ShapeDtypeStruct((b,), jnp.bool_),
                jax.ShapeDtypeStruct((1, 2), jnp.uint32))
        out.append(eng._fused.lower(eng.params, feed, eng.caches)
                   .compile().as_text())
    return out


def share(run, metric: str) -> Optional[float]:
    """A :data:`SHARES` metric of a traced run (``harness.Run``): None
    where the run holds no scope reduction or no fused-step time."""
    sc = run.trace and run.trace.get("scopes")
    return sc["shares"][metric] if sc else None


def main(argv=None) -> int:
    """Run one cell's traced run and print this reduction of its trace.

    The harness reduces its trace (``harness.reduce_trace``) and then
    deletes it; this keeps the whole scope reduction of that run, and the
    count and seconds of each tick-lane span, and prints them beside the
    run's result."""
    import argparse
    import json
    import time

    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=main.__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-s", type=float, default=None,
                    help="trace the window's last this many seconds")
    args = ap.parse_args(argv)

    import jax

    from bench import harness, run, spec

    bm = spec.load_benchmark()
    cell = spec.cell(bm, args.workload)
    harness.check_devices(jax.devices(), cell["chips"])
    run.configure_jax(jax)
    mix = spec.traffic(cell["traffic"])
    if args.trace_s is not None:
        mix["trace"] = dict(mix.get("trace", {}), seconds=args.trace_s)
    found: Dict = {}
    reduce_trace = harness.reduce_trace

    def reduce_trace_(traced, tracer, *a):
        red = reduce_trace(traced, tracer, *a)
        found["scopes"] = red and red["scopes"]
        spans: Dict[str, List[float]] = {}
        for sp in tracer.spans:
            if sp.lane[0] == "tick":
                n_s = spans.setdefault(sp.name, [0, 0.0])
                n_s[0] += 1
                n_s[1] += sp.t1 - sp.t0
        found["tick_spans"] = spans
        return red

    harness.reduce_trace = reduce_trace_
    try:
        result = harness.run_cell(bm, cell, args.seed, args.seconds, True,
                                  t_start=t_start, trace_dir=run.TRACE_DIR,
                                  mix=mix)
    finally:
        harness.reduce_trace = reduce_trace
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "device_scopes": found.get("scopes"),
                      # count and seconds of each tick-lane span, whole run
                      "tick_spans": found.get("tick_spans"),
                      "result": result}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
