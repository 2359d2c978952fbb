"""Reduction of a profiler trace to what the per-layer metrics read.

A traced run records one stretch of its window with ``jax.profiler``
(python tracer off) inside a host annotation ``bench_trace_window``. The
reduction takes, all on the trace's own clock:

* ``busy_s``: the union of the intervals in which a device operation
  ran (``XLA Ops`` lines of the device planes), inside the window;
* ``fused``: each execution of the fused step's program (``XLA Modules``
  line, by the program's name), paired in order with the steps the
  harness saw dispatched while tracing, within each of the step's two
  shapes (the profiler starts and stops with nothing in flight, so both
  lists hold the same steps, unless the profiler lost an execution's
  events: then that shape alone goes unpaired);
* ``breakdown``: the device operations that took most time, and the
  longest idle gaps, each labelled by the serving loop's tick phase
  that was open on the host during it (the program's own span tracer,
  shifted onto the trace's clock by the window annotation).
"""
from __future__ import annotations

import glob
import os
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench_trace_window"
# the fused step's two shapes, as the step log tells them apart
SHAPES = ("prefill", "decode")
Interval = Tuple[float, float]


def load(trace_dir: str) -> Dict[str, List[Dict]]:
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain events."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    return events(ProfileData.from_file(max(paths, key=os.path.getmtime)))


def events(pd) -> Dict[str, List[Dict]]:
    """A ``jax.profiler.ProfileData`` as plain events (seconds): device
    ops, device program executions, host events."""
    out = {"ops": [], "modules": [], "host": []}
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            kind = ("ops" if line.name == "XLA Ops" else
                    "modules" if line.name == "XLA Modules" else None) \
                if device else "host"
            if kind is None:
                continue
            for e in line.events:
                out[kind].append({"name": e.name, "t0": e.start_ns * 1e-9,
                                  "t1": e.end_ns * 1e-9,
                                  "plane": plane.name})
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def clip(intervals: Sequence[Interval], w: Interval) -> List[Interval]:
    return [(max(a, w[0]), min(b, w[1])) for a, b in intervals
            if b > w[0] and a < w[1]]


def gaps(busy: Sequence[Interval], w: Interval) -> List[Interval]:
    """Idle stretches of the window between merged busy intervals."""
    out, t = [], w[0]
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < w[1]:
        out.append((t, w[1]))
    return out


def label(gap: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The host span overlapping ``gap`` most, or ``host (no span)``."""
    best, name = 0.0, "host (no span)"
    for n, a, b in spans:
        ov = min(b, gap[1]) - max(a, gap[0])
        if ov > best:
            best, name = ov, n
    return name


def executions(ev: Dict[str, List[Dict]], program: str,
               w: Interval) -> List[Dict]:
    """The fused program's executions inside the window, in order."""
    return sorted((e for e in ev["modules"]
                   if e["name"].split("(")[0] == program
                   and e["t0"] >= w[0] and e["t1"] <= w[1]),
                  key=lambda e: e["t0"])


def by_shape(execs: List[Dict], steps: List[Dict]) -> Dict[str, Tuple]:
    """The fused step's executions and the steps dispatched, split by the
    step's shape: ``{"prefill": (executions, steps), "decode": (...)}``.

    A step's shape is its ``prefill`` flag (a ``[slots, chunk]`` step
    carried a prompt chunk, a ``[slots, 1]`` one did not). An execution's
    is its program: each shape is a program of its own, whose name
    carries the compiled program's fingerprint. The programs, ranked by
    their median duration, take the shapes present in the steps in the
    order prefill, decode: a chunk of positions takes longer than one.
    Where programs and shapes differ in number, no execution is given a
    shape."""
    shapes = {k: [s for s in steps if s["prefill"] == (k == "prefill")]
              for k in SHAPES}
    shapes = {k: ss for k, ss in shapes.items() if ss}
    progs: Dict[str, List[Dict]] = {}
    for e in execs:
        progs.setdefault(e["name"], []).append(e)
    ranked = sorted(progs.values(), key=lambda xs: -statistics.median(
        x["t1"] - x["t0"] for x in xs))
    if len(ranked) != len(shapes):
        ranked = [[] for _ in shapes]
    return {k: (xs, shapes[k]) for k, xs in zip(shapes, ranked)}


def reduce(ev: Dict[str, List[Dict]], program: str, steps: List[Dict],
           spans: Sequence[Tuple[str, float, float]]) -> Optional[Dict]:
    """``ev`` as :func:`load` returns it; ``program`` the fused step's
    program name; ``steps`` the steps dispatched while tracing, in order;
    ``spans`` host tick-phase spans (name, t0, t1) on the trace clock."""
    win = [e for e in ev["host"] if e["name"] == WINDOW]
    if not win:
        return None
    w = (win[0]["t0"], win[0]["t1"])
    devices = sorted({e["plane"] for e in ev["ops"]}) or [None]
    busy_by_dev = {d: union(clip([(e["t0"], e["t1"]) for e in ev["ops"]
                                  if e["plane"] == d], w)) for d in devices}
    busy_s = sum(b - a for iv in busy_by_dev.values() for a, b in iv) \
        / len(devices)
    per_op: Dict[str, float] = {}
    for e in ev["ops"]:
        a, b = max(e["t0"], w[0]), min(e["t1"], w[1])
        if b > a:
            per_op[e["name"]] = per_op.get(e["name"], 0.0) + (b - a)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    idle = gaps(busy_by_dev[devices[0]], w)
    top_gaps = sorted(idle, key=lambda g: g[0] - g[1])[:10]
    execs = executions(ev, program, w)
    # within a shape, executions and steps pair in order where their
    # counts agree; a shape whose counts differ gives no duration
    paired, counts, unpaired = [], {}, []
    for shape, (xs, ss) in by_shape(execs, steps).items():
        counts[shape] = [len(xs), len(ss)]
        if len(xs) == len(ss):
            paired += [(x["t0"], dict(s, dur_s=x["t1"] - x["t0"]))
                       for x, s in zip(xs, ss)]
        else:
            unpaired.append(shape)
    fused = [f for _, f in sorted(paired, key=lambda p: p[0])]
    return {"busy_s": busy_s, "window_s": w[1] - w[0],
            "fused": fused or None, "unpaired": unpaired, "steps": steps,
            "fused_executions": len(execs), "shape_counts": counts,
            "breakdown": {
                "device_ops": [[n, s] for n, s in top_ops],
                "idle_gaps": [[label(g, spans), g[1] - g[0]]
                              for g in top_gaps]}}


def reduce_run(ev: Dict[str, List[Dict]], traced: Dict, tracer,
               program: str) -> Optional[Dict]:
    """Reduce a harness-traced run: its trace (as :func:`load` returns
    it), the steps its step log saw dispatched while tracing, and the
    program's tick-phase spans shifted onto the trace's clock."""
    steps = [s for s in traced["steplog"].steps if s["traced"]]
    win = [e for e in ev["host"] if e["name"] == WINDOW]
    shift = (win[0]["t0"] - traced["t0_mono"]) if win else 0.0
    spans = [(s.name, s.t0 + shift, s.t1 + shift) for s in tracer.spans
             if s.lane[0] == "tick" and s.t1 > s.t0]
    return reduce(ev, program, steps, spans)
