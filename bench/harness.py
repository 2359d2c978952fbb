"""The window loop: one cell, one seed, one process.

``run_cell`` builds the served system from the cell's configuration file
(weights from the seed, on the device), warms up the fused step's two
shapes, drives the cell's traffic through ``ServeSession`` for the
measured window, and times every token as the client sees it arrive.
After the window it reads the device's peak memory, frees the program's
state, and decides ``correct`` by comparing the served tokens of a seeded
sample of finished requests with the float32 reference
(``bench/check.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import statistics
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from bench import check, generator, spec
from bench.peaks import peaks_for

# traced runs: the profiler records the window's last ``seconds``, as the
# mix's ``trace`` entry says (a stretch that holds the steps the cell's
# per-layer metrics read, and a trace small enough to read within the
# run's time limit). It stops, and its trace is read, after the window
# has closed: stopping takes the host many seconds, in which nothing
# would be served.
TRACE_DEFAULT = {"seconds": 6.0}


class DeviceError(RuntimeError):
    pass


def check_devices(devices, chips: int) -> None:
    """Refuse anything but enough TPU chips: a CPU number is never a
    device metric."""
    if not devices or devices[0].platform != "tpu":
        plat = devices[0].platform if devices else None
        raise DeviceError(f"no TPU: JAX found platform {plat!r}")
    if len(devices) < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX found "
                          f"{len(devices)}")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Programs compiled (or read from the persistent cache) since start,
    from ``jax.monitoring``'s backend-compile events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1


@dataclasses.dataclass
class Rec:
    """One request as its client sees it."""
    prompt: List[int]
    max_new: int
    due: float                       # when it was due (host clock)
    times: List[float] = dataclasses.field(default_factory=list)
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class Run:
    """What the per-layer metric readers see (``bench/metrics/*.py``)."""
    cfg: Dict
    family: object
    peaks: Dict[str, float]
    ticks: float                     # orchestrator ticks in the window
    phases: Dict[str, float]         # tick phase seconds in the window
    steps: List[Dict]                # every fused step collected
    trace: Optional[Dict]            # reduced profiler trace, traced runs
    memory_peak_bytes: int


class StepLog:
    """Records what each fused step carried, at dispatch and collect, by
    wrapping the engine's two calls (traced runs only)."""

    def __init__(self, eng):
        import jax
        self.jax, self.steps = jax, []
        self._pending, self._kv = {}, np.zeros(eng.slots)
        self.tracing = False
        dispatch, collect = eng.step_batch, eng.collect

        def step_batch(tasks, *a, **kw):
            pos0 = {t.slot: t.pos for t in tasks}
            st = dispatch(tasks, *a, **kw)
            if st is not None:
                rows = [dict(slot=t.slot, tokens=take, first=pos0[t.slot] == 0,
                             logits=int(fin))
                        for t, take, fin in zip(st.tasks, st.takes,
                                                st.finishing) if take > 0]
                rows += [dict(slot=s, tokens=1, first=False, logits=1)
                         for s in st.decode_rows]
                self._pending[id(st)] = dict(
                    rows=rows, prefill=bool(st.tasks), traced=self.tracing)
            return st

        def collect_(st):
            out = collect(st)
            rec = self._pending.pop(id(st))
            kv, adm = self.jax.device_get((st.stats["kv_tokens_rows"],
                                           st.stats["adm_sum_rows"]))
            for r in rec["rows"]:
                s = r["slot"]
                r["kv_before"] = 0.0 if r["first"] else float(self._kv[s])
                r["kv_after"] = float(kv[s])
                r["adm_sum"] = float(adm[s])
                self._kv[s] = kv[s]
            self.steps.append(rec)
            return out

        eng.step_batch, eng.collect = step_batch, collect_


def _pct(values: List[float], q: float) -> float:
    """Nearest-rank percentile."""
    v = sorted(values)
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def warm_up(eng, sched, slots: int, chunk: int, vocab: int) -> None:
    """Serve one short request per slot: compiles (or loads) the fused
    step's ``[slots, chunk]`` and ``[slots, 1]`` shapes and the host-side
    splice and free paths, and nothing the cell's traffic does not use."""
    from repro.serving.orchestrator import ServeSession
    sess = ServeSession(eng, sched=sched)
    rng = np.random.default_rng(0)
    hs = [sess.submit(rng.integers(0, vocab, chunk + 1).tolist(), max_new=4)
          for _ in range(slots)]
    sess.run()
    sess.close()
    assert all(h.done for h in hs), "warm-up requests did not finish"


def run_cell(bm: Dict, cell: Dict, seed: int, seconds: float, trace: bool,
             *, t_start: float, trace_dir: Optional[str] = None,
             cfg: Optional[Dict] = None, mix: Optional[Dict] = None,
             limits: Optional[Dict] = None, fault=None,
             control: bool = False) -> Dict:
    """One run of ``cell``; returns the result line's dict.

    ``cfg``/``mix``/``limits`` override the files the cell names (tests
    run a tiny configuration on the CPU); ``fault(eng)`` breaks the timed
    path underneath, for the tests that show ``correct`` turns false;
    ``control`` also reads the control's gap (``bench/control.py``)."""
    import jax
    from repro.serving.backend import make_backend
    from repro.serving.orchestrator import SchedulerConfig, ServeSession
    from repro.serving.orchestrator.queue import QueueFull

    cfg = cfg or spec.config(bm, cell["config"])
    mix = mix or spec.traffic(cell["traffic"])
    limits = limits or check.limits(cell["name"])
    fam = spec.family(cfg)
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform {dev.platform}, kind {dev.device_kind}, "
        f"count {len(devices)}")
    peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else {}
    compiles = CompileCounter()
    e = cfg["engine"]
    vocab = cfg["vocab_size"]

    params = fam.make_weights(cfg, seed)
    eng = make_backend(e["backend"], params, fam.program_config(cfg),
                       slots=e["slots"], capacity=e["capacity"],
                       mirror_paged=e["mirror_paged"], seed=seed)
    if fault is not None:
        fault(eng)
    sched = SchedulerConfig(chunk_tokens=e["chunk_tokens"],
                            dispatch_ahead=e["dispatch_ahead"])
    warm_up(eng, sched, e["slots"], e["chunk_tokens"], vocab)

    steplog = StepLog(eng) if trace else None
    tracer = None
    if trace:
        from repro.serving.obs.trace import Tracer
        tracer = Tracer(capacity=1 << 20, annotate_device=True)
    sess = ServeSession(eng, sched=sched, tracer=tracer)
    recs: Dict[int, Rec] = {}
    gen = generator.requests(mix, seed, vocab)
    finished: List[int] = []
    failed = 0

    def on_token(rid, tok, is_last):
        r = recs[rid]
        r.times.append(time.perf_counter())
        r.out.append(tok)
        if is_last:
            r.done = True
            finished.append(rid)

    def submit(req, due):
        nonlocal failed
        try:
            h = sess.submit(req.prompt, max_new=req.max_new,
                            on_token=on_token)
        except QueueFull:
            failed += 1
            return
        recs[h.rid] = Rec(req.prompt, req.max_new, due)

    closed = mix["loop"] == "closed"
    if closed:               # fill every client's slot before the window
        for _ in range(mix["clients"]):
            now = time.perf_counter()
            submit(next(gen), now)
        sess.tick()
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    t_end = t_open + seconds
    c0, tel0 = compiles.n, dict(sess.telemetry.counters)
    lateness: List[float] = []
    pending = None if closed else next(gen)
    traced = None
    tw = dict(TRACE_DEFAULT, **mix.get("trace", {}))
    t_trace0 = max(t_open, t_end - tw["seconds"])

    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if trace and traced is None and now >= t_trace0:
            traced = _start_trace(jax, eng, steplog, trace_dir)
        if closed:
            while finished:
                finished.pop()
                submit(next(gen), time.perf_counter())
        else:
            while pending is not None and t_open + pending.due_s <= now:
                due = t_open + pending.due_s
                submit(pending, due)
                lateness.append(time.perf_counter() - due)
                pending = next(gen)
        if not sess.tick() and pending is not None:
            # idle open loop: wait for the next arrival
            wait = min(t_open + pending.due_s, t_end)
            time.sleep(max(0.0, wait - time.perf_counter()))
    if traced is not None:
        _stop_trace(jax, eng, steplog, traced)
    window_compiles = compiles.n - c0
    tel1 = dict(sess.telemetry.counters)
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    sess.close()

    # ---- end-to-end metrics, from the client's timestamps --------------
    ttft, gaps, n_out, halves = [], [], 0, ([], [])
    t_mid = t_open + seconds / 2
    for r in recs.values():
        ts = [t for t in r.times if t <= t_end]
        if ts and ts[0] >= t_open:
            ttft.append(ts[0] - r.due)
            halves[r.due >= t_mid].append(ts[0] - r.due)
        n_out += sum(1 for t in ts if t >= t_open)
        gaps += [b - a for a, b in zip(ts, ts[1:]) if b >= t_open]
    attempted = len(recs) + failed
    done = [r for r in recs.values() if r.done and r.times[-1] <= t_end]
    backlog = sum(1 for r in recs.values()
                  if not [t for t in r.times if t <= t_end])
    window = {"sent": attempted, "finished": len(done),
              "first_tokens": len(ttft), "backlog_at_close": backlog,
              "ttft_p50_first_half_s": statistics.median(halves[0])
              if halves[0] else None,
              "ttft_p50_second_half_s": statistics.median(halves[1])
              if halves[1] else None,
              "compiles_in_window": window_compiles}
    if traced is not None:
        # the profiler's start and stop calls, in seconds from the open
        window["traced_s"] = [traced["t0"] - t_open, traced["t1"] - t_open]
        log(f"trace: from {window['traced_s'][0]:.2f} s into the window "
            f"to {window['traced_s'][1]:.2f} s; stopping the profiler "
            f"took {traced['stop_s']:.2f} s")
    log(f"window: {seconds:.0f} s, {attempted} requests sent, "
        f"{len(done)} finished, {len(ttft)} first tokens, {n_out} tokens, "
        f"{len(gaps)} inter-token gaps, {backlog} sent without a first "
        f"token at close; programs compiled inside the window: "
        f"{window_compiles}")
    if lateness:
        log(f"generator lateness: median {statistics.median(lateness):.4f} s,"
            f" max {max(lateness):.4f} s over {len(lateness)} arrivals")
    e2e = {}
    if ttft:
        e2e["ttft_p50_s"] = (statistics.median(ttft), "s")
    if gaps:
        e2e["itl_p95_ms"] = (1e3 * _pct(gaps, 95), "ms")
    e2e["out_tok_s"] = (n_out / seconds, "tokens/s")
    e2e["setup_s"] = (setup_s, "s")

    run = None
    if trace:
        red = None
        if traced:
            t_red = time.perf_counter()
            red = reduce_trace(traced, tracer, eng, fam, e["chunk_tokens"])
            shutil.rmtree(trace_dir, ignore_errors=True)
            log(f"trace: fused-step executions and steps dispatched by "
                f"shape {red and red['shape_counts']}, unpaired "
                f"{red and red['unpaired']}; read in "
                f"{time.perf_counter() - t_red:.1f} s")
        run = Run(cfg=cfg, family=fam, peaks=peaks,
                  ticks=tel1.get("ticks", 0) - tel0.get("ticks", 0),
                  phases={k: tel1.get(k, 0.0) - tel0.get(k, 0.0)
                          for k in tel1},
                  steps=steplog.steps, trace=red,
                  memory_peak_bytes=mem_peak)

    # ---- free the program's state, then the reference -------------------
    del sess, eng, params
    gc.collect()
    verdict = check.decide(fam, cfg, mix, seed, done, limits, vocab=vocab,
                           control=control)

    result = {"correct": verdict["correct"], "attempted": attempted,
              "failed": failed}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    if trace:
        metrics = {}
        for m in spec.per_layer(bm, cell["name"]):
            v = spec.reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        if run.trace:
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
            result["breakdown"] = run.trace["breakdown"]
    else:
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]][0], "unit": m["unit"]}
            for m in spec.end_to_end(bm, cell["name"]) if m["name"] in e2e}
    result["window"] = window
    result["device"] = device
    for name, c in verdict["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    if control:
        result["control"] = verdict["control"]
    result["checks"] = verdict["checks"]
    return result


def _start_trace(jax, eng, steplog, trace_dir: str) -> Dict:
    """Start the profiler with nothing in flight, so that every fused
    step it records is one dispatched after this point, in order."""
    from bench.tracing import WINDOW
    jax.block_until_ready(eng.caches)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    ann = jax.profiler.TraceAnnotation(WINDOW)
    t0_mono = time.monotonic()
    ann.__enter__()
    steplog.tracing = True
    return {"dir": trace_dir, "ann": ann, "t0_mono": t0_mono,
            "t0": time.perf_counter()}


def _stop_trace(jax, eng, steplog, traced: Dict) -> None:
    steplog.tracing = False
    jax.block_until_ready(eng.caches)
    traced["ann"].__exit__(None, None, None)
    traced["t1"] = time.perf_counter()
    jax.profiler.stop_trace()
    traced["stop_s"] = time.perf_counter() - traced["t1"]
    traced["steplog"] = steplog


def reduce_trace(traced: Dict, tracer, eng, fam, chunk: int
                 ) -> Optional[Dict]:
    """The traced window's reduction (``bench/tracing.py``), with the
    fused step's device time by named scope (``bench/scopes.py``) under
    ``scopes`` and its ten largest scopes in the breakdown."""
    from bench import scopes, tracing
    ev = tracing.load(traced["dir"])
    red = tracing.reduce_run(ev, traced, tracer, fam.FUSED_PROGRAM)
    if red is None:
        return None
    names: Dict[str, str] = {}
    for text in scopes.fused_hlo_texts(eng, chunk):
        names.update(scopes.op_names(text))
    sc = scopes.reduce(ev, fam.FUSED_PROGRAM, names, red["steps"],
                       scopes.tick_span_names(tracer))
    red["scopes"] = sc
    red["breakdown"]["device_scopes"] = sorted(
        ([k, v] for k, v in sc["scopes"].items()), key=lambda kv: -kv[1])[:10]
    return red
