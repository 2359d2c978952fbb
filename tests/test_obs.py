"""Serving observability (repro.serving.obs): tracer ring buffer +
disabled-path overhead, metrics registry windows, Telemetry edge cases,
deterministic-clock phase accounting, and Chrome-trace export validity.

Everything here runs host-only against a fake EngineBackend — no model,
no device work — so the tick-loop instrumentation and export contracts
are pinned cheaply; tests/test_serving.py covers the real engines end to
end (including ``--trace-out`` through bench_serving in CI).
"""
import gc
import json
import time
from typing import Dict

import jax
import pytest

from repro.serving.backend import (BackendCapabilities, FusedStep,
                                   PrefillTask)
from repro.serving.obs import (CAT_ENGINE, CAT_REQUEST, LANE_REQ, LANE_TICK,
                               NULL_TRACER, MetricsRegistry, Tracer,
                               chrome_trace, chrome_trace_events,
                               validate_chrome_trace, write_chrome_trace)
from repro.serving.obs.export import main as validate_cli
from repro.serving.orchestrator.scheduler import Orchestrator, SchedulerConfig
from repro.serving.orchestrator.session import ServeSession
from repro.serving.orchestrator.telemetry import (PHASE_TIME_KEYS,
                                                  TELEMETRY_SCHEMA_VERSION,
                                                  Telemetry)


class FakeClock:
    """Deterministic strictly-increasing clock (1 ms per read)."""

    def __init__(self, step: float = 1e-3):
        self.t = 0.0
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t


class FakeEngine:
    """Host-only EngineBackend: the fused megabatch tick as pure
    bookkeeping (one ``step_batch`` per tick advancing prompt chunks
    teacher-forced and marking decode rows; ``collect`` delivers one
    token per finishing/decoding row)."""
    eos = None

    def __init__(self, slots: int = 2):
        self.slots = slots
        self.live = [False] * slots
        self.stats = {"steps": 0, "evict_triggers": 0.0,
                      "decode_adm_sum": 0.0, "extend_time_s": 0.0,
                      "extend_tokens": 0.0, "fused_steps": 0.0,
                      "fused_time_s": 0.0, "fused_prefill_time_s": 0.0,
                      "fused_prefill_tokens": 0.0, "fused_slot_rows": 0.0,
                      "fused_active_rows": 0.0, "selected_pages": 0.0,
                      "selection_time_s": 0.0}
        self.tracer = NULL_TRACER
        self._n = 0

    def capabilities(self):
        return BackendCapabilities(name="fake", gated=False, paged=False)

    def memory_snapshot(self) -> Dict[str, float]:
        return {"kv_tokens": float(sum(self.live) * 10), "kv_bytes": 64.0}

    def start_prefill(self, prompt):
        return PrefillTask(prompt=list(prompt))

    def step_batch(self, tasks, chunk=None, decode=True):
        decode_rows = tuple(s for s in range(self.slots)
                            if decode and self.live[s])
        takes, fins = [], []
        for t in tasks:
            take = (len(t.prompt) - t.pos if chunk is None
                    else min(len(t.prompt) - t.pos, chunk))
            t.pos += take
            t.adm_weighted += 0.5 * take
            takes.append(take)
            fins.append(t.done)
            if t.done:          # row resident + live; first token at collect
                self.live[t.slot] = True
            self.stats["fused_prefill_tokens"] += take
            self.stats["fused_prefill_time_s"] += 1e-5
        if not tasks and not decode_rows:
            return None
        self.stats["fused_steps"] += 1
        self.stats["fused_time_s"] += 1e-4
        self.stats["fused_slot_rows"] += float(self.slots)
        self.stats["fused_active_rows"] += float(len(tasks)
                                                 + len(decode_rows))
        return FusedStep(tokens=None, stats=None, before=None, after=None,
                         live=tuple(self.live), gen=(0,) * self.slots,
                         tasks=tuple(tasks), takes=tuple(takes),
                         fulls=tuple(tk == chunk for tk in takes),
                         finishing=tuple(fins), decode_rows=decode_rows,
                         had_prefill=bool(tasks))

    def collect(self, step):
        self.stats["steps"] += 1
        self.stats["decode_adm_sum"] += 0.5
        self._n += 1
        out = {}
        for t, fin in zip(step.tasks, step.finishing):
            if fin and self.live[t.slot]:
                out[t.slot] = 100 + self._n
        for s in step.decode_rows:
            if step.live[s] and self.live[s]:
                out[s] = 100 + self._n
        return out

    def free_slot(self, slot):
        self.live[slot] = False


def _serve(n_req=3, prompt_len=10, max_new=5, **orch_kw):
    clk = FakeClock()
    orch_kw.setdefault("clock", clk)
    eng = FakeEngine()
    orch = Orchestrator(eng, sched=SchedulerConfig(chunk_tokens=4,
                                                   dispatch_ahead=1),
                        **orch_kw)
    rids = [orch.submit(list(range(prompt_len)), max_new=max_new)
            for _ in range(n_req)]
    orch.run()
    return orch, rids


# ==========================================================================
# tracer: ring buffer, disabled no-op path, span recording
# ==========================================================================
def test_tracer_ring_buffer_bounds_memory():
    tr = Tracer(capacity=8, clock=FakeClock())
    for i in range(20):
        tr.add(f"s{i}", float(i), float(i) + 0.5)
    assert len(tr.spans) == 8
    assert tr.emitted == 20
    assert tr.dropped == 12
    # the ring keeps the NEWEST spans (oldest fall off)
    assert [s.name for s in tr.spans] == [f"s{i}" for i in range(12, 20)]
    got = tr.drain()
    assert len(got) == 8 and not tr.spans


def test_tracer_span_context_manager_records():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("phase", cat=CAT_ENGINE, lane=(LANE_TICK, 0), tick=3):
        pass
    with tr.span("life", cat=CAT_REQUEST, lane=(LANE_REQ, 7)):
        pass
    tr.instant("finish", cat=CAT_REQUEST, lane=(LANE_REQ, 7), rid=7)
    assert len(tr.spans) == 3
    s0, s1, s2 = tr.spans
    assert s0.name == "phase" and s0.args == {"tick": 3} and s0.t1 > s0.t0
    assert s1.lane == (LANE_REQ, 7)
    assert s2.t0 == s2.t1            # instant
    assert tr.span("x").__class__.__name__ == "_SpanCm"


def test_null_tracer_is_noop_and_shared():
    calls = []
    tr = Tracer(capacity=4, clock=lambda: calls.append(1) or 0.0,
                enabled=False)
    cm1 = tr.span("a", tick=1)
    cm2 = tr.span("b")
    assert cm1 is cm2                # one shared pre-allocated no-op cm
    with cm1:
        pass
    tr.add("c", 0.0, 1.0)
    tr.instant("d")
    assert not calls                 # disabled path never touches the clock
    assert len(tr.spans) == 0 and tr.emitted == 0
    assert NULL_TRACER.enabled is False


def test_disabled_tracer_overhead_is_noop_cheap():
    """The acceptance bar: with tracing off, instrumented call sites cost
    a branch — bounded here as < 3x the cost of a bare function call, so
    a regression that makes the disabled path allocate or read the clock
    fails loudly."""
    tr = Tracer(capacity=1, enabled=False)
    n = 50_000

    # the baseline pays the SAME argument-passing cost as the call site
    # (a no-arg `bare()` makes the 3x bound a knife edge on slow boxes:
    # kwargs packing alone costs ~3x a bare no-arg call)
    def bare(name, t0, t1, cat=None, lane=None):
        pass

    t0 = time.perf_counter()
    for _ in range(n):
        bare("x", 0.0, 1.0, cat=CAT_ENGINE, lane=(LANE_TICK, 0))
    t_bare = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        tr.add("x", 0.0, 1.0, cat=CAT_ENGINE, lane=(LANE_TICK, 0))
    t_add = time.perf_counter() - t0
    assert t_add < max(t_bare, 1e-4) * 3.0, (t_add, t_bare)


def test_gc_span_under_enabled_tracer_only():
    """An enabled tracer that watches the collector records each
    collection as one ``gc`` span on the tick lane, with its generation;
    the disabled NULL_TRACER registers no callback and records nothing."""
    tr = Tracer(clock=FakeClock())
    n_cb = len(gc.callbacks)
    enabled = gc.isenabled()
    gc.disable()                     # only the explicit collection below
    try:
        tr.watch_gc()
        gc.collect()
        tr.unwatch_gc()
        NULL_TRACER.watch_gc()
        assert len(gc.callbacks) == n_cb
        gc.collect()
    finally:
        if enabled:
            gc.enable()
    spans = [s for s in tr.spans if s.name == "gc"]
    assert len(spans) == 1
    assert spans[0].lane == (LANE_TICK, 0) and spans[0].t1 > spans[0].t0
    assert spans[0].args == {"generation": 2}
    assert not NULL_TRACER.spans


def test_session_watches_gc_while_it_holds_the_tracer():
    n_cb = len(gc.callbacks)
    sess = ServeSession(FakeEngine(), tracer=Tracer(clock=FakeClock()))
    assert len(gc.callbacks) == n_cb + 1
    sess.close()
    assert len(gc.callbacks) == n_cb
    ServeSession(FakeEngine()).close()       # NULL_TRACER: nothing added
    assert len(gc.callbacks) == n_cb


def test_tick_phases_annotate_the_device_trace_only_when_asked(monkeypatch):
    """Under ``annotate_device`` every tick phase enters a profiler
    annotation of its own name; the default tracer never does."""
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    _serve()
    assert entered == []
    assert not Tracer(enabled=False, annotate_device=True).annotate_device
    _serve(tracer=Tracer(clock=FakeClock(), annotate_device=True))
    assert {"memory_sample", "admit", "fused_step", "collect",
            "evict"} <= set(entered)


# ==========================================================================
# metrics registry: counters / gauges / rolling-window histograms
# ==========================================================================
def test_registry_counter_rate_and_windows():
    clk = FakeClock(step=0.0)        # manual time control
    reg = MetricsRegistry(clock=lambda: clk.t, window_s=10.0)
    c = reg.counter("tok")
    c.mark(0.0)
    c.inc(50)
    clk.t = 5.0
    assert c.rate(clk.t, 10.0) == pytest.approx(10.0)
    h = reg.histogram("lat")
    for i, t in enumerate([1.0, 2.0, 11.0, 12.0]):
        h.observe(float(i), now=t)
    # at t=13 the 10s window holds only the observations at t=11, 12
    st = h.window_stats(13.0)
    assert st["count"] == 2.0
    assert st["p50"] == pytest.approx(2.5)
    assert h.count == 4 and h.min == 0.0 and h.max == 3.0   # cumulative
    snap = reg.snapshot()
    assert snap["counters"]["tok"] == 50.0
    assert snap["histograms"]["lat"]["count"] == 4.0


def test_registry_get_or_create_is_stable():
    reg = MetricsRegistry(clock=lambda: 0.0)
    assert reg.counter("a") is reg.counter("a")
    assert reg.gauge("g") is reg.gauge("g")
    assert reg.histogram("h") is reg.histogram("h")
    reg.gauge("g").set(3)
    assert reg.gauge("g").value == 3.0


# ==========================================================================
# telemetry edge cases (satellite: empty records, unseen keys, schema)
# ==========================================================================
def test_telemetry_empty_summary_and_report():
    """summary()/report() on a telemetry with zero recorded requests must
    not divide by zero or KeyError — every latency field is None and the
    report renders placeholders."""
    t = Telemetry(clock=FakeClock())
    s = t.summary()
    assert s["requests"] == 0
    assert s["ttft_p99_s"] is None and s["tpot_p99_s"] is None
    assert s["tokens_per_s"] is None or s["tokens_per_s"] == 0.0
    r = t.report()
    assert "requests=0" in r and "p99=-" in r
    assert "tick phases:" in r


def test_telemetry_bump_unseen_key_creates_counter():
    t = Telemetry(clock=FakeClock())
    assert "brand_new" not in t.counters
    t.bump("brand_new")
    t.bump("brand_new", 2.5)
    assert t.counters["brand_new"] == 3.5
    # dict-contract of the CounterView facade
    assert t.counters.get("missing") is None
    with pytest.raises(KeyError):
        t.counters["missing"]
    d = dict(t.counters)
    assert d["brand_new"] == 3.5


def test_telemetry_schema_version_and_generated_at():
    t = Telemetry(clock=FakeClock())
    s = t.summary()
    assert s["schema_version"] == TELEMETRY_SCHEMA_VERSION
    # ISO-8601 with explicit UTC offset
    assert "T" in s["generated_at"] and "+00:00" in s["generated_at"]


def test_telemetry_tpot_p99_in_report():
    """Satellite bugfix: the TPOT line must render the same p99 tail the
    SLO gate checks."""
    clk = FakeClock()
    t = Telemetry(clock=clk)
    for rid in range(5):
        t.record_request(rid=rid, prompt_len=8, n_out=4, ttft=0.010,
                         tpot=0.002 * (rid + 1), e2e=0.05,
                         mean_admission=0.5)
    tpot_line = [ln for ln in t.report().splitlines()
                 if ln.startswith("TPOT")][0]
    assert "p99=" in tpot_line
    assert f"{t.summary()['tpot_p99_s'] * 1e3:.2f}ms" in tpot_line


# ==========================================================================
# deterministic-clock orchestrator accounting + request lifecycle spans
# ==========================================================================
def test_phase_times_sum_within_tick_wall():
    """Satellite: with orchestrator and tracer on one deterministic
    clock, the disjoint phase durations must sum to <= the accumulated
    tick wall time (no double-counted phase)."""
    clk = FakeClock()
    tr = Tracer(clock=clk)
    orch, _ = _serve(clock=clk, tracer=tr)
    ph = orch.telemetry.phase_times()
    assert ph["tick_time_s"] > 0.0
    assert ph["phase_sum_s"] <= ph["tick_time_s"] + 1e-12
    assert ph["phase_sum_s"] == pytest.approx(
        sum(ph[k] for k in PHASE_TIME_KEYS))
    # every disjoint phase that ran is represented (prefill_time_s stays
    # 0 — prompt chunks ride the fused dispatch, not a separate stage)
    for k in ("dispatch_time_s", "collect_time_s",
              "evict_time_s", "memory_sample_time_s", "admit_time_s"):
        assert ph[k] > 0.0, k
    assert ph["prefill_time_s"] == 0.0


def test_request_lifecycle_spans_complete():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    orch, rids = _serve(clock=clk, tracer=tr)
    by_rid = {rid: [s for s in tr.spans if s.lane == (LANE_REQ, rid)]
              for rid in rids}
    for rid, spans in by_rid.items():
        names = [s.name for s in spans]
        assert "queued" in names
        assert any(n.startswith("prefill[chunk ") for n in names)
        assert "insert" in names and "decode" in names
        assert "finish" in names
        # lifecycle ordering: queued ends before decode begins
        queued = next(s for s in spans if s.name == "queued")
        decode = next(s for s in spans if s.name == "decode")
        assert queued.t1 <= decode.t0
    # engine-lane phases landed too (the fused tick's span vocabulary)
    tick_names = {s.name for s in tr.spans if s.lane == (LANE_TICK, 0)}
    assert {"memory_sample", "admit", "fused_step",
            "collect", "evict"} <= tick_names


def test_cancel_emits_terminal_instant():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    eng = FakeEngine()
    orch = Orchestrator(eng, sched=SchedulerConfig(chunk_tokens=4,
                                                   dispatch_ahead=1),
                        clock=clk, tracer=tr)
    rid = orch.submit(list(range(10)), max_new=50)
    for _ in range(6):
        orch.tick()
    assert orch.cancel(rid)
    marks = [s for s in tr.spans
             if s.lane == (LANE_REQ, rid) and s.t0 == s.t1]
    assert any(s.name == "cancelled" for s in marks)


def test_live_metrics_line_cuts_on_interval():
    lines = []
    clk = FakeClock()
    _serve(n_req=4, max_new=8, clock=clk, metrics_interval_s=0.02,
           on_metrics=lines.append)
    assert lines, "no live metrics line was cut"
    assert all(ln.startswith("[metrics +") for ln in lines)
    assert "tok/s=" in lines[0] and "ttft_p50=" in lines[0]


# ==========================================================================
# Chrome-trace export + validator
# ==========================================================================
def test_chrome_trace_export_and_validate(tmp_path):
    clk = FakeClock()
    tr = Tracer(clock=clk)
    orch, _ = _serve(clock=clk, tracer=tr)
    path = tmp_path / "trace.json"
    obj = write_chrome_trace(tr, str(path), meta={"run": "test"})
    assert validate_chrome_trace(obj) == []
    on_disk = json.loads(path.read_text())
    assert validate_chrome_trace(on_disk) == []
    assert on_disk["otherData"]["run"] == "test"
    assert on_disk["otherData"]["schema_version"] == 1
    # both span families present, timestamps rebased to 0 and in us
    evs = [e for e in on_disk["traceEvents"] if e["ph"] in ("X", "i")]
    assert any(e["cat"] == "engine" for e in evs)
    assert any(e["cat"] == "request" for e in evs)
    assert min(e["ts"] for e in evs) == 0.0
    xs = [e for e in evs if e["ph"] == "X"]
    assert all(e["dur"] >= 0 for e in xs)
    # the CLI validator agrees
    assert validate_cli([str(path)]) == 0


def test_validator_rejects_hollow_traces(tmp_path):
    assert chrome_trace_events([]) == []
    empty = {"traceEvents": [], "otherData": {}}
    errs = validate_chrome_trace(empty)
    assert any("engine" in e for e in errs)
    assert any("request" in e for e in errs)
    assert any("schema_version" in e for e in errs)
    # engine-only trace (request instrumentation fell off) is invalid
    tr = Tracer(clock=FakeClock())
    tr.add("tick", 0.0, 1.0, cat=CAT_ENGINE, lane=(LANE_TICK, 0))
    assert any("request" in e
               for e in validate_chrome_trace(chrome_trace(tr)))
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert validate_cli([str(bad)]) == 1
    assert validate_cli([]) == 2


def test_trace_disabled_serving_matches_enabled():
    """Tracing must observe, never steer: token streams are identical
    with the tracer on and off."""
    ref, rids = _serve()
    traced, rids2 = _serve(tracer=Tracer(clock=FakeClock()))
    assert rids == rids2
    for rid in rids:
        assert ref.tokens(rid) == traced.tokens(rid)
    # and the default orchestrator runs on the shared NULL_TRACER
    assert ref.tracer is NULL_TRACER and not ref.tracer.spans
