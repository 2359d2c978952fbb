"""Structured serving tracer: per-request lifecycle spans and per-tick
engine-phase spans over a ring buffer.

The serving stack is five PRs of pipelining (chunked prefill, batched
ragged extend, dispatch-ahead decode) — an end-of-run aggregate cannot
say *where a tick's wall time goes* or *where one request's TTFT was
spent*. The tracer answers both with two families of spans:

  * **request lane** (one lane per rid): ``queued -> prefill[chunk i]
    -> insert -> decode -> finish/cancel/deadline`` — the lifecycle the
    orchestrator drives;
  * **engine lane** (one lane per tick loop): ``memory_sample``,
    ``admit``, ``fused_step`` (the one jitted megabatch dispatch: its
    ``fused_open``/``prefix_splice`` row splices, then ``fused_feed``,
    ``fused_call`` — with a ``selection`` sub-span when top-K page
    selection is active — and ``fused_bookkeep``), ``collect`` (its
    ``collect_wait`` for the device, the rest host work), ``evict``,
    and ``gc`` for each garbage collection (:meth:`Tracer.watch_gc`) —
    the per-tick phase decomposition the ROADMAP's fused megabatch /
    prefix-cache items need as evidence.

Design constraints:

  * **Always-on capable**: spans land in a bounded ring buffer
    (``collections.deque(maxlen=...)``) of plain tuples — no I/O, no
    serialization, no unbounded growth on a long-lived session. The
    oldest spans fall off; ``emitted`` counts everything ever recorded
    so exporters can report truncation.
  * **No-op-cheap when disabled**: the module-level :data:`NULL_TRACER`
    is the default everywhere. Its ``span()`` returns one shared
    pre-allocated context manager and ``add``/``instant`` return before
    touching the clock, so un-traced serving pays one attribute load +
    one branch per call site (asserted by the overhead test).
  * **Device bridge**: with ``annotate_device=True`` every ``span()``,
    every orchestrator tick phase and every ``gc`` span also enters a
    ``jax.profiler.TraceAnnotation`` of the same name, so the host's
    work shows in a profiler trace on the device trace's own clock.

Timestamps come from the injected ``clock`` (monotonic by default) —
deterministic-clock tests drive the tracer and the orchestrator from the
same fake clock. Export to Chrome-trace JSON lives in
:mod:`repro.serving.obs.export`.
"""
from __future__ import annotations

import collections
import gc
import time
from typing import Callable, Deque, Dict, List, NamedTuple, Optional, Tuple

# lane kinds: spans are grouped into horizontal lanes by (kind, id) —
# ("req", rid) per request, ("tick", 0) for the engine tick loop
LANE_REQ = "req"
LANE_TICK = "tick"

# span categories (Chrome-trace "cat"): request lifecycle vs engine phase
CAT_REQUEST = "request"
CAT_ENGINE = "engine"


class Span(NamedTuple):
    """One completed span (or instant event, when ``t1 == t0``)."""
    name: str
    cat: str
    lane: Tuple[str, int]          # (kind, id): ("req", rid) | ("tick", 0)
    t0: float
    t1: float
    args: Optional[Dict[str, object]]


class _NullSpan:
    """Shared no-op context manager returned by a disabled tracer."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _SpanCm:
    """Context manager recording one span on exit (enabled tracers)."""
    __slots__ = ("tracer", "name", "cat", "lane", "args", "t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 lane: Tuple[str, int], args: Optional[Dict]):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.lane = lane
        self.args = args
        self._ann = None

    def __enter__(self):
        if self.tracer.annotate_device:
            from jax.profiler import TraceAnnotation
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        self.t0 = self.tracer.clock()
        return self

    def __exit__(self, *exc):
        t1 = self.tracer.clock()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.tracer.add(self.name, self.t0, t1, cat=self.cat,
                        lane=self.lane, args=self.args)
        return False


class Tracer:
    """Ring-buffer span recorder.

    ``capacity`` bounds resident spans (oldest dropped); ``enabled=False``
    short-circuits every entry point (see :data:`NULL_TRACER`);
    ``annotate_device`` (enabled tracers only) additionally wraps
    ``span()`` bodies, tick phases and ``gc`` spans in
    ``jax.profiler.TraceAnnotation`` so a device profile shows the same
    phase names as the host trace."""

    def __init__(self, capacity: int = 1 << 16, *,
                 clock: Callable[[], float] = time.monotonic,
                 enabled: bool = True, annotate_device: bool = False):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.clock = clock
        self.enabled = enabled
        self.annotate_device = enabled and annotate_device
        self.spans: Deque[Span] = collections.deque(maxlen=capacity)
        self.emitted = 0        # total ever recorded (>= len(spans))
        self._gc_cb = None      # the registered gc callback, if any
        self._gc_t0 = 0.0
        self._gc_ann = None

    # ---- recording -------------------------------------------------------
    def span(self, name: str, *, cat: str = CAT_ENGINE,
             lane: Tuple[str, int] = (LANE_TICK, 0), **args):
        """Context manager timing one phase; ``**args`` become span
        attributes (tick/rid/slot/batch width...)."""
        if not self.enabled:
            return _NULL_SPAN
        return _SpanCm(self, name, cat, lane, args or None)

    def add(self, name: str, t0: float, t1: float, *,
            cat: str = CAT_ENGINE, lane: Tuple[str, int] = (LANE_TICK, 0),
            args: Optional[Dict] = None) -> None:
        """Record an already-timed span (the orchestrator times phases
        with its own injected clock and reports [t0, t1] here)."""
        if not self.enabled:
            return
        self.spans.append(Span(name, cat, lane, t0, t1, args))
        self.emitted += 1

    def instant(self, name: str, *, cat: str = CAT_ENGINE,
                lane: Tuple[str, int] = (LANE_TICK, 0), **args) -> None:
        """Record a zero-duration marker (finish / cancel / deadline)."""
        if not self.enabled:
            return
        t = self.clock()
        self.spans.append(Span(name, cat, lane, t, t, args or None))
        self.emitted += 1

    # ---- garbage collections ------------------------------------------
    def watch_gc(self) -> None:
        """Record each garbage collection as a ``gc`` span on the tick
        lane (args: the generation collected) until :meth:`unwatch_gc`.
        A disabled tracer registers nothing."""
        if self.enabled and self._gc_cb is None:
            self._gc_cb = self._on_gc
            gc.callbacks.append(self._gc_cb)

    def unwatch_gc(self) -> None:
        """Stop recording garbage collections."""
        if self._gc_cb is not None:
            gc.callbacks.remove(self._gc_cb)
            self._gc_cb = None

    def _on_gc(self, phase: str, info: Dict) -> None:
        if phase == "start":
            if self.annotate_device:
                from jax.profiler import TraceAnnotation
                self._gc_ann = TraceAnnotation("gc")
                self._gc_ann.__enter__()
            self._gc_t0 = self.clock()
            return
        t1 = self.clock()
        if self._gc_ann is not None:
            self._gc_ann.__exit__(None, None, None)
            self._gc_ann = None
        self.add("gc", self._gc_t0, t1,
                 args={"generation": info["generation"]})

    # ---- views -----------------------------------------------------------
    def drain(self) -> List[Span]:
        """Snapshot and clear the ring (exporters call this)."""
        out = list(self.spans)
        self.spans.clear()
        return out

    @property
    def dropped(self) -> int:
        return self.emitted - len(self.spans)

    def clear(self) -> None:
        self.spans.clear()
        self.emitted = 0


# the default tracer everywhere: disabled, shared, allocation-free on the
# hot path — serving code calls through it unconditionally
NULL_TRACER = Tracer(capacity=1, enabled=False)
