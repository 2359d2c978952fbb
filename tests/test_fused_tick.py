"""Fused megabatch tick (Engine.step_batch / scheduler fused driver):
ONE jitted ragged device call per tick advances every live row of the
persistent batched cache tree — first-chunk opens (empty-template
splices, no batch-1 open path), mid-prefill extends, and piggybacked
length-1 decode rows with in-jit sampling — while dead rows stay
bit-identical padding.

Parity standard (the repo's cross-driver standard, as in
test_batched_prefill): greedy token streams byte-identical to a
sequential engine-level reference drive (task-local ``_extend_ragged``
chunks + decode-only ``step_batch([])`` dispatches) and across
dispatch depths, admission accounting approx-equal, padding rows
BITWISE untouched. tau=0.1 per the knife-edge note (random-init gate
scores cluster at 0.5)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import expected_scopes, make_cfg, scopes_in
from repro.analysis import CompileSentinel, SyncSentinel, SyncViolation
from repro.launch.specs import alloc_batched_caches, extract_slot_caches
from repro.models import transformer as T
from repro.serving.backend import FusedStep, make_backend
from repro.serving.obs import LANE_TICK, Tracer
from repro.serving.orchestrator import Orchestrator, SchedulerConfig

pytestmark = pytest.mark.backends

CHUNK = 16
BACKEND_NAMES = ("wgkv", "dense", "streaming_llm")


@pytest.fixture(scope="module")
def served():
    cfg = make_cfg("qwen3-0.6b", global_budget_frac=0.5)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(served, name="wgkv"):
    cfg, params = served
    return make_backend(name, params, cfg, slots=4, capacity=128,
                        mirror_paged=False)


def _bitwise_equal(a, b):
    for (pa, x), (_, y) in zip(
            jax.tree_util.tree_flatten_with_path(a)[0],
            jax.tree_util.tree_flatten_with_path(b)[0]):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=jax.tree_util.keystr(pa))


# ==========================================================================
# engine level: one fused call mixing every row role matches the unfused
# split-path ops token for token
# ==========================================================================
def test_fused_mixed_roles_single_call(served):
    """A single ``step_batch`` call carrying a FIRST-CHUNK row (opened as
    an empty-template splice, scanned from position 0), a MID-EXTEND row,
    a length-0 dead padding row, and decode rows — every emitted token
    identical to a sequential reference drive of the same prompts
    (task-local ``_extend_ragged`` chunks, ``finish_prefill`` /
    ``insert``, decode-only ``step_batch([])`` dispatches)."""
    rng = np.random.default_rng(3)
    pa = list(rng.integers(0, 200, 20))   # slot 1: first chunk in step 3
    pb = list(rng.integers(0, 200, 30))   # slot 0: mid-extend in step 3
    pc = list(rng.integers(0, 200, 12))   # slot 2: live decode row
    eng = _engine(served)

    # step 1: open+finish C in one fused call -> slot 2 goes live
    c = eng.start_prefill(pc)
    c.slot = 2
    s1 = eng.step_batch([c], CHUNK)
    out1 = eng.collect(s1)
    assert c.done and s1.finishing == (True,) and s1.decode_rows == ()
    assert set(out1) == {2}

    # step 2: open B's first chunk; C piggybacks as a decode row
    b = eng.start_prefill(pb)
    b.slot = 0
    s2 = eng.step_batch([b], CHUNK)
    assert s2.decode_rows == (2,) and s2.takes == (CHUNK,)
    out2 = eng.collect(s2)
    assert set(out2) == {2}

    # step 3 — THE mixed call: A first-chunk (slot 1), B extend (slot 0,
    # finishes), slot 3 dead padding, slot 2 decode; slot 3 bitwise
    # untouched by the masked scan
    row3_before = jax.device_get(extract_slot_caches(eng.caches, 3))
    a = eng.start_prefill(pa)
    a.slot = 1
    s3 = eng.step_batch([a, b], CHUNK)
    assert s3.decode_rows == (2,)
    assert s3.takes == (CHUNK, len(pb) - CHUNK)
    assert s3.finishing == (False, True)
    out3 = eng.collect(s3)
    assert set(out3) == {0, 2}          # B's first token + C's decode
    _bitwise_equal(extract_slot_caches(eng.caches, 3), row3_before)

    # step 4: A finishes; B and C decode alongside
    s4 = eng.step_batch([a], CHUNK)
    assert s4.decode_rows == (0, 2) and s4.finishing == (True,)
    out4 = eng.collect(s4)
    assert set(out4) == {0, 1, 2}       # A's first token + two decodes

    # ---- sequential reference drive of the same prompts: task-local
    # ragged chunks + decode-only fused dispatches ----
    ref = _engine(served)

    def chunks(task, n=1):
        task.caches = ref._fresh_task_caches()
        for _ in range(n):
            ref._extend_ragged([task], CHUNK)
        return ref.finish_prefill(task)

    tc = ref.start_prefill(pc)
    fc = chunks(tc)
    ref.insert(fc, 2)
    assert fc.first_token == out1[2]
    assert tc.adm_weighted == pytest.approx(c.adm_weighted, rel=1e-5)
    # C's decode tokens across fused steps 2-4
    dec1 = ref.collect(ref.step_batch([]))
    assert dec1[2] == out2[2]
    tb = ref.start_prefill(pb)
    fb = chunks(tb, 2)
    assert fb.first_token == out3[0]
    assert tb.adm_weighted == pytest.approx(b.adm_weighted, rel=1e-5)
    dec2 = ref.collect(ref.step_batch([]))
    assert dec2[2] == out3[2]
    ref.insert(fb, 0)
    dec3 = ref.collect(ref.step_batch([]))
    assert dec3[0] == out4[0] and dec3[2] == out4[2]
    ta = ref.start_prefill(pa)
    fa = chunks(ta, 2)
    assert fa.first_token == out4[1]
    assert ta.adm_weighted == pytest.approx(a.adm_weighted, rel=1e-5)


def test_fused_freed_row_reopens_clean(served):
    """free_slot drops residency: the next task on that slot gets a
    fresh empty-template splice, and its stream matches a never-used
    slot's (no state leaks across requests)."""
    rng = np.random.default_rng(9)
    prompt = list(rng.integers(0, 200, 20))
    eng = _engine(served)
    filler = eng.start_prefill(list(rng.integers(0, 200, 28)))
    filler.slot = 1
    while not filler.done:
        eng.collect(eng.step_batch([filler], CHUNK, decode=False))
    first = eng.start_prefill(prompt)
    first.slot = 1

    def drive(task):
        toks = []
        while not task.done:
            out = eng.collect(eng.step_batch([task], CHUNK, decode=False))
            toks += sorted(out.items())
        for _ in range(3):
            toks += sorted(eng.collect(eng.step_batch([])).items())
        eng.free_slot(task.slot)
        return toks

    # dirty slot 1 (filler ran there), then reuse it for the same prompt
    eng.free_slot(1)
    assert not eng._resident[1]
    t1 = drive(first)
    again = eng.start_prefill(prompt)
    again.slot = 1
    t2 = drive(again)
    assert t1 == t2


# ==========================================================================
# orchestrator level: the always-fused driver streams byte-identical
# across dispatch depths, all backend families
# ==========================================================================
@pytest.mark.parametrize("name", BACKEND_NAMES)
def test_stream_parity_async_vs_sync(served, name):
    prompts = [list(range(10, 58)), list(range(5, 60)),
               list(range(20, 30)), list(range(7, 52))]

    def serve(depth):
        orch = Orchestrator(_engine(served, name), sched=SchedulerConfig(
            chunk_tokens=CHUNK, dispatch_ahead=depth))
        for p in prompts:
            orch.submit(p, max_new=5)
        orch.run()
        return ([orch.tokens(r) for r in range(len(prompts))],
                orch.telemetry.summary())

    toks_a, s_a = serve(1)
    toks_s, s_s = serve(0)
    assert toks_a == toks_s
    assert all(len(t) == 5 for t in toks_a)
    ca, cs = s_a["counters"], s_s["counters"]
    assert ca["fused_steps"] > 0 and cs["fused_steps"] > 0
    # chunk/token accounting keeps its meaning across dispatch depths
    assert ca["prefill_chunks"] == cs["prefill_chunks"]
    assert ca["prefill_tokens"] == cs["prefill_tokens"]
    # every prefill token rides the fused tick; the split stage is gone
    assert ca["fused_prefill_tokens"] == ca["prefill_tokens"]
    assert ca["prefill_time_s"] == 0.0
    assert s_a["mean_admission"] == pytest.approx(s_s["mean_admission"],
                                                  rel=1e-5)


# ==========================================================================
# phase accounting + tracing under the fused driver
# ==========================================================================
def test_fused_phase_accounting_and_trace(served):
    tracer = Tracer()
    orch = Orchestrator(_engine(served), sched=SchedulerConfig(
        chunk_tokens=CHUNK, dispatch_ahead=1), tracer=tracer)
    for p in ([list(range(10, 58)), list(range(5, 41))]):
        orch.submit(p, max_new=4)
    orch.run()
    ph = orch.telemetry.phase_times()
    assert ph["tick_time_s"] > 0.0
    assert ph["phase_sum_s"] <= ph["tick_time_s"] + 1e-12
    # the fused call's wall is apportioned, never invented: the prefill
    # share is bounded by the fused total, and the old batch-1 open
    # stage is gone entirely (open_time_s retired with it)
    assert ph["fused_time_s"] > 0.0
    assert 0.0 < ph["fused_prefill_time_s"] <= ph["fused_time_s"]
    assert ph["prefill_time_s"] == 0.0
    assert "open_time_s" not in ph
    # dispatch_time_s carries the fused dispatch spans
    assert ph["dispatch_time_s"] > 0.0
    tick_names = {s.name for s in tracer.spans if s.lane == (LANE_TICK, 0)}
    assert "fused_step" in tick_names
    # with selection off, no decode-only dispatch runs the sel variant
    assert "selection" not in {s.name for s in tracer.spans}
    assert any(s.name == "fused_open" for s in tracer.spans)
    # request-lane lifecycle survives the fused path (chunk spans carry
    # fused=True, insert instants mark the prefill->decode flip)
    assert any(s.name.startswith("prefill[chunk") and s.args.get("fused")
               for s in tracer.spans)
    assert any(s.name == "insert" and s.args.get("fused")
               for s in tracer.spans)


def test_fused_sub_spans_nest_in_their_tick_phases(served):
    """The engine splits the fused_step phase into feed, call and
    bookkeeping spans and collect into its device wait; the wait is also
    an always-on engine stat, mirrored into telemetry, that never exceeds
    the collect phase it sits in."""
    tracer = Tracer()
    orch = Orchestrator(_engine(served), sched=SchedulerConfig(
        chunk_tokens=CHUNK, dispatch_ahead=1), tracer=tracer)
    for p in ([list(range(10, 58)), list(range(5, 41))]):
        orch.submit(p, max_new=4)
    orch.run()
    tick = [s for s in tracer.spans if s.lane == (LANE_TICK, 0)]
    parents = {"fused_feed": "fused_step", "fused_call": "fused_step",
               "fused_bookkeep": "fused_step", "collect_wait": "collect"}
    for child, parent in parents.items():
        kids = [s for s in tick if s.name == child]
        assert kids, child
        outer = [s for s in tick if s.name == parent]
        assert all(any(p.t0 <= k.t0 and k.t1 <= p.t1 for p in outer)
                   for k in kids), child
    ph = orch.telemetry.phase_times()
    assert 0.0 < ph["collect_wait_time_s"] <= ph["collect_time_s"]
    assert orch.telemetry.counters["collect_wait_time_s"] == \
        ph["collect_wait_time_s"]


@pytest.mark.parametrize("name", ["wgkv", "dense"])
@pytest.mark.parametrize("s", [CHUNK, 1], ids=["chunk", "decode"])
def test_fused_step_names_every_scope(served, name, s):
    """Each piece of the compiled fused step carries its named scope in
    the op_name metadata, in both compiled shapes, so a device trace can
    attribute its time; a refactor that drops one fails here."""
    eng = _engine(served, name)
    caches = jax.eval_shape(
        lambda: alloc_batched_caches(eng._build_empty_caches(), eng.slots))
    b = eng.slots
    feed = (jax.ShapeDtypeStruct((b, s), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.bool_),
            jax.ShapeDtypeStruct((1, 2), jnp.uint32))
    text = eng._fused.lower(eng.params, feed, caches).compile().as_text()
    want = expected_scopes(name)
    assert want <= scopes_in(text), want - scopes_in(text)


def test_fused_step_is_single_device_call_kind(served):
    """Exactly two compiled fused shapes per engine — (slots, chunk) and
    (slots, 1) — however rows mix roles across a whole serve; the whole
    replay runs under both runtime sentinels, so the PR 7 shape-count
    claim AND the PR 4/8 sync discipline (no host pull between dispatch
    and collect outside sanctioned engine methods) are executable."""
    eng = _engine(served)
    orch = Orchestrator(eng, sched=SchedulerConfig(
        chunk_tokens=CHUNK, dispatch_ahead=1))
    with CompileSentinel(eng) as cs, SyncSentinel(eng) as ss:
        for n in (48, 55, 10, 33):
            orch.submit(list(range(2, 2 + n)), max_new=4)
        orch.run()
        counts = cs.check()             # raises if over the declared budget
    assert counts["fused_step"] == 2    # (slots, chunk) + (slots, 1)
    assert counts.get("fused_step_sel", 0) == 0   # selection off
    assert counts["extend_batch"] == 0  # legacy sync path never compiled
    assert ss.syncs_in_collect > 0      # collect() did the pulling
    fused = eng._fused
    sizes = getattr(fused, "_cache_size", None)
    if sizes is not None:               # plain jax.jit exposes the count
        assert fused._cache_size() <= 2
    assert isinstance(orch.telemetry.counters["fused_steps"], float)
    assert orch.telemetry.counters["fused_steps"] > 0


def test_sync_sentinel_trips_on_naked_sync(served):
    """The sentinel is not a no-op: a host pull between dispatch and
    collect raises SyncViolation, and a sync inside step_batch itself
    (dispatch must never block) raises too."""
    eng = _engine(served)
    t = eng.start_prefill(list(range(2, 30)))
    t.slot = 0
    with pytest.raises(SyncViolation):
        with SyncSentinel(eng):
            step = eng.step_batch([t], CHUNK)
            jax.device_get(step.tokens)          # naked pre-collect pull
    # device_get must be restored even after the raise
    assert jax.device_get.__module__ != "repro.analysis.sentinels"
    eng.collect(step)                            # settle for hygiene


def test_compile_sentinel_over_selection_replay(served):
    """Full fused serve with decode-time selection on: the third declared
    shape ((slots, 1) selection variant) lands and the budget holds."""
    cfg, params = served
    eng = make_backend("wgkv", params, cfg, slots=4, capacity=128,
                       mirror_paged=False, selection="quest:2")
    orch = Orchestrator(eng, sched=SchedulerConfig(
        chunk_tokens=CHUNK, dispatch_ahead=1))
    with CompileSentinel(eng) as cs:
        for n in (48, 10):
            orch.submit(list(range(2, 2 + n)), max_new=6)
        orch.run()
        counts = cs.check()
    assert counts["fused_step_sel"] == 1
    assert counts["fused_step"] <= 2


# ==========================================================================
# in-place cache update: the fused step donates its cache tree unless
# something reads the input tree after the call (the paged mirror)
# ==========================================================================
def _leaves_deleted(tree):
    return [leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(tree)]


def test_mirror_off_fused_dispatch_donates(served):
    """Without the mirror the fused call updates the tree in place: the
    tree a dispatch was given is deleted once it returns, and every
    fused step counts as donated. A mirror-on engine (its collect diffs
    the input tree) never donates."""
    cfg, params = served
    for mirror in (False, True):
        eng = make_backend("wgkv", params, cfg, slots=4, capacity=128,
                           mirror_paged=mirror)
        t = eng.start_prefill(list(range(2, 40)))
        t.slot = 1
        s1 = eng.step_batch([t], CHUNK)
        given = s1.after
        s2 = eng.step_batch([t], CHUNK)
        assert all(_leaves_deleted(given)) is not mirror
        assert not any(_leaves_deleted(eng.caches))
        eng.collect(s1)
        eng.collect(s2)
        assert eng.stats["fused_steps"] == 2
        assert eng.stats["fused_donated_steps"] == (0 if mirror else 2)
        assert (s1.donated, s2.donated) == (not mirror, not mirror)


@pytest.mark.parametrize("mode", ["fused", "ahead0", "ahead1"])
def test_donating_streams_equal_mirror_on(served, mode):
    """A donating (mirror-off) engine streams byte for byte what a
    non-donating (mirror-on) engine streams at the same seed: driven by
    hand with two fused steps in flight, and through the scheduler at
    dispatch depths 0 and 1."""
    cfg, params = served
    prompts = [list(range(10, 58)), list(range(5, 60)),
               list(range(20, 30)), list(range(7, 52))]

    def by_hand(eng):
        tasks = []
        for slot, p in enumerate(prompts):
            t = eng.start_prefill(p)
            t.slot = slot
            tasks.append(t)
        out = {s: [] for s in range(len(prompts))}
        inflight = []
        while any(len(v) < 5 for v in out.values()):
            step = eng.step_batch([t for t in tasks if not t.done], CHUNK)
            inflight.append(step)
            if len(inflight) == 2:
                for s, tok in eng.collect(inflight.pop(0)).items():
                    out[s].append(tok)
        for s, tok in eng.collect(inflight.pop(0)).items():
            out[s].append(tok)
        return [v[:5] for v in out.values()], eng.stats

    def scheduled(eng, depth):
        orch = Orchestrator(eng, sched=SchedulerConfig(
            chunk_tokens=CHUNK, dispatch_ahead=depth))
        for p in prompts:
            orch.submit(p, max_new=5)
        orch.run()
        return ([orch.tokens(r) for r in range(len(prompts))],
                orch.telemetry.counters)

    runs = {}
    for mirror in (False, True):
        eng = make_backend("wgkv", params, cfg, slots=4, capacity=128,
                           mirror_paged=mirror, seed=7)
        runs[mirror] = by_hand(eng) if mode == "fused" else \
            scheduled(eng, int(mode[-1]))
    (toks, c_off), (ref, c_on) = runs[False], runs[True]
    assert toks == ref
    assert all(len(t) == 5 for t in toks)
    assert c_off["fused_steps"] > 0
    assert c_off["fused_donated_steps"] == c_off["fused_steps"]
    assert c_on["fused_donated_steps"] == 0


def test_fused_step_temp_below_one_cache_tree(served):
    """The compiled ``[slots, chunk]`` fused step needs less scratch
    memory than one cache tree: each position writes only the slots it
    touches, in place, and no copy of the tree is made per position."""
    from repro.launch.specs import cache_tree_bytes
    cfg, params = served
    eng = make_backend("wgkv", params, cfg, slots=4, capacity=2048,
                       mirror_paged=False)
    caches = jax.eval_shape(
        lambda: alloc_batched_caches(eng._build_empty_caches(), eng.slots))
    b = eng.slots
    feed = (jax.ShapeDtypeStruct((b, CHUNK), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.bool_),
            jax.ShapeDtypeStruct((1, 2), jnp.uint32))
    mem = eng._fused.lower(eng.params, feed, caches).compile() \
        .memory_analysis()
    tree = cache_tree_bytes(caches)
    assert 0 < mem.temp_size_in_bytes < tree, (mem.temp_size_in_bytes, tree)
