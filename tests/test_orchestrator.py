"""Continuous-batching orchestrator: queue backpressure, chunked-prefill
equivalence, streaming parity with the legacy engine loop, paged-pool
reclamation, and telemetry."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_cfg
from repro.models import inference as I
from repro.models import transformer as T
from repro.serving.engine import Engine
from repro.serving.orchestrator import (InvalidRequest, Orchestrator,
                                        QueueFull, RequestQueue, Scheduler,
                                        SchedulerConfig)


@pytest.fixture(scope="module")
def served():
    cfg = make_cfg("qwen3-0.6b", global_budget_frac=0.5)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


# ==========================================================================
# queue: arrival ordering + backpressure
# ==========================================================================
def test_queue_fifo_and_backpressure():
    q = RequestQueue(max_pending=2)
    r0 = q.submit([1, 2], max_new=4)
    r1 = q.submit([3, 4], max_new=4)
    with pytest.raises(QueueFull):
        q.submit([5, 6], max_new=4)
    assert q.rejected == 1
    assert [q.pop().rid, q.pop().rid] == [r0, r1]  # arrival order
    assert q.pop() is None
    r2 = q.submit([7], max_new=1)  # drained -> accepts again
    assert q.pop().rid == r2


def test_queue_validates_requests():
    """Malformed requests fail with a typed error at the queue boundary,
    not deep inside the backend's start_prefill."""
    q = RequestQueue(max_pending=4)
    with pytest.raises(InvalidRequest, match="non-empty"):
        q.submit([], max_new=4)
    with pytest.raises(InvalidRequest, match="max_new"):
        q.submit([1, 2], max_new=0)
    with pytest.raises(InvalidRequest, match="deadline"):
        q.submit([1, 2], max_new=4, deadline_s=0.0)
    assert len(q) == 0 and q.rejected == 0  # validation is not shed load


def test_queue_full_is_typed():
    """QueueFull carries the queue state so a frontend can back off."""
    q = RequestQueue(max_pending=1)
    q.submit([1], max_new=1)
    with pytest.raises(QueueFull) as ei:
        q.submit([2], max_new=1)
    assert ei.value.depth == 1 and ei.value.max_pending == 1


def test_scheduler_plan_respects_limits():
    s = Scheduler(SchedulerConfig(chunk_tokens=16, max_prefill_batch=1,
                                  decode_while_prefill=False))
    p = s.plan(free_slots=2, queue_depth=5, active_prefills=0, live_decodes=1)
    assert p.admit == 2 and p.advance_prefills == 1  # capped at the knob
    assert not p.decode  # decode_while_prefill=False and prefills pending
    p = s.plan(free_slots=0, queue_depth=5, active_prefills=0, live_decodes=2)
    assert p.admit == 0 and p.decode
    # default: every in-flight prefill advances every tick (one batched
    # ragged device call), bounded only by the slot count
    s = Scheduler(SchedulerConfig(chunk_tokens=16))
    p = s.plan(free_slots=2, queue_depth=5, active_prefills=3, live_decodes=0)
    assert p.advance_prefills == 5
    with pytest.raises(ValueError, match="max_prefill_batch"):
        SchedulerConfig(max_prefill_batch=0)


# ==========================================================================
# chunked prefill == one-shot prefill
# ==========================================================================
def test_chunked_prefill_matches_one_shot(served):
    """The serving scan (both engine drivers now open from the empty
    template and scan token-by-token) stays equivalent to the offline
    one-shot ``I.prefill`` — same admitted globals and ring state, logits
    allclose (different attention path, so float bits may differ)."""
    cfg, params = served
    prompt = list(range(20, 68))  # 48 = 3 x w_local(16): window-multiple
    eng = Engine(params, cfg, slots=1, capacity=128, mirror_paged=False)
    chunked = eng.prefill(prompt, chunk_tokens=16)
    budget = cfg.wgkv.global_budget(128)
    po, one_caches = I.prefill(params, cfg,
                               jnp.asarray(prompt, jnp.int32)[None],
                               budget=budget, max_len=128, opts=eng.opts)
    one_logits = po.logits
    assert np.allclose(np.asarray(one_logits),
                       np.asarray(chunked.first_logits), atol=1e-4)
    assert int(np.asarray(one_logits).argmax()) == chunked.first_token
    # cache state matches too (same admitted globals, same ring)
    for attr in ("gcnt", "t", "ptr"):
        assert np.array_equal(np.asarray(getattr(
            one_caches["blocks"]["b0"], attr)),
            np.asarray(getattr(chunked.caches["blocks"]["b0"], attr)))
    assert np.allclose(np.asarray(one_caches["blocks"]["b0"].lk),
                       np.asarray(chunked.caches["blocks"]["b0"].lk),
                       atol=1e-4)


def test_chunked_prefill_ragged_tail(served):
    """Non-window-multiple prompts: chunk size is invariant — the
    unchunked scan and the chunk-16 scan produce identical greedy
    rollouts."""
    cfg, params = served
    prompt = list(range(5, 60))  # 55 tokens: ragged
    eng = Engine(params, cfg, slots=1, capacity=128, mirror_paged=False)
    one = eng.prefill(prompt, chunk_tokens=None)
    chunked = eng.prefill(prompt, chunk_tokens=16)
    assert one.first_token == chunked.first_token


def test_splice_extract_roundtrip(served):
    """insert's splice and its inverse agree on every cache-tree leaf
    (batch axes resolved per-path: blocks vs obs vs batch-leading)."""
    from repro.launch.specs import (alloc_batched_caches, extract_slot_caches,
                                    splice_caches)
    cfg, params = served
    eng = Engine(params, cfg, slots=3, capacity=128, mirror_paged=False,
                 opts=I.DecodeOptions(evict_hard_budget=48, w_obs=16))
    prefix = eng.prefill(list(range(20, 68)), emit_first=False)
    batch = alloc_batched_caches(prefix.caches, 3)
    batch = splice_caches(batch, prefix.caches, 1)
    back = extract_slot_caches(batch, 1)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), prefix.caches, back)
    # untouched rows stay zero
    other = extract_slot_caches(batch, 0)
    assert float(jnp.abs(other["blocks"]["b0"].lk).max()) == 0.0


# ==========================================================================
# orchestrator streaming parity with the legacy engine loop
# ==========================================================================
def test_stream_matches_engine_run(served):
    cfg, params = served
    prompts = [list(range(10 + i, 58 + i)) for i in range(3)]
    ref = Engine(params, cfg, slots=2, capacity=128, mirror_paged=False)
    for p in prompts:
        ref.add_request(p, max_new=5)
    ref.run(max_steps=40)
    want = [ref.requests[r].out for r in range(len(prompts))]

    eng = Engine(params, cfg, slots=2, capacity=128, mirror_paged=False)
    orch = Orchestrator(eng, sched=SchedulerConfig(chunk_tokens=16))
    streamed = {}
    for p in prompts:
        rid = orch.submit(p, max_new=5,
                          on_token=lambda r, t, last:
                          streamed.setdefault(r, []).append(t))
    orch.run()
    for rid in range(len(prompts)):
        assert orch.tokens(rid) == want[rid]
        assert streamed[rid] == want[rid]
        assert orch.queue.requests[rid].state == "done"


def test_orchestrator_with_composition(served):
    """Quest read-time selection + SnapKV eviction stay composable under
    the orchestrator's chunked prefill + batched decode."""
    cfg, params = served
    opts = I.DecodeOptions(quest_pages=2, evict_hard_budget=48, w_obs=16)
    eng = Engine(params, cfg, slots=2, capacity=128, opts=opts,
                 mirror_paged=False)
    orch = Orchestrator(eng, sched=SchedulerConfig(chunk_tokens=16))
    for i in range(3):
        orch.submit(list(range(i, 80 + i)), max_new=6)
    orch.run()
    assert all(r.state == "done" for r in orch.queue.requests.values())
    assert all(len(r.out) == 6 for r in orch.queue.requests.values())


# ==========================================================================
# paged-pool reclamation (regression: no page leak across request churn)
# ==========================================================================
def test_pool_reclaimed_after_completion(served):
    cfg, params = served
    eng = Engine(params, cfg, slots=2, capacity=128)
    orch = Orchestrator(eng, sched=SchedulerConfig(chunk_tokens=16))
    for i in range(4):  # more requests than slots -> slot churn
        orch.submit(list(range(10 + i, 58 + i)), max_new=4)
    saw_pages = 0
    for _ in range(200):
        if orch.queue.all_done():
            break
        orch.tick()
        saw_pages = max(saw_pages, eng.pool.pages_in_use)
        if any(eng.live):
            assert eng.verify_paged() < 2e-3
    assert orch.queue.all_done()
    assert saw_pages > 0                      # pool was actually exercised
    assert eng.pool.pages_in_use == 0         # every stream freed
    assert eng.pool.utilization() == 1.0      # back to baseline
    assert not eng.pool.tables                # no stale page tables


# ==========================================================================
# telemetry
# ==========================================================================
def test_telemetry_records(served):
    cfg, params = served
    eng = Engine(params, cfg, slots=2, capacity=128)
    orch = Orchestrator(eng, sched=SchedulerConfig(chunk_tokens=16),
                        max_pending=8)
    for i in range(3):
        orch.submit(list(range(i, 48 + i)), max_new=4)
    orch.run()
    s = orch.telemetry.summary()
    assert s["requests"] == 3
    assert s["requests_per_s"] > 0 and s["tokens_per_s"] > 0
    assert s["ttft_mean_s"] is not None and s["ttft_mean_s"] >= 0
    assert s["tpot_mean_s"] is not None and s["tpot_mean_s"] >= 0
    assert 0.0 <= s["mean_admission"] <= 1.0
    assert 0.0 <= s["mean_admission_decode"] <= 1.0
    assert s["counters"]["generated_tokens"] == 12
    assert s["counters"]["decode_steps"] > 0
    assert s["counters"]["prefill_chunks"] >= 3
    # batched advance: one device call covers many tasks' chunks, while
    # prefill_chunks keeps its one-per-task-per-tick meaning
    assert 0 < s["counters"]["prefill_batches"] <= s["counters"]["prefill_chunks"]
    assert s["prefill_chunks_per_request_mean"] >= 1.0
    assert s["pool_util_mean"] is not None
    rep = orch.telemetry.report()
    assert "TTFT" in rep and "admission" in rep


def test_backpressure_under_load(served):
    cfg, params = served
    eng = Engine(params, cfg, slots=1, capacity=128, mirror_paged=False)
    orch = Orchestrator(eng, sched=SchedulerConfig(chunk_tokens=16),
                        max_pending=2)
    orch.submit(list(range(48)), max_new=2)
    orch.submit(list(range(48)), max_new=2)
    with pytest.raises(QueueFull):
        orch.submit(list(range(48)), max_new=2)
    orch.run()
    assert orch.telemetry.summary()["counters"]["rejected"] == 1
    assert all(r.state == "done" for r in orch.queue.requests.values())
