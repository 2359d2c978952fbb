"""Admission-gated prefix cache: content-addressed shared-context reuse.

Three layers under test:

  * pool refcount/COW (serving/paged.py): ``share_stream`` pins pages by
    refcount; writes through a shared stream copy-on-write, so sharers
    never observe each other's mutations;
  * the store itself (serving/prefix_cache.py): chained chunk hashing,
    longest-prefix lookup, LRU eviction under a byte budget with
    deferred reclamation of still-referenced entries;
  * serving integration: a prefix hit splices the cached post-admission
    tree and resumes the fused scan at the suffix — streams must be
    byte-identical to cold prefill, through cancellation and concurrent
    hits included.
"""
import jax
import numpy as np
import pytest

from conftest import make_cfg
from repro.models import transformer as T
from repro.serving import paged
from repro.serving.backend import make_backend
from repro.serving.orchestrator import (Orchestrator, SchedulerConfig,
                                        ServeSession)
from repro.serving.prefix_cache import CachedPrefix, PrefixCache, chain_hashes

CHUNK = 16


# ==========================================================================
# pool: refcounted pages + copy-on-write through shared streams
# ==========================================================================
def test_pool_share_stream_refcounts():
    pool = paged.PagedKVPool(64, head_dim=4)
    src = ("pfx", 0)
    for i in range(20):                      # 2 pages (16-slot pages)
        pool.append(src, np.full(4, i), np.full(4, -i))
    used = pool.pages_in_use
    pool.share_stream(src, ("slot", 0))
    assert pool.pages_in_use == used          # no new pages allocated
    for p in pool.table(src).pages:
        assert pool.refcount(p) == 2
    # freeing one sharer decrefs; pages survive for the other
    pool.free_stream(("slot", 0))
    assert pool.pages_in_use == used
    for p in pool.table(src).pages:
        assert pool.refcount(p) == 1
    pool.free_stream(src)
    assert pool.pages_in_use == 0


def test_pool_cow_append_isolates_sharers():
    pool = paged.PagedKVPool(64, head_dim=4)
    src = ("pfx", 0)
    for i in range(20):
        pool.append(src, np.full(4, i), np.full(4, i))
    k0, _ = pool.gather(src)
    pool.share_stream(src, ("slot", 0))
    # append through the sharer lands on the shared tail page -> COW
    pool.append(("slot", 0), np.full(4, 99.0), np.full(4, 99.0))
    assert pool.table(src).pages[-1] != pool.table(("slot", 0)).pages[-1]
    k1, _ = pool.gather(src)
    np.testing.assert_array_equal(k0, k1)     # source bytes untouched
    ks, _ = pool.gather(("slot", 0))
    assert ks.shape[0] == 21 and ks[-1, 0] == 99.0
    np.testing.assert_array_equal(ks[:20], k0)


def test_pool_cow_overwrite_isolates_sharers():
    pool = paged.PagedKVPool(64, head_dim=4)
    src = ("pfx", 0)
    for i in range(20):
        pool.append(src, np.full(4, i), np.full(4, i))
    pool.share_stream(src, ("a",))
    pool.share_stream(src, ("b",))
    pool.overwrite(("a",), 3, np.full(4, 7.0), np.full(4, 7.0))
    pool.overwrite(("b",), 3, np.full(4, 8.0), np.full(4, 8.0))
    ka, _ = pool.gather(("a",))
    kb, _ = pool.gather(("b",))
    k0, _ = pool.gather(src)
    assert k0[3, 0] == 3.0 and ka[3, 0] == 7.0 and kb[3, 0] == 8.0


# ==========================================================================
# store: chained hashing, lookup, LRU + deferred eviction
# ==========================================================================
def test_chain_hashes_commit_to_whole_prefix():
    p = list(range(70))
    hs = chain_hashes(p, CHUNK)
    assert [n for n, _ in hs] == [16, 32, 48, 64]
    # same prefix -> same hash, regardless of suffix
    assert chain_hashes(p[:40], CHUNK)[-1] == hs[1]
    # a change in an EARLIER chunk flips every later boundary hash
    q = list(p)
    q[3] += 1
    assert chain_hashes(q, CHUNK)[1][1] != hs[1][1]
    # whole-prompt boundary excluded: nothing to resume with
    assert [n for n, _ in chain_hashes(list(range(32)), CHUNK)] == [16]


def _entry(key, n_tokens, n_bytes=100):
    return CachedPrefix(key=key, n_tokens=n_tokens, caches=None,
                        n_bytes=n_bytes)


def test_store_lookup_longest_and_capture_target():
    store = PrefixCache(quantum=CHUNK, budget_bytes=1 << 20)
    p = list(range(70))
    hs = dict(chain_hashes(p, CHUNK))
    assert store.lookup(p) is None and store.misses == 1
    assert store.capture_target(p) == (64, hs[64])
    store.insert(_entry(hs[16], 16))
    store.insert(_entry(hs[48], 48))
    e = store.lookup(p)
    assert e is not None and e.n_tokens == 48    # longest stored prefix
    assert e.refs == 1 and store.hits == 1
    store.release(e)
    # a prompt diverging inside chunk 2 only matches the 16-boundary
    q = p[:20] + [999] * 50
    e2 = store.lookup(q)
    assert e2 is not None and e2.n_tokens == 16
    store.release(e2)
    # 48 stored but 64 not: capture still targets the longest boundary
    assert store.capture_target(p) == (64, hs[64])
    store.insert(_entry(hs[64], 64))
    assert store.capture_target(p) is None


def test_store_lru_eviction_and_deferred_reclaim():
    freed = []
    store = PrefixCache(quantum=CHUNK, budget_bytes=250,
                        free_fn=freed.append)
    a, b, c = _entry("a", 16), _entry("b", 16), _entry("c", 16)
    store.insert(a)
    store.insert(b)
    store.insert(c)                     # 300 bytes > 250: evicts LRU head
    assert "a" not in store and freed == [a]
    assert store.evictions == 1 and store.bytes_used == 200
    # pin b (an admitted request holds it), then force its eviction
    b.refs += 1
    store.insert(_entry("d", 16))
    assert "b" not in store and freed == [a]   # deferred: still referenced
    store.release(b)
    assert freed == [a, b]              # reclaimed at the last release
    # raced duplicate insert keeps the incumbent, frees the newcomer
    dup = _entry("c", 16)
    store.insert(dup)
    assert freed == [a, b, dup] and store._entries["c"] is c
    store.clear()
    assert len(store) == 0 and c in freed and store.bytes_used == 0


# ==========================================================================
# serving integration: hit == cold bytes, cancel, concurrency, cleanup
# ==========================================================================
@pytest.fixture(scope="module")
def served():
    cfg = make_cfg("qwen3-0.6b", global_budget_frac=0.5)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    eng = make_backend("wgkv", params, cfg, slots=2, capacity=192)
    return cfg, eng


def _prompts(cfg, shared=48, tails=(8, 8), seed=0):
    rng = np.random.default_rng(seed)
    base = rng.integers(0, cfg.vocab_size - 8, size=shared).tolist()
    return [base + rng.integers(0, cfg.vocab_size - 8, size=t).tolist()
            for t in tails]


def _serve(eng, prompts, pc=None, max_new=4, **sched_kw):
    sess = ServeSession(eng, sched=SchedulerConfig(chunk_tokens=CHUNK,
                                                   **sched_kw),
                        prefix_cache=pc)
    hs = [sess.submit(p, max_new=max_new) for p in prompts]
    sess.run()
    sess.close()
    return [h.tokens() for h in hs], sess


def test_quantum_must_match_chunk(served):
    _, eng = served
    with pytest.raises(ValueError, match="quantum"):
        Orchestrator(eng, sched=SchedulerConfig(chunk_tokens=CHUNK),
                     prefix_cache=PrefixCache(quantum=CHUNK + 1))


def test_hit_streams_cold_bytes(served):
    """Round 2 hits the store for every request and streams exactly what
    cold prefill streamed; telemetry reports the hit."""
    cfg, eng = served
    prompts = _prompts(cfg)
    cold, _ = _serve(eng, prompts)
    pc = PrefixCache(quantum=CHUNK, free_fn=eng.release_prefix)
    warm1, _ = _serve(eng, prompts, pc)
    assert warm1 == cold                       # miss round: no effect
    assert pc.misses == 2 and pc.hits == 0 and len(pc) == 1
    warm2, sess = _serve(eng, prompts, pc)
    assert warm2 == cold                       # hit round: same bytes
    assert pc.hits == 2
    s = sess.telemetry.summary()
    assert s["prefix_hit_rate"] == 1.0
    assert s["prefix_tokens_reused"] == 2 * 48
    assert s["counters"]["prefix_hit"] == 2
    assert sess.telemetry.records[0].prefix_hit
    pc.clear()
    assert eng.pool.pages_in_use == 0          # store pages all reclaimed


def test_concurrent_hits_never_share_mutable_state(served):
    """Two simultaneous hits on one entry decode divergent suffixes; the
    entry's pool bytes must be untouched and both streams cold-exact."""
    cfg, eng = served
    prompts = _prompts(cfg, tails=(8, 12), seed=1)
    cold, _ = _serve(eng, prompts)
    pc = PrefixCache(quantum=CHUNK, free_fn=eng.release_prefix)
    _serve(eng, [prompts[0]], pc)              # populate (one miss)
    (entry,) = pc._entries.values()
    before = {k: pool_k.copy() for k in entry.stream_keys
              for pool_k in [eng.pool.gather(k)[0]]}
    warm, _ = _serve(eng, prompts, pc)         # both hit the same entry
    assert pc.hits == 2
    assert warm == cold
    assert entry.refs == 0                     # pins dropped post-splice
    for k in entry.stream_keys:                # entry bytes never mutated
        np.testing.assert_array_equal(eng.pool.gather(k)[0], before[k])
    pc.clear()
    assert eng.pool.pages_in_use == 0


def test_cancel_before_splice_releases_ref(served):
    """A request admitted on a hit but cancelled before its first
    dispatch drops its store pin, so eviction can reclaim the entry."""
    cfg, eng = served
    prompts = _prompts(cfg, tails=(8, 8), seed=2)
    pc = PrefixCache(quantum=CHUNK, free_fn=eng.release_prefix)
    _serve(eng, [prompts[0]], pc)              # populate
    (entry,) = pc._entries.values()
    # max_prefill_batch=1: both admits land in one tick, only the first
    # task dispatches — the second sits admitted with its entry pinned
    orch = Orchestrator(eng, sched=SchedulerConfig(chunk_tokens=CHUNK,
                                                   max_prefill_batch=1),
                        prefix_cache=pc)
    r0 = orch.submit(prompts[0], max_new=2)
    r1 = orch.submit(prompts[1], max_new=2)
    orch.tick()
    assert entry.refs == 1                     # r0 released at dispatch
    assert orch.cancel(r1)
    assert entry.refs == 0                     # cancel released the pin
    orch.run()
    orch.telemetry.stop()
    assert len(orch.tokens(r0)) == 2
    pc.clear()
    assert eng.pool.pages_in_use == 0


def test_async_dispatch_hits_match_sync(served):
    """dispatch_ahead=1 over the store streams the same bytes (captures
    mature at FIFO collect regardless of the in-flight window)."""
    cfg, eng = served
    prompts = _prompts(cfg, tails=(8, 8), seed=3)
    cold, _ = _serve(eng, prompts)
    pc = PrefixCache(quantum=CHUNK, free_fn=eng.release_prefix)
    _serve(eng, prompts, pc, dispatch_ahead=1)
    warm, _ = _serve(eng, prompts, pc, dispatch_ahead=1)
    assert warm == cold and pc.hits == 2
    pc.clear()
    assert eng.pool.pages_in_use == 0


def test_capture_under_dispatch_ahead_mirror_off_matches_mirror_on(served):
    """With the mirror off the fused step donates its tree, so the next
    dispatch deletes the tree a capture was registered against; the row
    is held at dispatch instead, and the stored tree equals, leaf for
    leaf, the one a mirror-on (non-donating) engine captures."""
    cfg, eng_on = served
    eng_off = make_backend("wgkv", eng_on.params, cfg, slots=2,
                           capacity=192, mirror_paged=False)
    prompts = _prompts(cfg, tails=(8, 8), seed=4)
    entries = {}
    for name, eng in (("off", eng_off), ("on", eng_on)):
        pc = PrefixCache(quantum=CHUNK, free_fn=eng.release_prefix)
        _, sess = _serve(eng, prompts, pc, dispatch_ahead=1)
        (entries[name],) = pc._entries.values()
        c = sess.telemetry.counters
        assert c["fused_donated_steps"] == (
            c["fused_steps"] if name == "off" else 0)
        entries[name + "_pc"] = pc
    off, on = entries["off"], entries["on"]
    assert off.n_tokens == on.n_tokens == 48
    assert off.kv_tokens == on.kv_tokens
    flat_off = jax.tree_util.tree_flatten_with_path(off.caches)[0]
    flat_on = jax.tree_util.tree_flatten_with_path(on.caches)[0]
    assert len(flat_off) == len(flat_on)
    for (path, a), (_, b) in zip(flat_off, flat_on):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))
    entries["on_pc"].clear()
    entries["off_pc"].clear()
    assert eng_on.pool.pages_in_use == 0
