"""Serving engine + paged memory integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_cfg
from repro.models import inference as I
from repro.models import transformer as T
from repro.serving import paged
from repro.serving.engine import Engine


@pytest.fixture(scope="module")
def served():
    cfg = make_cfg("qwen3-0.6b", global_budget_frac=0.5)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_pool_allocator_basics():
    pool = paged.PagedKVPool(16, head_dim=4)
    key = (0, 0, 0, "global")
    for i in range(40):
        pool.append(key, np.full(4, i), np.full(4, -i))
    t = pool.table(key)
    assert t.length == 40
    assert len(t.pages) == 3  # ceil(40/16)
    k, v = pool.gather(key)
    assert (k[:, 0] == np.arange(40)).all()
    used = pool.pages_in_use
    pool.free_stream(key)
    assert pool.pages_in_use == used - 3


def test_pool_exhaustion():
    pool = paged.PagedKVPool(3, head_dim=4)  # page 0 reserved => 2 usable
    key = (0,)
    with pytest.raises(paged.PoolExhausted):
        for i in range(100):
            pool.append(key, np.zeros(4), np.zeros(4))


def test_pool_fragmentation_metric():
    pool = paged.PagedKVPool(64, head_dim=4)
    pool.append((1,), np.zeros(4), np.zeros(4))  # 1 token on a 16-slot page
    assert pool.utilization() == pytest.approx(1 / 16)


def test_pool_reserve_grows_free_list():
    pool = paged.PagedKVPool(3, head_dim=4)
    pool.append((0,), np.ones(4), np.ones(4))
    pool.reserve(5)
    pool.reserve(4)                              # never shrinks
    assert pool.num_pages == 5 and pool.k.shape[0] == 5
    for _ in range(3 * paged.PAGE_SIZE):         # 3 more pages now fit
        pool.append((1,), np.zeros(4), np.zeros(4))
    assert pool.pages_in_use == 4
    assert (pool.gather((0,))[0] == 1).all()
    with pytest.raises(paged.PoolExhausted):
        for _ in range(paged.PAGE_SIZE + 1):
            pool.append((1,), np.zeros(4), np.zeros(4))


def test_pool_sized_from_cache_shapes(served):
    """slots x attention layers x KV heads x (global + ring pages) + the
    null page: a full slot fits however much the gates admit."""
    cfg, params = served
    eng = Engine(params, cfg, slots=3, capacity=128)
    g_pages = -(-cfg.wgkv.global_budget(128) // paged.PAGE_SIZE)
    r_pages = -(-cfg.wgkv.w_local // paged.PAGE_SIZE)
    row = cfg.n_layers * cfg.n_kv_heads * (g_pages + r_pages)
    assert eng.pool.num_pages == 3 * row + 1


def test_kernel_interpret_mode(monkeypatch):
    from repro.kernels import ops
    assert ops.interpret_mode() is True          # tests run on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops.interpret_mode()


def test_engine_end_to_end(served):
    cfg, params = served
    eng = Engine(params, cfg, slots=2, capacity=128)
    rids = [eng.add_request(list(range(10 + i, 60 + i)), max_new=6)
            for i in range(3)]
    eng.run(max_steps=40)
    assert all(eng.requests[r].done for r in rids)
    assert all(len(eng.requests[r].out) == 6 for r in rids)
    assert eng.pool.pages_in_use == 0  # everything freed


def test_engine_paged_mirror_exact(served):
    """Physical pool bytes == logical dual cache, and the paged_decode
    kernel over the pool matches an oracle computed from the logical view."""
    cfg, params = served
    eng = Engine(params, cfg, slots=2, capacity=128)
    eng.add_request(list(range(5, 55)), max_new=30)
    eng.add_request(list(range(100, 170)), max_new=30)
    for _ in range(10):
        eng.step()
    assert eng.verify_paged() < 2e-3


def test_engine_matches_raw_decode(served):
    """Engine output tokens == direct prefill+decode greedy rollout.

    The first token comes from the prefill's own last-position logits
    (the retired convention re-fed ``prompt[-1]``, double-writing its KV
    at position n); later tokens from the decode loop."""
    cfg, params = served
    prompt = list(range(20, 68))  # 48 tokens = 3 x w_local
    eng = Engine(params, cfg, slots=1, capacity=128, mirror_paged=False)
    rid = eng.add_request(prompt, max_new=5)
    eng.run(max_steps=10)
    got = eng.requests[rid].out
    toks = jnp.asarray(prompt, jnp.int32)[None]
    po, caches = I.prefill(params, cfg, toks,
                           budget=cfg.wgkv.global_budget(128), max_len=128)
    cur = int(jnp.argmax(po.logits[0]))
    want = [cur]
    for _ in range(4):
        logits, caches, _ = I.decode_step(
            params, cfg, jnp.asarray([cur], jnp.int32), caches)
        cur = int(jnp.argmax(logits[0]))
        want.append(cur)
    assert got == want


def test_engine_with_composition(served):
    cfg, params = served
    opts = I.DecodeOptions(quest_pages=2, evict_hard_budget=48, w_obs=16)
    eng = Engine(params, cfg, slots=2, capacity=128, opts=opts,
                 mirror_paged=False)
    eng.add_request(list(range(0, 80)), max_new=8)
    eng.run(max_steps=20)
    assert all(r.done for r in eng.requests.values())
