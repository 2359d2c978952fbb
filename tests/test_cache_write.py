"""The dual cache's decode write as a Pallas kernel (interpreted here)
against the jnp scatters: bit-identical on every leaf, for active and
inactive rows, a full budget, fresh and partial pages, in float32 and
bfloat16, at shapes whose axes are and are not whole tiles."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import dual_cache as DC
from repro.core.selection import META_BIG, PAGE_SIZE

TAU = 0.5


def _state(rng, l, b, h, w, c, d, dtype):
    p = c // PAGE_SIZE
    lk = rng.standard_normal((l, b, h, w, d)).astype(np.float32)
    gk = rng.standard_normal((l, b, h, c, d)).astype(np.float32)
    pkmin = rng.standard_normal((l, b, h, p, d)).astype(np.float32)
    pkmin[rng.random((l, b, h, p)) < 0.3] = META_BIG
    gcnt = rng.integers(0, c + 1, (l, b, h))
    gcnt[rng.random((l, b, h)) < 0.2] = c                 # budget full
    page_start = rng.random((l, b, h)) < 0.3              # a fresh page
    gcnt[page_start] = PAGE_SIZE * (gcnt[page_start] // PAGE_SIZE)
    lpos = rng.integers(-1, 5000, (l, b, w))
    lpos[rng.random((l, b, w)) < 0.2] = -1                # empty slots
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    return DC.DualCache(
        lk=jnp.asarray(lk, dtype), lv=jnp.asarray(2 * lk, dtype),
        lg=jnp.asarray(rng.random((l, b, h, w)), jnp.float32),
        lpos=i32(lpos), gk=jnp.asarray(gk, dtype),
        gv=jnp.asarray(gk - 1, dtype),
        gpos=i32(rng.integers(0, 99, (l, b, h, c))), gcnt=i32(gcnt),
        t=i32(rng.integers(0, 5000, (l, b))),
        ptr=i32(rng.integers(0, w, (l, b))),
        overflow=i32(rng.integers(0, 9, (l, b, h))),
        pkmin=jnp.asarray(pkmin, dtype), pkmax=jnp.asarray(-pkmin, dtype))


def _write(cache, k, v, g, active, layer, kernel):
    return jax.jit(lambda *a: DC.lazy_promote_and_write(
        *a[:4], tau=TAU, active=a[4], layer=a[5], kernel=kernel))(
            cache, k, v, g, active, layer)


@pytest.mark.parametrize("seed,b,h,w,c,dtype", [
    (0, 1, 1, 16, 64, jnp.float32),
    (1, 2, 2, 16, 64, jnp.bfloat16),
    (2, 4, 8, 16, 64, jnp.float32),
    (3, 3, 2, 24, 256, jnp.bfloat16),
    (4, 2, 4, 12, 48, jnp.float32),
    (5, 8, 8, 32, 256, jnp.bfloat16),
])
@pytest.mark.parametrize("rows", ["mixed", "none"])
def test_kernel_write_matches_jnp(seed, b, h, w, c, dtype, rows):
    rng = np.random.default_rng(seed)
    l, d = 3, 128
    cache = _state(rng, l, b, h, w, c, d, dtype)
    k = jnp.asarray(rng.standard_normal((b, h, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, h, d)), dtype)
    g = jnp.asarray(rng.random((b, h)), jnp.float32)
    li = seed % l
    if rows == "mixed":
        # row 0 writes, and its first heads promote a valid, admitted
        # victim: one into a fresh page, one into a page part filled
        active = jnp.asarray(rng.random(b) < 0.7).at[0].set(True)
        p0 = int(cache.ptr[li, 0])
        cache = cache._replace(lpos=cache.lpos.at[li, 0, p0].set(7))
        for j in range(min(h, 2)):
            slot = PAGE_SIZE * int(rng.integers(0, c // PAGE_SIZE)) \
                + 3 * ((j + seed) % 2)
            cache = cache._replace(
                lg=cache.lg.at[li, 0, j, p0].set(0.9),
                gcnt=cache.gcnt.at[li, 0, j].set(slot))
    else:
        active = jnp.zeros((b,), bool)
    layer = jnp.asarray(li, jnp.int32)
    want = _write(cache, k, v, g, active, layer, kernel=False)
    got = _write(cache, k, v, g, active, layer, kernel=True)
    for name in DC.DualCache._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)),
            err_msg=name)
    if rows == "none":      # every row masked: nothing changes
        for name in DC.DualCache._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, name)),
                np.asarray(getattr(cache, name)), err_msg=name)
    else:                   # the write did promote and append somewhere
        assert not np.array_equal(np.asarray(got.gcnt),
                                  np.asarray(cache.gcnt))
        assert not np.array_equal(np.asarray(got.lk), np.asarray(cache.lk))


@pytest.mark.parametrize("platform,meshed,kernel", [
    ("cpu", False, False), ("tpu", False, True), ("tpu", True, False)],
    ids=["cpu", "tpu", "tpu-mesh"])
def test_engine_chooses_the_write(monkeypatch, platform, meshed, kernel):
    """The engine, which knows whether it shards the cache, picks the
    write: the kernel on a TPU without a mesh, the jnp scatters on the
    CPU and under a mesh, whose partitioner splits them."""
    from conftest import make_cfg
    from repro.models import transformer as T
    from repro.serving.backend import make_backend
    from repro.serving.sharded import build_mesh
    cfg = make_cfg("qwen3-0.6b")
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    mesh = build_mesh("1x1") if meshed else None
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    eng = make_backend("wgkv", params, cfg, slots=2, capacity=64,
                       mirror_paged=False, mesh=mesh)
    assert eng.opts.write_kernel is kernel
