"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.gate_mlp import gate_mlp
from repro.kernels.gated_flash import gated_flash
from repro.kernels.paged_decode import paged_decode, paged_decode_selected
from repro.kernels.rglru_scan import rglru_scan_pallas
from repro.kernels.vertical_slash import vertical_slash

TOL = {jnp.float32: 5e-5, jnp.bfloat16: 5e-2}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape).astype(dtype)


@pytest.mark.parametrize("n,s,hd,w,bq,bk", [
    (2, 256, 64, 32, 64, 64),
    (1, 128, 128, 16, 128, 32),
    (3, 512, 64, 256, 128, 128),
    (1, 64, 256, 8, 32, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gated_flash_sweep(n, s, hd, w, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (_rand(ks[i], (n, s, hd), dtype) for i in range(3))
    g = jax.nn.sigmoid(jax.random.normal(ks[3], (n, s))).astype(jnp.float32)
    out = gated_flash(q, k, v, g, w_local=w, bq=bq, bk=bk, interpret=True)
    r = ref.gated_flash_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32), g, w_local=w)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(r),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("n,s,hd,w,c,bc", [
    (2, 256, 64, 64, 64, 32),
    (1, 512, 128, 128, 128, 128),
    (2, 384, 64, 128, 96, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_vertical_slash_sweep(n, s, hd, w, c, bc, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    q, k, v = (_rand(ks[i], (n, s, hd), dtype) for i in range(3))
    gpos = jnp.sort(jax.random.randint(ks[3], (n, c), 0, s - w), axis=-1)
    nvalid = jax.random.randint(ks[4], (n, 1), 1, c)
    gpos = jnp.where(jnp.arange(c)[None] < nvalid, gpos,
                     jnp.iinfo(jnp.int32).max)
    bi = jnp.arange(n)[:, None]
    safe = jnp.minimum(gpos, s - 1)
    kg = jnp.where((gpos < s)[..., None], k[bi, safe], 0)
    vg = jnp.where((gpos < s)[..., None], v[bi, safe], 0)
    out = vertical_slash(q, k, v, kg, vg, gpos, w_local=w, bc=bc,
                         interpret=True)
    r = ref.vertical_slash_ref(*(x.astype(jnp.float32)
                                 for x in (q, k, v, kg, vg)), gpos, w_local=w)
    assert not bool(jnp.isnan(out.astype(jnp.float32)).any())
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(r),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("n,hd,page,ptotal,mp", [
    (6, 64, 16, 32, 8), (2, 128, 16, 8, 4), (12, 64, 32, 64, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_sweep(n, hd, page, ptotal, mp, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q = _rand(ks[0], (n, hd), dtype)
    kp = _rand(ks[1], (ptotal, page, hd), dtype)
    vp = _rand(ks[2], (ptotal, page, hd), dtype)
    tbl = jax.random.randint(ks[3], (n, mp), 0, ptotal)
    lens = jax.random.randint(ks[4], (n,), 1, mp * page)
    out = paged_decode(q, kp, vp, tbl, lens, interpret=True)
    r = ref.paged_decode_ref(*(x.astype(jnp.float32) for x in (q, kp, vp)),
                             tbl, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(r),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("n,hd,page,ptotal,mp,kp", [
    (6, 64, 16, 32, 8, 3), (2, 128, 16, 8, 4, 2), (4, 64, 32, 64, 16, 5),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_selected_sweep(n, hd, page, ptotal, mp, kp, dtype):
    """Quest-selected paged decode vs oracle: random sorted K-subsets of
    each stream's logical pages, ragged n_sel (trailing ids dropped)."""
    ks = jax.random.split(jax.random.PRNGKey(6), 7)
    q = _rand(ks[0], (n, hd), dtype)
    kpool = _rand(ks[1], (ptotal, page, hd), dtype)
    vpool = _rand(ks[2], (ptotal, page, hd), dtype)
    tbl = jax.random.randint(ks[3], (n, mp), 0, ptotal)
    lens = jax.random.randint(ks[4], (n,), 1, mp * page)
    perm = jax.random.uniform(ks[5], (n, mp)).argsort(axis=-1)[:, :kp]
    sel = jnp.sort(perm, axis=-1).astype(jnp.int32)
    nsel = jax.random.randint(ks[6], (n,), 1, kp + 1)
    out = paged_decode_selected(q, kpool, vpool, tbl, lens, sel, nsel,
                                interpret=True)
    r = ref.paged_decode_selected_ref(
        *(x.astype(jnp.float32) for x in (q, kpool, vpool)),
        tbl, lens, sel, nsel)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(r),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_paged_decode_selected_all_pages_identity():
    """K covering every page with the ascending id list is the identity
    permutation: the selected kernel reduces over the same lanes in the
    same order as the dense-page kernel, so outputs are BITWISE equal —
    the kernel-level form of the serving parity acceptance axis."""
    n, hd, page, ptotal, mp = 4, 64, 16, 16, 6
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    q = _rand(ks[0], (n, hd), jnp.float32)
    kpool = _rand(ks[1], (ptotal, page, hd), jnp.float32)
    vpool = _rand(ks[2], (ptotal, page, hd), jnp.float32)
    tbl = jax.random.randint(ks[3], (n, mp), 0, ptotal)
    lens = jax.random.randint(ks[4], (n,), 1, mp * page)
    sel = jnp.broadcast_to(jnp.arange(mp, dtype=jnp.int32)[None], (n, mp))
    nsel = jnp.full((n,), mp, jnp.int32)
    a = paged_decode(q, kpool, vpool, tbl, lens, interpret=True)
    b = paged_decode_selected(q, kpool, vpool, tbl, lens, sel, nsel,
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("b,s,d,bt,bd", [
    (2, 256, 256, 64, 128), (1, 128, 512, 128, 128), (3, 64, 128, 32, 64),
])
def test_rglru_scan_sweep(b, s, d, bt, bd):
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (b, s, d)))
    bb = jax.random.normal(ks[1], (b, s, d))
    out = rglru_scan_pallas(a, bb, bt=bt, bd=bd, interpret=True)
    r = ref.rglru_scan_ref(a, bb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), atol=1e-5)


@pytest.mark.parametrize("h,s,f,m,bs", [
    (4, 512, 128, 64, 128), (2, 64, 64, 32, 64), (8, 256, 256, 16, 256),
])
def test_gate_mlp_sweep(h, s, f, m, bs):
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(ks[0], (h, s, f))
    w1 = jax.random.normal(ks[1], (h, f, m)) * 0.1
    b1 = jax.random.normal(ks[2], (h, m)) * 0.1
    w2 = jax.random.normal(ks[3], (h, m, 1)) * 0.1
    b2 = jnp.zeros((h, 1))
    out = gate_mlp(x, w1, b1, w2, b2, bs=bs, interpret=True)
    r = ref.gate_mlp_ref(x, w1, b1, w2, b2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), atol=1e-5)
    assert ((np.asarray(out) > 0) & (np.asarray(out) < 1)).all()


def test_ops_wrappers_gqa_fold():
    """Model-level wrappers: GQA head folding matches core mask semantics."""
    from repro.core import masks as M
    from repro.kernels import ops

    key = jax.random.PRNGKey(5)
    B, Hq, Hkv, S, hd, W = 2, 4, 2, 128, 64, 32
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, Hq, S, hd))
    k = jax.random.normal(ks[1], (B, Hkv, S, hd))
    v = jax.random.normal(ks[2], (B, Hkv, S, hd))
    g = jax.nn.sigmoid(jax.random.normal(ks[3], (B, Hkv, S)))
    out = ops.gated_flash_attention(q, k, v, g, w_local=W, bq=64, bk=64)
    bias = M.write_gate_bias(g, S, W)
    qg = q.reshape(B, Hkv, Hq // Hkv, S, hd)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k) / jnp.sqrt(hd) + bias[:, :, None]
    r = jnp.einsum("bhgqk,bhkd->bhgqd", jax.nn.softmax(logits, -1), v)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(r.reshape(B, Hq, S, hd)), atol=5e-5)
