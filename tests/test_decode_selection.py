"""Decode-time top-K page selection (Quest) in the fused serving tick.

Parity standard: ``topk_page_ids`` returns ascending-sorted page ids, so
when K covers every page the id list is the identity permutation and the
gathered decode path reduces over the same lanes in the same order as
the full path — greedy streams must be BYTE-identical to selection off
(``selection=None``). With K < pages the gathered path must still serve
complete streams while touching fewer pages (``selected_pages``
counter), and the incremental ``pkmin``/``pkmax`` page metadata the dual
cache maintains in-jit must equal a from-scratch ``build_page_meta``
rebuild after prefill + decode + slot-churn. The 2x2-mesh variant of the
stream parity lives in test_sharded_serving.py; the kernel-level sweep
in test_kernels.py.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_cfg
from repro.core import admission as A
from repro.core.selection import PAGE_SIZE, build_page_meta
from repro.models import transformer as T
from repro.serving.backend import make_backend
from repro.serving.obs import Tracer
from repro.serving.orchestrator import SchedulerConfig, ServeSession

pytestmark = pytest.mark.backends

CAPACITY = 64
ALL_PAGES = CAPACITY // PAGE_SIZE  # quest:4 covers every page
MAX_NEW = 12

_rng = np.random.default_rng(42)
# long enough past w_local=16 that the gate populates global pages
PROMPTS = [list(_rng.integers(0, 200, 48 + 8 * i)) for i in range(4)]


@pytest.fixture(scope="module")
def served():
    # tau=0.1 keeps the threshold away from the random-init gate-score
    # cluster at 0.5 (knife-edge note), so both decode paths admit the
    # same token set and byte-parity is meaningful
    cfg = make_cfg("qwen3-0.6b")
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve(params, cfg, selection, backend="wgkv"):
    eng = make_backend(backend, params, cfg, slots=2, capacity=CAPACITY,
                       temperature=0.0, seed=0, selection=selection)
    tracer = Tracer(capacity=1 << 14)
    sess = ServeSession(eng, sched=SchedulerConfig(chunk_tokens=16,
                                                   dispatch_ahead=1),
                        tracer=tracer)
    handles = [sess.submit(p, max_new=MAX_NEW) for p in PROMPTS]
    sess.run()
    streams = [tuple(h.tokens()) for h in handles]
    counters = dict(sess.orchestrator.telemetry.counters)
    sess.close()
    spans = [s.name for s in tracer.spans]
    return streams, counters, spans, eng.capabilities()


@pytest.fixture(scope="module")
def runs(served):
    """Off / K-covers-all / partial-K serves of the same workload, shared
    across the assertions below (each serve compiles the fused step)."""
    cfg, params = served
    return {sel: _serve(params, cfg, sel)
            for sel in (None, f"quest:{ALL_PAGES}", "quest:2")}


# ==========================================================================
# byte parity: selection with K covering every page == selection off
# ==========================================================================
def test_stream_parity_off_vs_all_pages(runs):
    base, _, spans0, cap0 = runs[None]
    sel_all, c_all, spans_all, cap_all = runs[f"quest:{ALL_PAGES}"]
    assert cap0.selection is None
    assert cap_all.selection == f"quest:{ALL_PAGES}"
    assert all(len(s) == MAX_NEW for s in base)
    assert base == sel_all
    # the gathered path actually ran (counters + trace span), and the
    # off path never did
    assert c_all["selected_pages"] > 0 and c_all["selection_time_s"] > 0
    assert "selection" in spans_all
    assert "selection" not in spans0


def test_stream_parity_static_backend(served):
    """The static-admission backend family inherits the same selection
    surface: off vs K-all byte-identical there too."""
    cfg, params = served
    base, _, _, _ = _serve(params, cfg, None, backend="streaming_llm")
    sel, c, _, cap = _serve(params, cfg, f"quest:{ALL_PAGES}",
                            backend="streaming_llm")
    assert cap.selection == f"quest:{ALL_PAGES}"
    assert c["selected_pages"] > 0
    assert base == sel


# ==========================================================================
# partial K: streams complete, fewer pages gathered
# ==========================================================================
def test_partial_k_serves_with_fewer_pages(runs):
    _, c0, _, _ = runs[None]
    _, c_all, _, _ = runs[f"quest:{ALL_PAGES}"]
    sel2, c2, spans2, cap2 = runs["quest:2"]
    assert cap2.selection == "quest:2"
    assert all(len(s) == MAX_NEW for s in sel2)
    assert c0.get("selected_pages", 0) == 0
    assert 0 < c2["selected_pages"] < c_all["selected_pages"]
    assert c2["selection_time_s"] > 0
    assert "selection" in spans2


def test_dense_rejects_selection(served):
    cfg, params = served
    with pytest.raises(ValueError, match="selection"):
        make_backend("dense", params, cfg, slots=2, capacity=CAPACITY,
                     selection="quest:2")


# ==========================================================================
# incremental page metadata == from-scratch rebuild after churn
# ==========================================================================
def _assert_meta_matches_rebuild(eng):
    """Every dual-cache leaf's incrementally-maintained pkmin/pkmax equals
    build_page_meta over the live global entries — bitwise (min/max are
    exact, and both paths fold exactly the valid lanes)."""
    checked = 0
    for lkey, dc in eng._iter_dual(eng.caches):
        c = dc.gk.shape[2]
        valid = jnp.arange(c)[None, None] < dc.gcnt[..., None]
        meta = build_page_meta(dc.gk, valid)
        np.testing.assert_array_equal(
            np.asarray(dc.pkmin), np.asarray(meta.kmin), err_msg=str(lkey))
        np.testing.assert_array_equal(
            np.asarray(dc.pkmax), np.asarray(meta.kmax), err_msg=str(lkey))
        checked += 1
    assert checked > 0


def test_incremental_meta_matches_rebuild(served):
    cfg, params = served
    eng = make_backend("wgkv", params, cfg, slots=2, capacity=CAPACITY,
                       temperature=0.0, seed=0)
    eng.insert(eng.prefill(PROMPTS[0], emit_first=True), 0)
    eng.insert(eng.prefill(PROMPTS[1], emit_first=True), 1)
    for _ in range(8):
        eng.collect(eng.step_batch([]))
    _assert_meta_matches_rebuild(eng)
    # slot churn: retire row 0 and splice a fresh request in, then decode
    # past a page boundary — the boundary-reset in the incremental update
    # must stop the retired occupant's metadata widening the bounds
    eng.free_slot(0)
    eng.insert(eng.prefill(PROMPTS[2], emit_first=True), 0)
    for _ in range(8):
        eng.collect(eng.step_batch([]))
    _assert_meta_matches_rebuild(eng)
    # at least one stream actually promoted past the ring into global
    assert any(int(np.asarray(dc.gcnt).max()) > 0
               for _, dc in eng._iter_dual(eng.caches))


# ==========================================================================
# knife-edge tau guard (the parity footgun behind the tau=0.1 convention)
# ==========================================================================
def test_tau_guard_warns_on_knife_edge():
    g = jnp.asarray([0.40, 0.5004, 0.60])
    with pytest.warns(RuntimeWarning, match="knife-edge"):
        m = A.check_tau_margin(g, 0.5)
    assert m == pytest.approx(4e-4, rel=1e-3)
    # a tau clear of the score cluster passes silently and reports margin
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        m2 = A.check_tau_margin(g, 0.1)
    assert m2 == pytest.approx(0.30, rel=1e-5)
