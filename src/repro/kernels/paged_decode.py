"""Pallas TPU kernel: paged decode attention over the dual-cache pool.

The paper folds the kv-head dimension into batch (Appendix B) so each
(batch x kv-head) stream attends over its own ragged page list. On TPU the
page table is a *scalar-prefetch* operand: the BlockSpec index_map reads
``page_table[stream, j]`` to choose which physical page tile the next grid
step DMAs from HBM into VMEM — the TPU-native analogue of vLLM's gather.

Grid: (n_streams, max_pages_per_stream), pages innermost; flash-combine
scratch across page steps. Pages beyond ``lengths[stream]`` are masked
(their DMA still happens — index_map clamps to page 0 — but contributes 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, page: int, max_pages: int):
    n = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                              # [1, hd] single query row
    k = k_ref[0]                              # [page, hd]
    hd = q.shape[-1]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * (hd ** -0.5)
    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    s = jnp.where(pos < len_ref[n], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe[:, None])
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == max_pages - 1)
    def _out():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def paged_decode(q, k_pool, v_pool, page_table, lengths, *,
                 interpret: bool):
    """q: [N, hd]; k_pool/v_pool: [P, page, hd]; page_table: [N, max_pages]
    int32 physical page ids; lengths: [N] valid tokens. Returns [N, hd]."""
    n, hd = q.shape
    p_total, page, _ = k_pool.shape
    max_pages = page_table.shape[1]
    kernel = functools.partial(_kernel, page=page, max_pages=max_pages)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page_table, lengths
        grid=(n, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda i, j, tbl, ln: (i, 0, 0)),
            pl.BlockSpec((1, page, hd), lambda i, j, tbl, ln: (tbl[i, j], 0, 0)),
            pl.BlockSpec((1, page, hd), lambda i, j, tbl, ln: (tbl[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda i, j, tbl, ln: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, hd), q.dtype),
        interpret=interpret,
    )(page_table, lengths, q[:, None, :], k_pool, v_pool)
    return out[:, 0]


def _kernel_selected(table_ref, len_ref, sel_ref, nsel_ref,
                     q_ref, k_ref, v_ref, o_ref,
                     m_ref, l_ref, acc_ref, *, page: int, k_pages: int):
    n = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                              # [1, hd]
    k = k_ref[0]                              # [page, hd]
    hd = q.shape[-1]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * (hd ** -0.5)
    # token position within the LOGICAL stream is recovered from the
    # selected page id, so the ragged-tail mask is the same lengths[] test
    # as the dense-page kernel; whole pages past n_sel[stream] are dropped
    logical = sel_ref[n, j]
    pos = logical * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    ok = (pos < len_ref[n]) & (j < nsel_ref[n])
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe[:, None])
    p = jnp.where(ok, p, 0.0)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == k_pages - 1)
    def _out():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def paged_decode_selected(q, k_pool, v_pool, page_table, lengths,
                          sel_ids, n_sel, *, interpret: bool):
    """Quest-selected paged decode: attend over only the top-K pages.

    Same layout as :func:`paged_decode` plus ``sel_ids`` [N, K] int32
    LOGICAL page indices per stream (sorted ascending — identity
    permutation when K covers every page) and ``n_sel`` [N] valid counts.
    The grid's page axis shrinks from max_pages to K: the index_map
    double-indirects ``page_table[i, sel_ids[i, j]]`` so only the selected
    physical pages are ever DMA'd from HBM — the kernel-level form of the
    gathered decode path, cost O(K·page) per stream instead of
    O(max_pages·page). Returns [N, hd]."""
    n, hd = q.shape
    p_total, page, _ = k_pool.shape
    k_pages = sel_ids.shape[1]
    kernel = functools.partial(_kernel_selected, page=page, k_pages=k_pages)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # page_table, lengths, sel_ids, n_sel
        grid=(n, k_pages),
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda i, j, tbl, ln, sel, ns: (i, 0, 0)),
            pl.BlockSpec((1, page, hd),
                         lambda i, j, tbl, ln, sel, ns: (tbl[i, sel[i, j]], 0, 0)),
            pl.BlockSpec((1, page, hd),
                         lambda i, j, tbl, ln, sel, ns: (tbl[i, sel[i, j]], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd),
                               lambda i, j, tbl, ln, sel, ns: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, hd), q.dtype),
        interpret=interpret,
    )(page_table, lengths, sel_ids.astype(jnp.int32),
      n_sel.astype(jnp.int32), q[:, None, :], k_pool, v_pool)
    return out[:, 0]
