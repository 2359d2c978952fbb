"""DenseEngine: the full-KV (no admission) baseline serving backend.

Implements the same :class:`repro.serving.backend.EngineBackend` protocol
as the WG-KV Engine, but serves the uncompressed dense cache through the
non-gated decode path (models/inference.py dense branch). Every prompt and
generated token is written — admission is identically 1.0 — so replaying
one arrival trace through this backend and the WG-KV backend yields the
paper's comparative numbers (memory reduction, decode speedup) as a
serving-level A/B instead of a microbenchmark.

Shares the batched slot machinery (insert/dispatch-collect/free via
launch/specs.py splice helpers) with the Engine base class; only the
prefill path and the memory accounting differ:

  * prefill: every chunk — the first included — rides the shared batched
    ragged extend scan from an empty DENSE cache template (decode_step
    dispatches on the cache type); for full causal attention the scan is
    mathematically the one-shot ``I.prefill(use_wgkv=False)``, and
    sharing the per-token path with the fused tick keeps fused-vs-unfused
    streams byte-identical.
  * memory: no paged-pool mirror — the dense baseline's resident KV is
    exactly ``t`` tokens per (layer, kv-head) stream, reported logically
    via ``memory_snapshot`` for the A/B memory comparison.
"""
from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.launch.specs import build_decode_caches
from repro.models import inference as I
from repro.models.attention import DenseCache
from repro.serving.backend import BackendCapabilities, PrefillTask
from repro.serving.engine import Engine


class DenseEngine(Engine):
    """Full-KV baseline backend (admission == 1.0, linear cache growth)."""

    def __init__(self, params, cfg: ModelConfig, *, slots: int = 4,
                 capacity: int = 4096, opts: Optional[I.DecodeOptions] = None,
                 eos: Optional[int] = None, temperature: float = 0.0,
                 seed: int = 0, mesh=None, **_paged_kw):
        # dense caches are contiguous [B, H, capacity, hd] buffers; the
        # paged mirror (and the mirror_paged kwarg) does not apply
        if opts is not None and opts.selection_policy is not None:
            raise ValueError(
                "selection_policy requires the paged dual cache; the dense "
                "full-KV baseline has no page metadata to select against")
        super().__init__(params, cfg, slots=slots, capacity=capacity,
                         opts=opts, eos=eos, temperature=temperature,
                         seed=seed, mirror_paged=False, mesh=mesh)
        # host-tracked per-slot sequence length: dense_cache_append past
        # ``capacity`` silently drops the write (JAX OOB scatter), so the
        # engine must fail loudly instead of serving a corrupted cache
        self._slot_len = [0] * slots

    # ------------------------------------------------------------------
    def capabilities(self) -> BackendCapabilities:
        return BackendCapabilities(
            name="dense", gated=False, paged=False,
            description="uncompressed full-KV cache (no admission)",
            sharded=self.mesh is not None)

    # memory_snapshot itself is inherited: the base reads the host-cached
    # per-row counts (fused stats / insert), so the dense baseline only
    # supplies its own in-jit counter and snapshot leaf
    def _kv_tokens_device(self, caches) -> jax.Array:
        total = None
        for dc in self._iter_dense(caches):
            per = dc.t * dc.k.shape[1]            # t tokens x kv heads
            total = per if total is None else total + per
        if total is None:
            b = int(np.shape(caches["t"])[0])
            return jnp.zeros((b,), jnp.int32)
        return total.astype(jnp.int32)

    def _snapshot_leaf(self):
        if self.caches is None:
            return None
        blocks = self.caches["blocks"]
        for i in range(len(self.cfg.block_pattern)):
            node = blocks[f"b{i}"]
            if isinstance(node, dict) and "self" in node:
                node = node["self"]
            if isinstance(node, DenseCache):
                return node.k
        return None

    def _iter_dense(self, caches) -> List[DenseCache]:
        """Batched DenseCache leaves, one per (repeat, block) layer."""
        out = []
        blocks = caches["blocks"]
        for i, bt in enumerate(self.cfg.block_pattern):
            node = blocks[f"b{i}"]
            if isinstance(node, dict) and "self" in node:
                node = node["self"]
            if isinstance(node, DenseCache):
                if node.k.ndim == 5:  # stacked [n_repeats, B, ...]
                    for r in range(node.k.shape[0]):
                        out.append(jax.tree.map(lambda x, r=r: x[r], node))
                else:
                    out.append(node)
        return out

    # ------------------------------------------------------------------
    # chunked prefill (dense: scan-from-empty like the base class; only
    # the capacity guard differs — the prompt must fit the dense buffer)
    # ------------------------------------------------------------------
    def start_prefill(self, prompt: List[int]) -> PrefillTask:
        # the first token is sampled from the prefill's own last-position
        # logits (no re-feed), so the prompt alone must fit the buffer
        assert len(prompt) < self.capacity, \
            f"prompt {len(prompt)} needs dense capacity > {len(prompt)}"
        return PrefillTask(prompt=list(prompt))

    def _extend_admission(self, adm_sum, take: int, full: bool) -> float:
        return 1.0 * take                  # dense admits every token

    def _build_empty_caches(self):
        # fused first-chunk open: an empty DENSE tree (t=0); the ragged
        # scan appends the chunk token-by-token, which for full causal
        # attention is mathematically the one-shot prefill
        return build_decode_caches(self.cfg, 1, self.capacity,
                                   use_wgkv=False, prefilled=0)

    # ------------------------------------------------------------------
    # capacity guard: a dense slot grows by one token per decode step
    # ------------------------------------------------------------------
    def insert(self, prefix, slot: int) -> None:
        super().insert(prefix, slot)
        self._slot_len[slot] = int(np.asarray(prefix.caches["t"])[0])

    def _pre_fused_dispatch(self, prefill, decode_rows) -> None:
        # same dispatch-time overflow guard for the fused step: a prefill
        # row grows by its chunk take, a decode row by one token
        for s, take in prefill:
            if self._slot_len[s] + take > self.capacity:
                raise RuntimeError(
                    f"dense cache overflow: slot {s} at t={self._slot_len[s]} "
                    f"+ chunk {take} > capacity {self.capacity}")
            self._slot_len[s] += take
        for s in decode_rows:
            if self._slot_len[s] >= self.capacity:
                raise RuntimeError(
                    f"dense cache overflow: slot {s} at t={self._slot_len[s]} "
                    f"== capacity {self.capacity}; raise capacity or lower "
                    "max_new")
            self._slot_len[s] += 1

    def free_slot(self, slot: int) -> None:
        super().free_slot(slot)
        self._slot_len[slot] = 0

    # ------------------------------------------------------------------
    # prefix store hooks: the dense baseline participates logically (the
    # stored artifact is its full-KV batch-1 tree; no pool streams)
    # ------------------------------------------------------------------
    def _adopt_prefix(self, slot: int, entry) -> None:
        super()._adopt_prefix(slot, entry)
        self._slot_len[slot] = entry.n_tokens

    def capture_prefix(self, step, slot: int, key: str, *,
                       adm_weighted: float = 0.0):
        from repro.launch.specs import cache_tree_bytes
        from repro.serving.prefix_cache import CachedPrefix
        caches = step.rows.pop(slot)
        n = int(jax.device_get(caches["t"])[0])
        layers = self._iter_dense(caches)
        heads = layers[0].k.shape[1] if layers else 0
        return CachedPrefix(key=key, n_tokens=n, caches=caches,
                            adm_weighted=adm_weighted, meta={},
                            kv_tokens=n * heads * len(layers),
                            n_bytes=cache_tree_bytes(caches))

    # ------------------------------------------------------------------
    def _decode_admission(self, st: Any, live_rows: List[int]) -> float:
        return 1.0  # the dense baseline writes everything
