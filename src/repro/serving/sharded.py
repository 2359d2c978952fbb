"""Mesh-sharded serving execution: one SPMD decode step over all slots.

The serving backends (engine.py / dense.py / static_admission.py) jit
one model entry point — the ragged ``prefill_extend_ragged`` scan — in
two dressings: the fused megabatch tick over the persistent batched
tree (with on-device sampling folded in) and the batched prefill
extend over per-task batch-1 trees. This module is the single place where a
``jax.sharding.Mesh`` enters that path, so every backend
(and therefore the whole A/B harness) scales across a data x model device
mesh without the orchestrator or scheduler changing at all:

  * **params** are placed once with ``param_shardings(...,
    replicate_fsdp=True)`` — weights replicated across "data" (decode is
    weights-stationary; no per-step FSDP all-gathers) and tensor-parallel
    over "model" where head/FFN dims divide.
  * **cache trees** are placed with ``cache_shardings``: decode slots
    batch over "data", KV heads over "model" (with the repo's
    divisibility fallback to replication — phi3's 10 KV heads on a
    model=4 mesh replicate rather than pad).
  * ``decode_step`` / ``prefill_extend_ragged`` are jitted with
    **explicit in/out shardings** (memoized per input structure, since
    the batched and batch-1 trees differ), so the cache layout is pinned
    across steps instead of drifting with whatever GSPMD infers.
  * ``insert`` splices a batch-1 prefix into the batched tree under jit
    with the prefix device-put row-wise and the output pinned back to
    the canonical batched shardings.

Unmeshed (``mesh=None``) every helper degrades to the exact pre-sharding
behavior: plain ``jax.jit`` and host-side splices.

Debug recipe (no accelerator needed; a v5e host has four chips, so the
recipes stay within 1x4 / 2x2)::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.launch.serve \
        --arch qwen3-0.6b --reduced --mesh 1x4 --requests 4
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_auto_mesh
from repro.launch.specs import (alloc_batched_caches, extract_slot_caches,
                                splice_caches)
from repro.models import inference as I
from repro.serving.sampling import sample
from repro.sharding import rules


# ==========================================================================
# mesh construction from a CLI "dxm" spec
# ==========================================================================
def parse_mesh_shape(spec: str) -> Tuple[int, int]:
    """``"1x4"`` -> ``(1, 4)`` (data ways, model ways)."""
    try:
        d, m = spec.lower().split("x")
        shape = (int(d), int(m))
    except ValueError:
        raise ValueError(f"mesh spec must look like '1x4' (data x model), "
                         f"got {spec!r}") from None
    if shape[0] < 1 or shape[1] < 1:
        raise ValueError(f"mesh axes must be >= 1, got {spec!r}")
    return shape


def build_mesh(spec: Optional[str]) -> Optional[Mesh]:
    """Build a ("data", "model") mesh from a "dxm" spec (None -> None).

    Works on real accelerators and on host platform devices alike; for a
    headless debug mesh export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=<d*m>`` before any
    jax import.
    """
    if not spec:
        return None
    shape = parse_mesh_shape(spec)
    need, have = shape[0] * shape[1], len(jax.devices())
    if have < need:
        raise RuntimeError(
            f"mesh {spec} needs {need} devices, found {have}; for a debug "
            "mesh on host devices set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} before jax "
            "imports")
    return make_auto_mesh(shape, ("data", "model"))


class InPlaceStep:
    """A jitted step in two forms that differ only in whether they donate
    the cache tree (argument 2), each compiled on first use. Calling the
    step never donates, so a caller may read the tree it passed after the
    call; :meth:`in_place` runs the form ``donate`` chose at build, which
    (when True) updates the tree in place and deletes the caller's input
    tree. :meth:`lower` compiles that same form."""

    def __init__(self, forms: Dict[bool, Callable], donate: bool):
        self._forms = forms
        self.donate = donate

    def __call__(self, params, feed, caches):
        return self._forms[False](params, feed, caches)

    def in_place(self, params, feed, caches):
        return self._forms[self.donate](params, feed, caches)

    def lower(self, *args):
        return self._forms[self.donate].lower(*args)

    def _cache_size(self) -> Optional[int]:
        """Compiled entries of both forms; None under a mesh, whose forms
        memoize their compiled entries in the backend's ``_fn_cache``."""
        sizes = [getattr(f, "_cache_size", None) for f in self._forms.values()]
        return None if None in sizes else sum(n() for n in sizes)


def _struct_key(tree: Any) -> Tuple:
    """Hashable (treedef, leaf shapes/dtypes) key for jit memoization."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (treedef, tuple((tuple(np.shape(l)), str(jnp.result_type(l)))
                           for l in leaves))


class ShardedDecodeMixin:
    """Mesh-aware jitted decode/extend + cache placement for backends.

    Expects the host class to provide ``self.cfg`` and ``self.opts``
    before calling :meth:`_sharding_setup`, and ``self.params`` before
    the first decode/extend call. With ``mesh=None`` everything reduces
    to the unsharded single-device path.
    """

    mesh: Optional[Mesh] = None

    # ------------------------------------------------------------------
    # setup / placement
    # ------------------------------------------------------------------
    def _sharding_setup(self, params, mesh: Optional[Mesh]):
        """Record the mesh and place params on it; returns the (possibly
        device-put) params."""
        self.mesh = mesh
        self._fn_cache: Dict[Tuple, Any] = {}
        self._splice_cache: Dict[Tuple, Any] = {}
        if mesh is None:
            self._param_sh = None
            return params
        self._param_sh = rules.param_shardings(params, mesh, self.cfg,
                                               replicate_fsdp=True)
        return jax.device_put(params, self._param_sh)

    def _replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def _row_sharding(self, b: int, ndim: int) -> NamedSharding:
        """Sharding for a batch-leading array: rows over "data" when the
        batch divides, else replicated."""
        bax = rules.pick(b, self.mesh, rules.batch_axes(self.mesh), "data")
        return NamedSharding(self.mesh, P(bax, *(None,) * (ndim - 1)))

    def cache_shardings_for(self, caches):
        """NamedSharding tree for a concrete cache tree (slots over
        "data", KV heads over "model", divisibility fallback)."""
        return rules.cache_shardings(caches, self.mesh, self.cfg)

    def place_caches(self, caches):
        """device_put a cache tree onto its canonical mesh shardings
        (identity when unmeshed)."""
        if self.mesh is None:
            return caches
        return jax.device_put(caches, self.cache_shardings_for(caches))

    # ------------------------------------------------------------------
    # jitted model steps
    # ------------------------------------------------------------------
    def _make_extend_batch(self) -> Callable:  # jaxlint: shapes(extend_batch=per-batch-width)
        """(params, (tokens [B, S], lengths [B]), caches) ->
        (last_logits [B, V], caches, per-row stats): the batched ragged
        prefill extend. Under a mesh the prefill rows shard over "data"
        (tokens, lengths, logits, and the batched cache tree all pinned
        with explicit in/out shardings via the same memoized-spec
        machinery as decode)."""

        def fn(params, feed, caches):
            tokens, lengths = feed
            return I.prefill_extend_ragged(params, self.cfg, tokens,
                                           lengths, caches, opts=self.opts)

        return jax.jit(fn) if self.mesh is None \
            else self._mesh_jit(fn, kind="extend_batch")

    # jaxlint: shapes(fused_step=2, fused_step_sel=1)
    def _make_fused_step(self, opts=None, *, kind: str = "fused_step",
                         donate: bool = False) -> Callable:
        """(params, feed, caches) -> (last_logits, caches, stats): the
        fused megabatch tick over the PERSISTENT batched cache tree.
        ``opts`` overrides ``self.opts`` for this build — the engine uses
        it to compile a second, selection-enabled variant of the same
        step (``DecodeOptions.selection_policy``) dispatched on
        decode-only ticks; ``kind`` keys the mesh-jit memo so the two
        variants never share a compiled entry.

        ``feed`` is ``(tokens [B, S], lengths [B], tok_dev [B],
        use_dev [B] bool, key [1, 2])``: prompt chunks arrive from the
        host left-aligned in ``tokens``; decode rows are length-1 ragged
        rows whose position-0 token is substituted from the ON-DEVICE
        sampled vector ``tok_dev`` (``use_dev`` marks them), so the
        decode feed never round-trips through the host between steps
        under the two-phase dispatch-ahead contract. Sampling happens
        INSIDE the same jitted
        call (``stats["sampled"]``), making a whole tick exactly one
        device dispatch: a decode row's next token and a finishing
        prefill row's first token come out together. Length-0 rows stay
        bit-identical via the ragged scan's row-masked writes.
        Under a mesh, rows shard over "data" exactly like the batched
        extend (the [1, 2] key replicates).

        Returns an :class:`InPlaceStep` whose ``in_place`` donates the
        cache tree when ``donate``: the step then updates it in place and
        the caller's input tree is deleted, so only a caller that reads
        nothing of that tree after the call may ask for it."""
        temperature = self.temperature
        opts = self.opts if opts is None else opts

        def fn(params, feed, caches):  # jaxlint: masked-scan-body
            # named scopes label the device trace's ops by the piece of the
            # step they belong to (models/inference.py, attention.py,
            # core/dual_cache.py); "fused_step" catches the rest
            with jax.named_scope("fused_step"):
                tokens, lengths, tok_dev, use_dev, key = feed
                tokens = tokens.at[:, 0].set(
                    jnp.where(use_dev, tok_dev, tokens[:, 0]))
                last_logits, caches, st = I.prefill_extend_ragged(
                    params, self.cfg, tokens, lengths, caches, opts=opts)
                with jax.named_scope("sample"):
                    sampled = sample(key[0], last_logits,
                                     temperature=temperature)
                    # per-row resident KV tokens computed IN-JIT from the
                    # post-step tree and pulled with collect's one sync, so
                    # memory_snapshot reads host state only
                    kv_rows = self._kv_tokens_device(caches)
                return last_logits, caches, {**st, "sampled": sampled,
                                             "kv_tokens_rows": kv_rows}

        if self.mesh is None:
            forms = {d: jax.jit(fn, donate_argnums=2 if d else ())
                     for d in (False, True)}
        else:
            forms = {d: self._mesh_jit(fn, kind=kind, donate=d)
                     for d in (False, True)}
        return InPlaceStep(forms, donate)

    def _mesh_jit(self, fn: Callable, *, kind: str,
                  donate: bool = False) -> Callable:
        """Wrap ``fn(params, tokens, caches)`` with explicit in/out
        shardings, memoized per (tokens, caches) structure — the batched
        decode, the batch-1 prefill tail, and the ragged batched extend
        (where ``tokens`` is a ``(tokens [B, S], lengths [B])`` feed
        tree) share one engine but need different placements."""

        def call(params, tokens, caches):
            key = (kind, donate) + _struct_key((tokens, caches))
            ent = self._fn_cache.get(key)
            if ent is None:
                ent = self._build_mesh_jit(fn, tokens, caches, donate)
                self._fn_cache[key] = ent
            jfn, tok_sh, csh = ent
            # eager prefill / splice outputs may carry compiler-chosen
            # placements; pin them (no-op copy when already canonical)
            return jfn(params, jax.device_put(tokens, tok_sh),
                       jax.device_put(caches, csh))

        return call

    def _build_mesh_jit(self, fn, tokens, caches,  # jaxlint: shapes(mesh-jit=per-structure)
                        donate: bool = False):
        mesh, cfg = self.mesh, self.cfg
        csh = self.cache_shardings_for(caches)
        # feed leaves with a batch-leading axis (tokens/lengths/device
        # feed) shard rows over "data"; anything else (the fused step's
        # [1, 2] PRNG key) replicates
        b = int(np.shape(jax.tree_util.tree_leaves(tokens)[0])[0])
        tok_sh = jax.tree.map(
            lambda x: (self._row_sharding(b, np.ndim(x))
                       if np.ndim(x) >= 1 and np.shape(x)[0] == b
                       else self._replicated()), tokens)
        out_struct = jax.eval_shape(fn, self.params, tokens, caches)
        logits_s, caches_s, stats_s = out_struct

        def row_or_repl(leaf):
            if leaf.ndim >= 1 and leaf.shape[0] == b:
                return self._row_sharding(b, leaf.ndim)
            return self._replicated()

        out_sh = (row_or_repl(logits_s),
                  rules.cache_shardings(caches_s, mesh, cfg),
                  jax.tree.map(row_or_repl, stats_s))
        jfn = jax.jit(fn, in_shardings=(self._param_sh, tok_sh, csh),
                      out_shardings=out_sh,
                      donate_argnums=2 if donate else ())
        return jfn, tok_sh, csh

    # ------------------------------------------------------------------
    # batched ragged prefill: stack / unstack around the one jitted call
    # ------------------------------------------------------------------
    def batched_prefill_stack(self, trees):  # jaxlint: shapes(stack=per-structure)
        """Stack B batch-1 prefill cache trees into one batch-B tree in a
        single jitted call (memoized per structure; under a mesh the
        result is pinned to the canonical batched shardings — prefill
        rows over "data", KV heads over "model").

        Rows are written with the same dynamic-update-slice splice the
        decode ``insert`` path uses, NOT a batch-axis concatenate: XLA
        CPU's SPMD partitioner miscomputes mixed-tiling concats (the
        PR-3 gate_features bug all over again — replicated batch-1
        inputs concatenated straight into a "data"-sharded batch axis
        come out permuted)."""
        trees = tuple(trees)
        n = len(trees)
        key = ("stack", n) + _struct_key(trees)
        ent = self._fn_cache.get(key)
        if ent is None:

            def fn(ts):
                out = alloc_batched_caches(ts[0], n)
                for i, t in enumerate(ts):
                    out = splice_caches(out, t, i)
                return out

            if self.mesh is None:
                ent = (jax.jit(fn), None)
            else:
                osh = rules.cache_shardings(
                    jax.eval_shape(fn, trees), self.mesh, self.cfg)
                ish = tuple(self.cache_shardings_for(t) for t in trees)
                ent = (jax.jit(fn, in_shardings=(ish,),
                               out_shardings=osh), ish)
            self._fn_cache[key] = ent
        jfn, ish = ent
        if ish is not None:
            trees = jax.device_put(trees, ish)
        return jfn(trees)

    def batched_prefill_unstack(self, batched, n: int):  # jaxlint: shapes(unstack=per-structure)
        """Slice a batch-``n`` prefill cache tree back into ``n`` batch-1
        trees in a single jitted call (inverse of
        :meth:`batched_prefill_stack`; bitwise row-preserving)."""
        key = ("unstack", n) + _struct_key(batched)
        ent = self._fn_cache.get(key)
        if ent is None:

            def fn(bt):
                return tuple(extract_slot_caches(bt, i) for i in range(n))

            if self.mesh is None:
                ent = jax.jit(fn)
            else:
                osh = tuple(rules.cache_shardings(t, self.mesh, self.cfg)
                            for t in jax.eval_shape(fn, batched))
                ent = jax.jit(fn, in_shardings=(
                    self.cache_shardings_for(batched),), out_shardings=osh)
            self._fn_cache[key] = ent
        return ent(batched)

    # ------------------------------------------------------------------
    # sharded slot splice (insert)
    # ------------------------------------------------------------------
    def sharded_splice(self, batch_tree, one_tree, slot: int):  # jaxlint: shapes(splice=per-structure)
        """``splice_caches`` with the batch-1 prefix device-put onto the
        mesh and the result pinned to the batched tree's canonical
        shardings (plain splice when unmeshed)."""
        if self.mesh is None:
            return splice_caches(batch_tree, one_tree, slot)
        key = _struct_key((batch_tree, one_tree))
        ent = self._splice_cache.get(key)
        if ent is None:
            bsh = self.cache_shardings_for(batch_tree)
            osh = self.cache_shardings_for(one_tree)
            jfn = jax.jit(splice_caches, static_argnums=2,
                          in_shardings=(bsh, osh), out_shardings=bsh)
            ent = (jfn, bsh, osh)
            self._splice_cache[key] = ent
        jfn, bsh, osh = ent
        return jfn(jax.device_put(batch_tree, bsh),
                   jax.device_put(one_tree, osh), slot)

    # ------------------------------------------------------------------
    # telemetry
    # ------------------------------------------------------------------
    def _per_shard_snapshot(self, snap: Dict[str, float],
                            leaf=None) -> Dict[str, float]:
        """Annotate a memory snapshot with mesh-level telemetry: the
        even-occupancy per-device share of the resident KV total
        (``kv_bytes`` stays global; with live slots concentrated on one
        data shard, that shard's devices hold proportionally more) and
        the mesh device count. ``leaf`` is a representative cache array
        whose sharding gives the per-device fraction."""
        if self.mesh is None:
            return snap
        snap["mesh_devices"] = float(self.mesh.size)
        frac = 1.0 / self.mesh.size
        if leaf is not None and hasattr(leaf, "sharding") and leaf.size:
            shard = int(np.prod(leaf.sharding.shard_shape(leaf.shape)))
            frac = shard / leaf.size
        snap["kv_bytes_per_shard"] = snap.get("kv_bytes", 0.0) * frac
        return snap
