"""Serving engine: the JetStream-style accelerator backend for the WG-KV
dual cache, with the paged physical layer (serving/paged.py) mirroring
every logical cache write — page tables, lazy-promotion page appends,
ring-slot overwrites — exactly as §4.1/§4.3 of the paper describe, plus
Quest/SnapKV composition flags.

The model math runs through the jitted decode path (models/inference.py);
the engine implements the :class:`repro.serving.backend.EngineBackend`
protocol — the prefill/insert/generate decomposition an outer
continuous-batching orchestrator (serving/orchestrator/) schedules
backend-agnostically (dense full-KV and static-admission siblings live in
serving/dense.py and serving/static_admission.py):

  * ``step_batch(tasks, chunk, decode=True)`` — the FUSED megabatch
    tick: one jitted ragged call over the persistent batched cache tree
    advances every live row whatever its phase. A first-chunk task is
    spliced in as an EMPTY row (per-row ``t`` makes the ragged scan
    start it at position 0 — the batch-1 budgeted open path is gone
    from the fused tick), mid-prefill rows take their next prompt
    chunk, live decode rows piggyback as length-1 rows fed from the
    on-device sampled vector, dead rows are length-0 bit-identical
    padding. Sampling runs inside the same call; ``collect`` returns
    decode tokens AND the first tokens of rows whose prompt finished.
    On a DECODE-ONLY tick (no prefill tasks in the dispatch) an engine
    configured with ``DecodeOptions.selection_policy = "quest:K"``
    dispatches a second compiled variant of the same fused step whose
    attention GATHERS only the top-K global pages per (row, kv head) —
    scored query-aware from the incremental per-page key min/max
    metadata the dual cache maintains in-jit (core/selection.py) — so
    decode attention reads K*16 + W entries instead of the full global
    budget. Mixed ticks (any prompt chunk aboard) always run the full
    path; with K >= resident pages the gather is the identity
    permutation and the token stream is byte-identical to selection
    off.
  * ``start_prefill`` / ``finish_prefill`` / ``prefill`` — task
    construction plus the one-shot convenience wrapper over the same
    batched ragged ``prefill_extend_ragged`` scan the fused tick runs
    (offline/eval callers; serving traffic rides ``step_batch``). The
    unfused per-cycle driver (``prefill_step_batch``) served its
    deprecation cycle and is gone.
  * ``insert(prefix, slot)`` — splice a batch-1 cache tree into the
    batched decode state (launch/specs.py helpers) and mirror it into
    the physical paged pool (offline prefix path; fused rows are
    already resident).
  * ``collect(step)`` — the host sync point: pull sampled tokens, fold
    stats, apply the paged-mirror delta. (``dispatch_decode`` served
    its deprecation cycle and is gone — ``step_batch([])`` is the
    decode-only dispatch.)
  * ``free_slot(slot)`` — release the slot and reclaim its pool pages.

The legacy fixed-slot loop (``add_request``/``step``/``run``) is kept as a
thin layer over that API. The ``verify_paged()`` method recomputes one
layer's decode attention from the *physical pool* via the paged_decode
Pallas kernel and asserts it matches the logical path — the systems-level
correctness check that theoretical paging actually serves the right bytes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh

from repro.analysis.contracts import hot_path
from repro.configs.base import ModelConfig
from repro.core.dual_cache import DualCache
from repro.launch.specs import (alloc_batched_caches, build_decode_caches,
                                cache_tree_bytes, extract_slot_caches)
from repro.models import inference as I
from repro.serving import paged
from repro.serving.backend import (BackendCapabilities, FusedStep,  # noqa: F401,E501
                                   InflightStep, Prefix, PrefillTask)
from repro.serving.obs.trace import NULL_TRACER
from repro.serving.sampling import sample
from repro.serving.sharded import InPlaceStep, ShardedDecodeMixin


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class Engine(ShardedDecodeMixin):
    """Batched serving backend (slots = max concurrent decodes).

    Implements the :class:`repro.serving.backend.EngineBackend` protocol
    for the paper's write-gated dual cache. With ``mesh`` set (a
    ("data", "model") :class:`jax.sharding.Mesh`), params are placed
    model-parallel, the batched slot state shards rows over "data" and KV
    heads over "model", and every jitted decode/extend runs as one SPMD
    step over the mesh (serving/sharded.py)."""

    def __init__(self, params, cfg: ModelConfig, *, slots: int = 4,
                 capacity: int = 4096, opts: Optional[I.DecodeOptions] = None,
                 eos: Optional[int] = None,
                 temperature: float = 0.0, seed: int = 0,
                 mirror_paged: bool = True, mesh: Optional[Mesh] = None):
        assert cfg.has_attention_cache, "engine serves KV-cache archs"
        self.cfg = cfg
        self.slots = slots
        self.capacity = capacity
        self.opts = opts or I.DecodeOptions()
        self.eos = eos
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)
        self.requests: Dict[int, Request] = {}
        self.slot_rid: List[Optional[int]] = [None] * slots
        self._next_rid = 0
        self.caches = None
        self.live: List[bool] = [False] * slots
        # host view of each row's newest token (telemetry / invariants);
        # the authoritative decode feed is the DEVICE vector `_tok_dev`,
        # which dispatch-ahead keeps one or more steps ahead of this list
        self.last_token: List[int] = [0] * slots
        # bumped on every insert/free so collect() can tell whether a slot
        # still belongs to the request a step was dispatched for
        self._slot_gen: List[int] = [0] * slots
        self.mirror = mirror_paged
        # decode-time page selection: the engine's base opts run the full
        # path (prefill chunks and mixed ticks must see every admitted
        # token); the policy compiles into a SECOND fused-step variant
        # dispatched only on decode-only ticks
        self.selection = self.opts.selection_policy
        self._sel_k = I.parse_selection_policy(self.selection)  # validates
        if self.selection is not None:
            self.opts = dataclasses.replace(self.opts, selection_policy=None)
        if mirror_paged:
            # every slot row at its largest, plus the null page; each
            # prefix-store entry holding pool streams reserves one more row
            self._row_pages = self._row_pool_pages()
            self._pool_entries: set = set()
            self.pool = paged.PagedKVPool(slots * self._row_pages + 1,
                                          cfg.head_dim)
        self.params = self._sharding_setup(params, mesh)
        if self.mesh is None and jax.default_backend() == "tpu":
            # one device: the decode write runs as the Pallas kernel; a
            # mesh keeps the jnp scatters its partitioner splits
            self.opts = dataclasses.replace(self.opts, write_kernel=True)
        self._extend_batch = self._make_extend_batch()
        # the fused step updates the cache tree in place (donation) unless
        # something reads its input tree after the call: the paged
        # mirror's collect diffs ``FusedStep.before`` against ``after``
        donate = not mirror_paged
        self._fused = self._make_fused_step(donate=donate)
        self._fused_sel = None if self.selection is None \
            else self._make_fused_step(
                dataclasses.replace(self.opts,
                                    selection_policy=self.selection),
                kind="fused_step_sel", donate=donate)
        self._tok_dev = jnp.zeros((slots,), jnp.int32)
        # fused path: which rows of the persistent batched tree hold a
        # mid-prefill task's state (spliced empty at its first step_batch)
        self._resident: List[bool] = [False] * slots
        self._empty_tree = None
        # host cache of per-row resident KV tokens: computed IN-JIT by the
        # fused step (stats["kv_tokens_rows"]) and refreshed at collect's
        # one sync — memory_snapshot reads this instead of pulling device
        # counters on the metrics path
        self._kv_rows = np.zeros((slots,), np.float64)
        # prefix-cache adoption bookkeeping: the CachedPrefix a row was
        # seeded from (drives the suffix-only pool mirror at finish) and
        # whether an eviction trigger fired since the row opened (eviction
        # compacts/reorders the global cache, forcing the full re-mirror)
        self._slot_prefix: List[Optional[object]] = [None] * slots
        self._slot_evicted: List[bool] = [False] * slots
        self.stats = {"steps": 0, "evict_triggers": 0.0, "decode_adm_sum": 0.0,
                      # extend-phase advances only (the path batching
                      # coalesces): wall time is a true device measure
                      # because _extend_ragged syncs on the step's stats
                      # before returning
                      "extend_time_s": 0.0, "extend_tokens": 0.0,
                      # fused megabatch ticks: dispatch->collect wall per
                      # step, plus the prefill-stage share (steps carrying
                      # at least one prompt chunk, and the chunk tokens
                      # they advanced) so bench can report a compile-free
                      # fused prefill-stage tokens/s
                      "fused_steps": 0.0, "fused_time_s": 0.0,
                      "fused_prefill_time_s": 0.0,
                      # fused steps whose call updated the cache tree in
                      # place (donated it); all of them without the mirror
                      "fused_donated_steps": 0.0,
                      "fused_prefill_tokens": 0.0,
                      # fixed-shape padding accounting: every fused
                      # dispatch pays for ``slots`` rows whatever their
                      # length; 1 - active/slot rows is the padding
                      # fraction bench reports so the CPU-XLA stage
                      # ratios are interpretable
                      "fused_slot_rows": 0.0, "fused_active_rows": 0.0,
                      # decode-time page selection: pages gathered (mean
                      # over kv heads, summed over attention layers and
                      # decode row-steps) and the wall time of
                      # selection-enabled fused steps
                      "selected_pages": 0.0, "selection_time_s": 0.0,
                      # collect's wait for the device: the device_get that
                      # blocks on the fused call (the rest of collect is
                      # host work)
                      "collect_wait_time_s": 0.0}
        # observability handle; the Orchestrator overwrites this with its
        # own tracer so engine-side sub-phase spans share its timeline
        self.tracer = NULL_TRACER

    # ------------------------------------------------------------------
    # EngineBackend protocol: descriptor + memory telemetry
    # ------------------------------------------------------------------
    def capabilities(self) -> BackendCapabilities:
        return BackendCapabilities(
            name="wgkv", gated=True, paged=self.mirror,
            description="write-gated dual cache (learned admission)",
            sharded=self.mesh is not None, selection=self.selection)

    # the fused tick's declared compiled-shape budget — PR 7/8's "exactly
    # three compiled shapes" as data: the base fused step compiles
    # (slots, chunk) for prefill-carrying ticks and (slots, 1) for
    # decode-only ticks; the selection variant compiles (slots, 1) only.
    # analysis.CompileSentinel asserts the jit caches stay within this
    # over a replay; the legacy synchronous extend path ("extend_batch")
    # is per-batch-width by design and carries no budget.
    COMPILE_SHAPE_BUDGETS: Dict[str, int] = {
        "fused_step": 2,
        "fused_step_sel": 1,
    }

    def compiled_shape_counts(self) -> Dict[str, int]:
        """Jit-cache entry count per step kind: ``_cache_size()`` of the
        plain jits when unmeshed, ``_fn_cache`` entries per kind under a
        mesh (each memoized entry is one compiled structure)."""
        out: Dict[str, int] = {}
        for kind, fn in (("extend_batch", self._extend_batch),
                         ("fused_step", self._fused),
                         ("fused_step_sel", self._fused_sel)):
            if fn is None:
                continue
            size = getattr(fn, "_cache_size", None)
            n = size() if size is not None else None
            out[kind] = (int(n) if n is not None else
                         sum(1 for k in self._fn_cache if k and k[0] == kind))
        return out

    @hot_path
    def memory_snapshot(self) -> Dict[str, float]:
        """Point-in-time memory telemetry: resident logical KV tokens/bytes
        over live slots, plus physical pool occupancy when mirroring and
        per-shard KV bytes when meshed.

        Reads HOST state only: the per-row token counts are computed
        in-jit by the fused step (``stats["kv_tokens_rows"]``) and cached
        at collect's designated sync (``insert`` seeds its slot the same
        way), so sampling memory every tick never pulls device counters
        inside the dispatch-ahead window."""
        snap: Dict[str, float] = {}
        if self.mirror:
            snap["pool_pages"] = float(self.pool.pages_in_use)
            snap["pool_util"] = float(self.pool.utilization())
        live = [s for s in range(self.slots) if self.live[s]]
        toks = float(self._kv_rows[live].sum()) if live else 0.0
        snap["kv_tokens"] = float(toks)
        snap["kv_bytes"] = float(
            toks * 2 * self.cfg.head_dim * jnp.dtype(self.cfg.dtype).itemsize)
        return self._per_shard_snapshot(snap, self._snapshot_leaf())

    def _snapshot_leaf(self):
        """A representative batched cache leaf whose sharding metadata
        gives the per-device KV fraction (no device sync)."""
        if self.caches is None:
            return None
        blocks = self.caches["blocks"]
        for i in range(len(self.cfg.block_pattern)):
            node = blocks[f"b{i}"]
            if isinstance(node, dict) and "self" in node:
                node = node["self"]
            if isinstance(node, DualCache):
                return node.gk
        return None

    def _row_pool_pages(self) -> int:
        """Most pool pages one cache row's streams can hold, read off the
        engine's own cache shapes: per (dual-cache layer, KV head), the
        global budget plus the ring, each in whole pages."""
        tree = jax.eval_shape(self._build_empty_caches)
        pages = 0
        for node in tree["blocks"].values():
            if isinstance(node, dict) and "self" in node:
                node = node["self"]
            if isinstance(node, DualCache):
                *lead, heads, cap, _ = node.gk.shape
                ring = node.lk.shape[-2]
                layers = lead[0] if len(lead) == 2 else 1
                pages += layers * heads * (-(-cap // paged.PAGE_SIZE)
                                           + -(-ring // paged.PAGE_SIZE))
        return pages

    def _kv_tokens_device(self, caches) -> jax.Array:
        """[B] resident KV token count per row, computed from device
        values WITHOUT syncing (traced inside the fused step): per layer,
        admitted global entries summed over kv heads plus the filled ring
        window per head — the same accounting memory_snapshot reported
        when it pulled these counters itself."""
        total = None
        for _, dc in self._iter_dual(caches):
            per = (dc.gcnt.sum(axis=1)
                   + jnp.minimum(dc.t, dc.w_local) * dc.gcnt.shape[1])
            total = per if total is None else total + per
        if total is None:
            b = int(np.shape(caches["t"])[0])
            return jnp.zeros((b,), jnp.int32)
        return total.astype(jnp.int32)

    # ------------------------------------------------------------------
    # JetStream-style backend API: chunked prefill
    # ------------------------------------------------------------------
    @property
    def _w_align(self) -> int:
        """Prefill chunk alignment: the largest ring window in the model."""
        w = self.cfg.wgkv.w_local
        if any(bt == "local_attn"
               for bt in self.cfg.block_pattern + self.cfg.stem_pattern):
            w = max(w, self.cfg.sliding_window)
        return w

    def start_prefill(self, prompt: List[int]) -> PrefillTask:
        return PrefillTask(prompt=list(prompt))

    def _fresh_task_caches(self):
        """Batch-1 EMPTY decode-cache tree: the state a prefill row starts
        from before its first token. Cached — jax arrays are immutable, so
        one template serves every unfused short-prompt open and every
        fused row splice."""
        if self._empty_tree is None:
            self._empty_tree = self._build_empty_caches()
        return self._empty_tree

    def _build_empty_caches(self):
        caches = build_decode_caches(
            self.cfg, 1, self.capacity, use_wgkv=True, prefilled=0)
        if self.opts.evict_hard_budget is not None:
            caches["obs"] = I._init_obs_tree(self.cfg, 1, self.opts)
        return caches

    @hot_path
    def _extend_ragged(self, tasks: List[PrefillTask],
                       max_tokens: Optional[int]) -> None:
        """ONE batched ragged extend for every mid-prefill task. ``S`` is
        pinned to ``max_tokens`` when chunked, and rounded up to a
        ``w_align`` multiple when unchunked — one compiled shape per
        batch width instead of one per distinct tail length; rows whose
        remaining prompt is shorter are masked padding past their
        length. At B = 1 the task's own batch-1 tree feeds the scan
        directly — no stack/unstack round trip."""
        t_wall = time.perf_counter()
        takes = [len(t.prompt) - t.pos if max_tokens is None
                 else min(len(t.prompt) - t.pos, max_tokens) for t in tasks]
        if max_tokens is None:
            q = self._w_align
            s = -(-max(takes) // q) * q
        else:
            s = max_tokens
        b = len(tasks)
        toks = np.zeros((b, s), np.int32)
        for i, (t, take) in enumerate(zip(tasks, takes)):
            toks[i, :take] = t.prompt[t.pos:t.pos + take]
        batched = tasks[0].caches if b == 1 \
            else self.batched_prefill_stack([t.caches for t in tasks])
        with self.tracer.span("prefill_extend_ragged", batch=b, s=s,
                              tokens=int(sum(takes))):
            logits, batched, st = self._extend_batch(
                self.params,
                (jnp.asarray(toks), jnp.asarray(takes, jnp.int32)), batched)
            outs = (batched,) if b == 1 \
                else self.batched_prefill_unstack(batched, b)
            trig, adm = jax.device_get(  # jaxlint: allow-sync(synchronous extend path - the sync IS the extend_time_s measure)
                (st["evict_trigger_rows"], st["adm_sum_rows"]))
        # the device_get above blocked on the extend, so this wall delta
        # is a true device+host measure of the coalesced advance — the
        # batched-vs-per-request axis bench_serving's speedup rides on
        self.stats["extend_time_s"] += time.perf_counter() - t_wall
        self.stats["extend_tokens"] += float(sum(takes))
        self.stats["evict_triggers"] += float(trig.sum())
        for i, (t, take) in enumerate(zip(tasks, takes)):
            t.caches = outs[i]
            t.last_logits = logits[i:i + 1]
            t.adm_weighted += self._extend_admission(
                adm[i], take, full=(max_tokens is not None
                                    and take == max_tokens))
            t.pos += take

    def _extend_admission(self, adm_sum, take: int, full: bool) -> float:
        """Admission mass one ragged extend adds to a task's
        ``adm_weighted``, mirroring the sequential accounting: a full
        chunk records mean * take (float32 mean, like the device scan's),
        a ragged tail the raw per-step sum."""
        if full:
            return float(np.float32(adm_sum) / np.float32(take)) * take
        return float(adm_sum)

    def finish_prefill(self, task: PrefillTask, *,
                       emit_first: bool = True) -> Prefix:
        """Seal a completed prefill task into a Prefix. With
        ``emit_first`` the first generated token is sampled from the
        prefill's own last-position logits (JetStream semantics: prefill
        returns the first token, so streaming TTFT ends at prefill, not at
        the next batched decode). The prefill paths already computed those
        logits, so no extra decode step runs — the old convention of
        re-feeding ``prompt[-1]`` wrote a duplicate KV entry at position n
        and shifted every later position by one."""
        assert task.done, "prefill task not finished"
        assert task.last_logits is not None, "prefill produced no logits"
        adm = task.adm_weighted / max(task.pos, 1)
        prefix = Prefix(caches=task.caches, prompt_len=len(task.prompt),
                        mean_admission=adm)
        if emit_first:
            self.key, sk = jax.random.split(self.key)
            prefix.first_token = int(
                sample(sk, task.last_logits, temperature=self.temperature)[0])
            prefix.first_logits = task.last_logits[0]
        return prefix

    def prefill(self, prompt: List[int], *,
                chunk_tokens: Optional[int] = None,
                emit_first: bool = True) -> Prefix:
        """One-shot convenience wrapper: drive one task's whole prompt
        through the batched ragged extend (``chunk_tokens`` sets the
        chunk width; None ingests the remaining prompt in one aligned
        call). Offline/eval surface — serving traffic rides the fused
        :meth:`step_batch`, which shares the identical per-token scan."""
        if chunk_tokens is not None and chunk_tokens < 1:
            raise ValueError(f"chunk_tokens must be >= 1, got {chunk_tokens}")
        task = self.start_prefill(prompt)
        task.caches = self._fresh_task_caches()
        while task.pos < len(task.prompt):
            self._extend_ragged([task], chunk_tokens)
        return self.finish_prefill(task, emit_first=emit_first)

    # ------------------------------------------------------------------
    # JetStream-style backend API: insert / dispatch-collect / free
    # ------------------------------------------------------------------
    def insert(self, prefix: Prefix, slot: int) -> None:
        """Splice a prefix's caches into batch row ``slot`` (device-put
        onto the mesh when sharded) and mirror it into the physical paged
        pool."""
        if self.caches is None:
            self.caches = self.place_caches(
                alloc_batched_caches(prefix.caches, self.slots))
        self.caches = self.sharded_splice(self.caches, prefix.caches, slot)
        self.live[slot] = True
        self._slot_gen[slot] += 1
        tok = prefix.first_token if prefix.first_token is not None else 0
        self.last_token[slot] = tok
        self._tok_dev = self._tok_dev.at[slot].set(tok)
        # seed the host kv accounting (insert is a sanctioned sync point;
        # fused rows are refreshed by every collect instead)
        self._kv_rows[slot] = float(jax.device_get(
            self._kv_tokens_device(prefix.caches))[0])
        if self.mirror:
            self._mirror_prefill(slot, prefix.caches)

    # ------------------------------------------------------------------
    # fused megabatch tick: ONE jitted ragged call per dispatched step
    # ------------------------------------------------------------------
    @hot_path
    def step_batch(self, tasks: List[PrefillTask],
                   max_tokens: Optional[int] = None, *,
                   decode: bool = True) -> Optional[FusedStep]:
        """Dispatch ONE fused jitted ragged step advancing every live row
        of the persistent batched cache tree — prefill chunks and decode
        tokens together — without synchronizing.

        Each ``task`` must carry its reserved ``slot``. A task seen for
        the first time has an EMPTY batch-1 tree spliced into its row
        (per-row ``t`` offsets mean the ragged scan simply starts it at
        position 0 — there is no separately-compiled batch-1 open); a
        mid-prefill row takes up to ``max_tokens`` of its remaining
        prompt; with ``decode`` every live slot not taking a chunk joins
        as a length-1 row fed from the ON-DEVICE sampled-token vector;
        all other rows are length-0 padding kept bit-identical by the
        scan's row-masked writes. Sampling runs inside the same
        jitted call, so a finishing row's first generated token and every
        decode row's next token come back together from :meth:`collect`.

        Host state advances at dispatch (teacher-forced positions; a
        finishing row goes live immediately) so a second fused step can
        be dispatched behind this one (dispatch-ahead depth >= 1). With
        ``DecodeOptions.selection_policy`` set, a task-less dispatch
        runs the gathered top-K page-selection variant of the same
        compiled step (full-path parity at K >= resident pages). At most
        three compiled shapes exist per engine: ``[slots, chunk]``,
        ``[slots, 1]``, and the selection ``[slots, 1]``. Returns None
        when nothing can advance."""
        if max_tokens is not None and max_tokens < 1:
            raise ValueError(f"max_tokens must be >= 1, got {max_tokens}")
        tasks = [t for t in tasks if not t.done]
        if not tasks and not (decode and any(self.live)):
            return None
        t0 = time.perf_counter()
        if self.caches is None:
            self.caches = self.place_caches(
                alloc_batched_caches(self._fresh_task_caches(), self.slots))
        for t in tasks:
            assert t.slot is not None, "fused step_batch needs slot-bound tasks"
            assert not self.live[t.slot], "prefill task in a live decode row"
            if not self._resident[t.slot]:
                if t.prefix_entry is not None:
                    # prefix-cache hit: splice the cached (already
                    # gate-filtered) tree instead of the empty template —
                    # the row's per-cache ``t`` makes the ragged scan
                    # resume at the suffix, skipping the re-prefill
                    with self.tracer.span("prefix_splice", slot=t.slot,
                                          tokens=t.prefix_entry.n_tokens):
                        self.caches = self.sharded_splice(
                            self.caches, t.prefix_entry.caches, t.slot)
                    self._adopt_prefix(t.slot, t.prefix_entry)
                else:
                    # first-chunk open: splice the empty template into the
                    # row (a dynamic-update-slice, not a model call — the
                    # chunk itself runs through the same fused scan below)
                    with self.tracer.span("fused_open", slot=t.slot):
                        self.caches = self.sharded_splice(
                            self.caches, self._fresh_task_caches(), t.slot)
                    self._slot_prefix[t.slot] = None
                self._resident[t.slot] = True
                self._slot_gen[t.slot] += 1
        with self.tracer.span("fused_feed"):
            # ragged feed: prompt chunks left-aligned per row; S pinned to
            # the chunk width (or w-aligned when unchunked) for compile
            # stability
            takes = [len(t.prompt) - t.pos if max_tokens is None
                     else min(len(t.prompt) - t.pos, max_tokens)
                     for t in tasks]
            if not tasks:
                s = 1
            elif max_tokens is None:
                q = self._w_align
                s = -(-max(takes) // q) * q
            else:
                s = max_tokens
            toks = np.zeros((self.slots, s), np.int32)
            lengths = np.zeros((self.slots,), np.int32)
            use_dev = np.zeros((self.slots,), bool)
            for t, take in zip(tasks, takes):
                toks[t.slot, :take] = t.prompt[t.pos:t.pos + take]
                lengths[t.slot] = take
            decode_rows = tuple(
                sl for sl in range(self.slots)
                if decode and self.live[sl] and lengths[sl] == 0)
            for sl in decode_rows:
                lengths[sl] = 1
                use_dev[sl] = True
            # a dead row decodes masked but still feeds its last_token; a
            # nonzero token there is a missed free_slot reset (a stale
            # replay of the retired request's final token)
            assert all(self.last_token[sl] == 0 for sl in range(self.slots)
                       if not self.live[sl] and lengths[sl] == 0), \
                "stale last_token on a dead row"
            self._pre_fused_dispatch(
                [(t.slot, take) for t, take in zip(tasks, takes)],
                decode_rows)
            # fixed-shape padding accounting: the compiled step always
            # spans ``slots`` rows; only length>0 rows do real work
            self.stats["fused_slot_rows"] += float(self.slots)
            self.stats["fused_active_rows"] += float(
                int((lengths > 0).sum()))
            # decode-only ticks run the gathered top-K selection variant
            # when configured; any prompt chunk aboard forces the full path
            # (its decode rows ride that mixed call with full attention)
            use_sel = self._fused_sel is not None and not tasks
            self.key, sk = jax.random.split(self.key)
            before = self.caches
            mirror = self.mirror
            fused = self._fused_sel if use_sel else self._fused
            # donate only through the engine's own step: a step replaced
            # by a wrapper may read the tree it was given after the call
            donated = isinstance(fused, InPlaceStep) and fused.donate
            if donated:
                fused = fused.in_place
            feed = (jnp.asarray(toks), jnp.asarray(lengths), self._tok_dev,
                    jnp.asarray(use_dev), sk[None])
        with self.tracer.span("fused_call"):
            if use_sel:
                with self.tracer.span("selection", k=self._sel_k,
                                      rows=len(decode_rows)):
                    logits, self.caches, st = fused(
                        self.params, feed, before)
            else:
                logits, self.caches, st = fused(self.params, feed, before)
        with self.tracer.span("fused_bookkeep"):
            sampled = st["sampled"]
            # host bookkeeping at dispatch (teacher-forced, deterministic):
            # advance positions; a finishing row goes live NOW so the next
            # dispatched step can already decode it
            finishing = []
            for t, take in zip(tasks, takes):
                t.pos += take
                fin = t.pos >= len(t.prompt)
                finishing.append(fin)
                if fin:
                    self.live[t.slot] = True
            # only rows that really sampled this step (decode rows +
            # finishing prefill rows) update the device feed; a
            # masked/mid-prefill row's sampled value is garbage and must
            # not clobber its feed token
            fed = np.zeros((self.slots,), bool)
            for sl in decode_rows:
                fed[sl] = True
            for t, fin in zip(tasks, finishing):
                fed[t.slot] = fin
            self._tok_dev = jnp.where(jnp.asarray(fed), sampled,
                                      self._tok_dev)
            fulls = [max_tokens is not None and take == max_tokens
                     for take in takes]
            return FusedStep(
                tokens=sampled, stats=st,
                before=before if mirror else None,
                # ``after`` is the live tree (references, no device copy);
                # when the fused call donates, the next dispatch deletes
                # it, so a prefix capture holds its row at dispatch
                # (``hold_prefix_row``) and collect never reads it
                after=self.caches, donated=donated,
                live=tuple(self.live), gen=tuple(self._slot_gen),
                tasks=tuple(tasks), takes=tuple(takes), fulls=tuple(fulls),
                finishing=tuple(finishing), decode_rows=decode_rows,
                had_prefill=bool(tasks), t_dispatch=t0, selection=use_sel,
                logits=logits)

    def _pre_fused_dispatch(self, prefill: List[Tuple[int, int]],
                            decode_rows: Tuple[int, ...]) -> None:
        """Hook before a fused dispatch: ``prefill`` is [(slot, take)].
        DenseEngine uses it for host-side slot-length tracking and the
        capacity overflow guard; the dual cache never overflows (ring
        wraps, global is budget-bounded)."""

    @hot_path
    def _collect_fused(self, step: FusedStep) -> Dict[int, int]:
        """Collect one fused step: ONE host sync pulls sampled tokens and
        per-row stats; fold admission/eviction accounting, mirror
        finishing rows' full prefixes and decode rows' deltas into the
        paged pool, and return {slot: token} — decode tokens plus the
        FIRST tokens of rows whose prompt completed in this step. The
        per-slot generation guard drops rows freed (or freed and
        re-opened) while the step was in flight."""
        assert not step.collected, "in-flight step collected twice"
        step.collected = True
        with self.tracer.span("collect_wait"):
            t_wait = time.perf_counter()
            nxt, trig, adm, selp, kvr = jax.device_get(  # jaxlint: allow-sync(collect is THE designated sync point of the dispatch/collect contract)
                (step.tokens, step.stats["evict_trigger_rows"],
                 step.stats["adm_sum_rows"],
                 step.stats["selected_pages_rows"],
                 step.stats["kv_tokens_rows"]))
            self.stats["collect_wait_time_s"] += \
                time.perf_counter() - t_wait
        # refresh the host kv accounting memory_snapshot reads (rows whose
        # slot churned while the step was in flight keep their newer value)
        for sl in range(self.slots):
            if self._slot_gen[sl] == step.gen[sl]:
                self._kv_rows[sl] = float(kvr[sl])
            if trig[sl] > 0:
                # SnapKV eviction compacts/reorders the row's global cache:
                # a prefix-hit row can no longer take the suffix-only
                # mirror at finish
                self._slot_evicted[sl] = True
        # the device_get blocked on the fused call, so this wall delta is
        # a true device+host measure of the whole dispatched step
        wall = time.perf_counter() - step.t_dispatch
        self.stats["fused_steps"] += 1
        self.stats["fused_donated_steps"] += float(step.donated)
        self.stats["fused_time_s"] += wall
        if step.had_prefill:
            self.stats["fused_prefill_time_s"] += wall
            self.stats["fused_prefill_tokens"] += float(sum(step.takes))
        if step.selection:
            self.stats["selection_time_s"] += wall
            if step.decode_rows:
                self.stats["selected_pages"] += float(
                    selp[list(step.decode_rows)].sum())
        self.stats["evict_triggers"] += float(trig.sum())
        # prefill-row admission: same float path as the unfused extend
        for t, take, full in zip(step.tasks, step.takes, step.fulls):
            t.adm_weighted += self._extend_admission(adm[t.slot], take,
                                                     full=full)
        if step.decode_rows:
            self.stats["steps"] += 1
            # a decode row has exactly one real position, so its ragged
            # adm SUM is that step's per-row mean admission
            self.stats["decode_adm_sum"] += self._decode_admission(
                {"mean_admission": adm}, list(step.decode_rows))
        rows = [s for s in step.decode_rows
                if self.live[s] and self._slot_gen[s] == step.gen[s]]
        if self.mirror and step.before is not None:
            for t, fin in zip(step.tasks, step.finishing):
                if fin and self._slot_gen[t.slot] == step.gen[t.slot]:
                    # prompt complete: mirror the resident prefix (the
                    # fused analogue of insert's mirror). A prefix-hit row
                    # already aliases the entry's pool pages, so only the
                    # suffix is mirrored — unless an eviction compacted
                    # the global cache, which forces the full re-sync.
                    entry = self._slot_prefix[t.slot]
                    sc = extract_slot_caches(step.after, t.slot)
                    if entry is not None and not self._slot_evicted[t.slot]:
                        self._mirror_prefill_suffix(t.slot, sc, entry)
                    else:
                        self._mirror_prefill(t.slot, sc)
            if rows:
                self._mirror_decode(step.before, step.after, rows=rows,
                                    evicted_rows=trig > 0)
        out: Dict[int, int] = {}
        for t, fin in zip(step.tasks, step.finishing):
            if fin and self._slot_gen[t.slot] == step.gen[t.slot]:
                tok = int(nxt[t.slot])
                self.last_token[t.slot] = tok
                out[t.slot] = tok
        for s in rows:
            tok = int(nxt[s])
            self.last_token[s] = tok
            out[s] = tok
        return out

    # ------------------------------------------------------------------
    # collect: the host sync point of the two-phase dispatch contract
    # ------------------------------------------------------------------
    @hot_path
    def collect(self, step: FusedStep) -> Dict[int, int]:
        """Synchronize one in-flight fused step: pull its sampled tokens
        to host, fold eviction/admission/selection stats, and apply the
        cache delta to the paged mirror. Returns {slot: token} for every
        slot still owned by the request the step was dispatched for — a
        slot freed (or freed + re-inserted) while the step was in flight
        is skipped, so a cancelled request can never leak its token into
        a successor and the mirror never resurrects freed pool streams.
        (The unfused ``dispatch_decode`` step kind served its
        deprecation cycle and is gone; every step is a
        :class:`FusedStep` now.)"""
        return self._collect_fused(step)

    def _decode_admission(self, st, live_rows: List[int]) -> float:
        """Mean write-gate admission over live rows for one decode step."""
        adm_rows = np.asarray(st["mean_admission"])
        return float(adm_rows[live_rows].mean())

    def free_slot(self, slot: int) -> None:
        """Retire a slot: stop decoding it and reclaim its pool pages.
        Safe to call with steps in flight: the generation bump makes
        :meth:`collect` discard the dead row's token and skip its mirror
        delta, so the pages freed here stay freed."""
        self.live[slot] = False
        self._resident[slot] = False
        self._slot_gen[slot] += 1
        # a retired row keeps decoding (masked) in the batched step; zero
        # its token so the dead row never replays its final token
        self.last_token[slot] = 0
        self._tok_dev = self._tok_dev.at[slot].set(0)
        self._kv_rows[slot] = 0.0
        self._slot_prefix[slot] = None
        self._slot_evicted[slot] = False
        if self.mirror and self.caches is not None:
            for lkey, _ in self._iter_dual(self.caches):
                for h in range(self.cfg.n_kv_heads):
                    # pages shared with a prefix-store entry are only
                    # dereferenced here; the entry's own refs keep them
                    self.pool.free_stream((slot, lkey, h, "global"))
                    self.pool.free_stream((slot, lkey, h, "local"))

    # ------------------------------------------------------------------
    # content-addressed prefix store hooks (serving/prefix_cache.py)
    # ------------------------------------------------------------------
    @hot_path
    def _adopt_prefix(self, slot: int, entry) -> None:
        """Host-side adoption of a cached prefix into a freshly spliced
        row: alias the entry's pool pages into the slot's streams (incref
        only — copy-on-write unshares any page either side later writes,
        so a hit can never alias mutable decode state) and seed the host
        kv accounting. Runs inside the fused dispatch, so it is hot-path
        code: pure host bookkeeping, never a device sync."""
        self._slot_prefix[slot] = entry
        self._slot_evicted[slot] = False
        self._kv_rows[slot] = float(entry.kv_tokens)
        if self.mirror:
            for skey in entry.stream_keys:
                # ("pfx", key, lkey, h, region) -> (slot, lkey, h, region)
                dst = (slot,) + skey[2:]
                self.pool.free_stream(dst)
                self.pool.share_stream(skey, dst)

    def hold_prefix_row(self, step: FusedStep, slot: int) -> None:
        """Snapshot row ``slot`` of a just-dispatched step's output tree
        as a batch-1 tree for :meth:`capture_prefix` at collect. An
        enqueued device slice, no sync; it is taken before the next
        dispatch can donate (and so delete) ``step.after``."""
        step.rows[slot] = extract_slot_caches(step.after, slot)

    def capture_prefix(self, step: FusedStep, slot: int, key: str, *,
                       adm_weighted: float = 0.0):
        """Freeze row ``slot`` of a collected step into a shareable
        :class:`~repro.serving.prefix_cache.CachedPrefix`: the batch-1
        device tree (its own buffers, held at dispatch — later dispatches
        update the batched tree in place and cannot disturb it), the
        per-layer host counters the suffix mirror needs, and — when
        mirroring — entry-owned pool streams holding the post-admission
        bytes, ready to be aliased into a hitting slot with zero copies.

        A sanctioned sync point (:data:`SyncSentinel.SANCTIONED`): the
        per-layer pulls run once per unique prefix, off the dispatch
        window, exactly like insert's mirror."""
        from repro.serving.prefix_cache import CachedPrefix
        caches = step.rows.pop(slot)
        if self.mirror:
            # the entry's streams hold at most one row's pages
            self._pool_entries.add(key)
            self.pool.reserve((self.slots + len(self._pool_entries))
                              * self._row_pages + 1)
        meta: Dict[Tuple, Dict] = {}
        stream_keys: List[Tuple] = []
        kv_tokens = 0
        n_tokens = 0
        pool_pages = 0
        for lkey, dc in self._iter_dual(caches):
            hdc = jax.device_get(dc)          # batch-1: one pull per layer
            n_tokens = int(hdc.t[0])
            n_local = min(n_tokens, dc.w_local)
            gcnt = np.asarray(hdc.gcnt[0], np.int64)        # [H]
            meta[lkey] = {"gcnt": gcnt, "n_local": n_local}
            kv_tokens += int(gcnt.sum()) + n_local * gcnt.shape[0]
            if not self.mirror:
                continue
            for h in range(self.cfg.n_kv_heads):
                gkey = ("pfx", key, lkey, h, "global")
                self.pool.free_stream(gkey)
                cnt = int(gcnt[h])
                self.pool.bulk_append(
                    gkey, np.asarray(hdc.gk[0, h, :cnt], np.float32),
                    np.asarray(hdc.gv[0, h, :cnt], np.float32))
                lkey_ = ("pfx", key, lkey, h, "local")
                self.pool.free_stream(lkey_)
                self.pool.bulk_append(
                    lkey_, np.asarray(hdc.lk[0, h, :n_local], np.float32),
                    np.asarray(hdc.lv[0, h, :n_local], np.float32))
                stream_keys += [gkey, lkey_]
                pool_pages += len(self.pool.table(gkey).pages)
                pool_pages += len(self.pool.table(lkey_).pages)
        n_bytes = cache_tree_bytes(caches) + \
            pool_pages * paged.PAGE_SIZE * self.cfg.head_dim * 2 * 4
        return CachedPrefix(key=key, n_tokens=n_tokens, caches=caches,
                            adm_weighted=adm_weighted, meta=meta,
                            kv_tokens=kv_tokens, n_bytes=n_bytes,
                            stream_keys=tuple(stream_keys))

    def release_prefix(self, entry) -> None:
        """Free an evicted store entry's pool streams. Pages a live slot
        still shares survive via their per-page refcounts."""
        if self.mirror:
            self._pool_entries.discard(entry.key)
            for skey in entry.stream_keys:
                self.pool.free_stream(skey)

    def _mirror_prefill_suffix(self, slot: int, caches, entry) -> None:
        """Mirror only the tokens a prefix-hit row appended past the
        cached boundary: global entries grown beyond the entry's per-head
        counts are appended, and only the ring slots positions
        ``[n_tokens, t)`` touched are written (copy-on-write unshares any
        page the entry still references). The full :meth:`_mirror_prefill`
        re-sync handles the eviction fallback upstream."""
        t0 = entry.n_tokens
        for lkey, dc in self._iter_dual(caches):
            hdc = jax.device_get(dc)
            t1 = int(hdc.t[0])
            w = dc.w_local
            len0, len1 = min(t0, w), min(t1, w)
            touched = (set(range(len1)) if t1 - t0 >= w
                       else {p % w for p in range(t0, t1)})
            grow = list(range(len0, len1))
            over = sorted(touched.difference(grow))
            gcnt0 = entry.meta[lkey]["gcnt"]
            for h in range(self.cfg.n_kv_heads):
                c0, c1 = int(gcnt0[h]), int(hdc.gcnt[0, h])
                assert c1 >= c0, \
                    "global cache shrank without an eviction trigger"
                if c1 > c0:
                    self.pool.bulk_append(
                        (slot, lkey, h, "global"),
                        np.asarray(hdc.gk[0, h, c0:c1], np.float32),
                        np.asarray(hdc.gv[0, h, c0:c1], np.float32))
                lkey_ = (slot, lkey, h, "local")
                for i in grow:
                    self.pool.append(
                        lkey_, np.asarray(hdc.lk[0, h, i], np.float32),
                        np.asarray(hdc.lv[0, h, i], np.float32))
                for i in over:
                    self.pool.overwrite(
                        lkey_, i, np.asarray(hdc.lk[0, h, i], np.float32),
                        np.asarray(hdc.lv[0, h, i], np.float32))

    # ------------------------------------------------------------------
    # paged-pool mirroring
    # ------------------------------------------------------------------
    def _mirror_prefill(self, slot: int, caches) -> None:
        """Copy the logical dual caches into the physical paged pool.

        Ring pages are allocated lazily: before the ring wraps only slots
        ``0..t-1`` hold tokens (slot = pos % W), so a short prompt mirrors
        ``min(t, W)`` tokens instead of the full ring — `_mirror_decode`
        grows the stream page-by-page until the wrap. The batch-1 prefix
        is pulled to host in one transfer per layer (under a mesh,
        per-head slicing would issue a cross-shard gather per vector)."""
        for lkey, dc in self._iter_dual(caches):
            hdc = jax.device_get(dc)          # batch-1: one pull per leaf
            n_local = min(int(hdc.t[0]), dc.w_local)
            for h in range(self.cfg.n_kv_heads):
                gkey = (slot, lkey, h, "global")
                self.pool.free_stream(gkey)
                cnt = int(hdc.gcnt[0, h])
                self.pool.bulk_append(
                    gkey, np.asarray(hdc.gk[0, h, :cnt], np.float32),
                    np.asarray(hdc.gv[0, h, :cnt], np.float32))
                lkey_ = (slot, lkey, h, "local")
                self.pool.free_stream(lkey_)
                self.pool.bulk_append(
                    lkey_, np.asarray(hdc.lk[0, h, :n_local], np.float32),
                    np.asarray(hdc.lv[0, h, :n_local], np.float32))

    def _iter_dual(self, caches) -> List[Tuple[Tuple, DualCache]]:
        """Yield (layer-key, DualCache[batch=...]) pairs from a cache tree."""
        out = []
        blocks = caches["blocks"]
        for i, bt in enumerate(self.cfg.block_pattern):
            node = blocks[f"b{i}"]
            if isinstance(node, dict) and "self" in node:
                node = node["self"]
            if isinstance(node, DualCache):
                for r in range(node.gk.shape[0] if node.gk.ndim == 5 else 1):
                    if node.gk.ndim == 5:  # stacked [n_repeats, B, ...]
                        out.append(((r, i), jax.tree.map(lambda x: x[r], node)))
                    else:
                        out.append(((0, i), node))
        return out

    def _mirror_decode(self, before, after, *,
                       rows: Optional[List[int]] = None,
                       evicted_rows: Optional[np.ndarray] = None) -> None:
        """Apply one decode step's logical cache delta to the pool.

        ``rows`` limits the mirror to those slot rows (collect passes the
        rows still owned by the request the step was dispatched for —
        mirroring a freed or re-inserted row would resurrect freed pool
        streams or corrupt the successor's); None mirrors all live rows.

        ``evicted_rows`` ([slots] bool) marks rows whose jitted decode
        reported a SnapKV eviction trigger: eviction compacts and reorders
        the logical global cache, so that row's shrunken/unchanged streams
        are re-synced NOW — freed physical pages return to the allocator
        at eviction time instead of lingering until the slot's next
        insert. A stream that *grew* (ca > cb) cannot have evicted this
        step, so the cheap append path still applies to it.

        Device -> host traffic is bounded per layer regardless of
        slots/heads: only the requested slot rows are gathered, and only
        the vectors the step can have written (the ring slot at each
        row's pre-step pointer, the newest global entry per head, and —
        only on an eviction trigger — that row's compacted global
        streams). Under a mesh the batched tree is spread over devices,
        so per-vector slicing would otherwise issue a cross-shard
        transfer each."""
        if rows is None:
            rows = [s for s in range(self.slots) if self.live[s]]
        if not rows:
            return
        ridx = jnp.asarray(rows, jnp.int32)
        ev_rows = [s for s in rows
                   if evicted_rows is not None and bool(evicted_rows[s])]
        for (lkey, dcb), (_, dca) in zip(self._iter_dual(before),
                                         self._iter_dual(after)):
            gcb, ptrb, gca = jax.device_get((
                jnp.take(dcb.gcnt, ridx, 0), jnp.take(dcb.ptr, ridx, 0),
                jnp.take(dca.gcnt, ridx, 0)))
            # one fused gather each for the ring vector every live row
            # wrote this step and the newest global entry per (row, head):
            # [R, Hkv, hd] straight from the batched buffers, no
            # full-capacity [R, Hkv, C, hd] intermediate copies
            r2 = ridx[:, None]
            h2 = jnp.arange(dca.lk.shape[1])[None, :]
            p2 = jnp.asarray(ptrb, jnp.int32)[:, None]
            g2 = jnp.maximum(jnp.asarray(gca, jnp.int32) - 1, 0)
            ring_k, ring_v, prom_k, prom_v = jax.device_get((
                dca.lk[r2, h2, p2], dca.lv[r2, h2, p2],
                dca.gk[r2, h2, g2], dca.gv[r2, h2, g2]))
            full = None
            if ev_rows:
                eidx = jnp.asarray(ev_rows, jnp.int32)
                full = jax.device_get((jnp.take(dca.gk, eidx, 0),
                                       jnp.take(dca.gv, eidx, 0)))
            ev_pos = {s: i for i, s in enumerate(ev_rows)}
            for j, slot in enumerate(rows):
                k = ev_pos.get(slot)
                for h in range(self.cfg.n_kv_heads):
                    cb, ca = int(gcb[j, h]), int(gca[j, h])
                    gkey = (slot, lkey, h, "global")
                    if k is not None and ca <= cb:
                        # post-eviction re-sync (reclaims freed pages)
                        self.pool.free_stream(gkey)
                        self.pool.bulk_append(
                            gkey, np.asarray(full[0][k, h, :ca], np.float32),
                            np.asarray(full[1][k, h, :ca], np.float32))
                    elif ca > cb:
                        # promotion: gcnt increased -> append promoted token
                        self.pool.append(
                            gkey, np.asarray(prom_k[j, h], np.float32),
                            np.asarray(prom_v[j, h], np.float32))
                    # ring write at ptr_before: grows the stream until the
                    # ring wraps (lazy page allocation), overwrites after
                    p = int(ptrb[j])
                    lkey_ = (slot, lkey, h, "local")
                    kvec = np.asarray(ring_k[j, h], np.float32)
                    vvec = np.asarray(ring_v[j, h], np.float32)
                    if p == self.pool.table(lkey_).length:
                        self.pool.append(lkey_, kvec, vvec)
                    else:
                        self.pool.overwrite(lkey_, p, kvec, vvec)

    # ------------------------------------------------------------------
    # legacy fixed-slot loop (thin layer over prefill/insert/dispatch)
    # ------------------------------------------------------------------
    def add_request(self, prompt: List[int], max_new: int = 32) -> int:
        rid = self._next_rid
        self._next_rid += 1
        self.requests[rid] = Request(rid, list(prompt), max_new)
        return rid

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_rid) if r is None]

    def _retire_if_done(self, req: Request, slot: int, tok: int) -> None:
        if len(req.out) >= req.max_new or (self.eos is not None
                                           and tok == self.eos):
            req.done = True
            self.slot_rid[slot] = None
            self.free_slot(slot)

    def step(self) -> Dict[int, int]:
        """Admit pending requests, run one decode step, return {rid:
        newest token}. A request admitted THIS step emits both its
        prefill first token and a decode token; the dict keeps only the
        newest, ``requests[rid].out`` holds the full record."""
        pending = [r for r in self.requests.values()
                   if not r.done and r.rid not in self.slot_rid]
        emitted: Dict[int, int] = {}
        for slot in self._free_slots():
            if not pending:
                break
            req = pending.pop(0)
            self.slot_rid[slot] = req.rid
            # the first generated token comes straight from the prefill's
            # last-position logits; insert feeds it to the batched decode
            prefix = self.prefill(req.prompt, emit_first=True)
            self.insert(prefix, slot)
            req.out.append(prefix.first_token)
            emitted[req.rid] = prefix.first_token
            self._retire_if_done(req, slot, prefix.first_token)
        inflight = self.step_batch([])
        emitted_slots = self.collect(inflight) if inflight is not None else {}
        for slot, tok in emitted_slots.items():
            rid = self.slot_rid[slot]
            if rid is None:
                continue
            req = self.requests[rid]
            req.out.append(tok)
            emitted[rid] = tok
            self._retire_if_done(req, slot, tok)
        return emitted

    def run(self, max_steps: int = 256) -> None:
        for _ in range(max_steps):
            self.step()
            if all(r.done for r in self.requests.values()):
                break

    # ------------------------------------------------------------------
    # largest paged-vs-logical deviation verify_paged accepts: the pool is
    # float32, so it holds the logical cache's values exactly and only the
    # kernel's attention arithmetic separates the two (full float32 when
    # interpreted; bfloat16 passes at the TPU's default matmul precision)
    PAGED_ATOL = 2e-3

    def verify_paged(self, layer_repeat: int = 0, block: int = 0) -> float:
        """Recompute one layer's decode attention for all live slots from
        the PHYSICAL pool via the paged_decode kernel and compare with the
        logical dual-cache contents. Returns max abs deviation (healthy:
        below :attr:`PAGED_ATOL`). The kernel runs compiled on the TPU and
        interpreted on the CPU (:func:`repro.kernels.ops.interpret_mode`)."""
        from repro.kernels.ops import interpret_mode
        from repro.kernels.paged_decode import paged_decode

        assert self.mirror and self.caches is not None
        live = [s for s in range(self.slots) if self.live[s]]
        if not live:
            return 0.0
        node = self.caches["blocks"][f"b{block}"]
        if isinstance(node, dict):
            node = node["self"]
        # one host pull of the layer (every slot row, this repeat only)
        dc: DualCache = jax.device_get(
            jax.tree.map(lambda x: x[layer_repeat], node))
        worst = 0.0
        for slot in live:
            n_local = min(int(dc.t[slot]), dc.w_local)
            for h in range(self.cfg.n_kv_heads):
                gk, gv = self.pool.gather((slot, (layer_repeat, block), h, "global"))
                cnt = int(dc.gcnt[slot, h])
                logical = np.asarray(dc.gk[slot, h, :cnt], np.float32)
                if cnt:
                    worst = max(worst, float(np.abs(gk[:cnt] - logical).max()))
                lk, _ = self.pool.gather((slot, (layer_repeat, block), h, "local"))
                # ring pages are allocated lazily: the stream holds exactly
                # the min(t, W) slots written so far
                assert lk.shape[0] == n_local, (lk.shape, n_local)
                if n_local:
                    worst = max(worst, float(np.abs(
                        lk - np.asarray(dc.lk[slot, h, :n_local],
                                        np.float32)).max()))
        # kernel-level check: paged attention over global streams
        keys = [(s, (layer_repeat, block), h, "global")
                for s in live for h in range(self.cfg.n_kv_heads)]
        kp, vp, tbl, lens = self.pool.kernel_args(keys)
        if int(lens.max()) > 0:
            hd = self.cfg.head_dim
            q = jnp.ones((len(keys), hd), jnp.float32) / hd
            out = np.asarray(paged_decode(q, kp, vp, tbl, lens,
                                          interpret=interpret_mode()))
            # oracle from logical cache
            i = 0
            for s in live:
                for h in range(self.cfg.n_kv_heads):
                    cnt = int(dc.gcnt[s, h])
                    if cnt:
                        kk = np.asarray(dc.gk[s, h, :cnt], np.float32)
                        vv = np.asarray(dc.gv[s, h, :cnt], np.float32)
                        lg = (np.ones(hd) / hd) @ kk.T / np.sqrt(hd)
                        w = np.exp(lg - lg.max())
                        w /= w.sum()
                        oracle = w @ vv
                        worst = max(worst, float(
                            np.abs(out[i] - oracle).max()))
                    i += 1
        return worst
