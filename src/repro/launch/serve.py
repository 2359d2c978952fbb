"""Serving driver: the ServeSession client API over any registered
engine backend — WG-KV dual cache (default), dense full-KV, or a static
admission baseline — with chunked prefill, dispatch-ahead decode
(two-phase dispatch/collect), per-request token streaming, mid-stream
cancellation, deadlines, and admission-aware telemetry (plus optional
Quest / SnapKV composition).

    PYTHONPATH=src python -m repro.launch.serve \
        --arch qwen3-0.6b --reduced --requests 8 --max-new 16 --quest-pages 4
    PYTHONPATH=src python -m repro.launch.serve \
        --arch qwen3-0.6b --reduced --backend dense --requests 4
    PYTHONPATH=src python -m repro.launch.serve \
        --arch qwen3-0.6b --reduced --dispatch-ahead 0     # sync baseline
    PYTHONPATH=src python -m repro.launch.serve \
        --arch qwen3-0.6b --reduced --selection quest:4  # top-K decode
    PYTHONPATH=src python -m repro.launch.serve \
        --arch qwen3-0.6b --reduced --trace-out trace.json \
        --metrics-interval 5                               # observability
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --reduced --mesh 1x4
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b  # TPU

``--reduced`` is the CPU recipe: a cut-down config in float32 with the
Pallas kernels interpreted. Without it the published config serves in its
own dtype (bfloat16 for qwen3-0.6b) with compiled kernels. JAX's
persistent compilation cache goes to ``JAX_COMPILATION_CACHE_DIR`` when
set, else to ``<repo>/.jax_cache`` (repro.launch.compile_cache).
"""
from __future__ import annotations

import argparse
import sys
import warnings

import jax

from repro.configs import ARCH_NAMES, get_config, get_reduced_config
from repro.core import admission as A
from repro.launch.compile_cache import enable_compile_cache
from repro.models import inference as I
from repro.models import transformer as T
from repro.serving.backend import BACKEND_NAMES, make_backend
from repro.serving.obs import Tracer, write_chrome_trace
from repro.serving.orchestrator import (QueueFull, SchedulerConfig,
                                        ServeSession)
from repro.serving.sharded import build_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_NAMES)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--backend", default="wgkv", choices=BACKEND_NAMES,
                    help="serving engine backend (protocol implementation)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--capacity", type=int, default=512)
    ap.add_argument("--chunk-tokens", type=int, default=64,
                    help="prefill chunk per scheduler tick (w_local-aligned)")
    ap.add_argument("--max-prefill-batch", type=int, default=None,
                    help="cap on prefill tasks advanced per tick in the one "
                         "batched ragged device call (default: all in-flight "
                         "prefills, bounded by --slots)")
    ap.add_argument("--selection", default=None, metavar="quest:K",
                    help="decode-time page selection: on decode-only fused "
                         "ticks, attend over only the top-K global pages "
                         "per (row, kv head), scored query-aware from "
                         "incremental per-page key min/max metadata "
                         "(dual-cache backends only)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable the content-addressed prefix store: "
                         "requests sharing a chunk-aligned prompt prefix "
                         "splice the cached post-admission KV instead of "
                         "re-prefilling it (multi-turn / shared-context "
                         "TTFT win)")
    ap.add_argument("--prefix-cache-mb", type=int, default=256,
                    help="prefix store LRU byte budget in MiB")
    ap.add_argument("--dispatch-ahead", type=int, default=1,
                    help="decode steps kept in flight on the device "
                         "(0 = synchronous one-step-per-tick baseline)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request latency deadline; overdue requests "
                         "are cancelled mid-stream")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="queue backpressure bound (default unbounded)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="run decode/extend SPMD over a data x model mesh, "
                         "e.g. 1x4 (debug recipe: XLA_FLAGS="
                         "--xla_force_host_platform_device_count=4)")
    ap.add_argument("--quest-pages", type=int, default=None)
    ap.add_argument("--evict-budget", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quiet-stream", action="store_true",
                    help="suppress per-token stream prints")
    # observability (repro.serving.obs): lifecycle + tick-phase tracing
    ap.add_argument("--trace-out", default=None, metavar="TRACE.json",
                    help="record request-lifecycle and tick-phase spans and "
                         "write a Chrome-trace/Perfetto JSON on exit "
                         "(open in chrome://tracing or ui.perfetto.dev)")
    ap.add_argument("--trace-capacity", type=int, default=1 << 16,
                    help="tracer ring-buffer span capacity (oldest dropped)")
    ap.add_argument("--device-annotations", action="store_true",
                    help="also wrap traced phases in jax.profiler."
                         "TraceAnnotation so device profiles show them")
    ap.add_argument("--metrics-interval", type=float, default=None,
                    metavar="SECONDS",
                    help="print a live rolling metrics line (windowed tok/s "
                         "+ latency percentiles + memory gauges) at most "
                         "every SECONDS while serving")
    args = ap.parse_args()
    if args.max_pending is not None and args.max_pending < 1:
        ap.error("--max-pending must be >= 1")
    if args.chunk_tokens < 1:
        ap.error("--chunk-tokens must be >= 1")
    if args.dispatch_ahead < 0:
        ap.error("--dispatch-ahead must be >= 0")
    if args.max_prefill_batch is not None and args.max_prefill_batch < 1:
        ap.error("--max-prefill-batch must be >= 1")
    if args.trace_capacity < 1:
        ap.error("--trace-capacity must be >= 1")
    if args.metrics_interval is not None and args.metrics_interval <= 0:
        ap.error("--metrics-interval must be > 0")
    if args.prefix_cache_mb < 1:
        ap.error("--prefix-cache-mb must be >= 1")
    enable_compile_cache()
    if args.reduced:
        cfg = get_reduced_config(args.arch).replace(dtype="float32")
    else:
        cfg = get_config(args.arch)
    if not cfg.has_attention_cache:
        raise SystemExit(f"{args.arch} has no KV cache; engine serves "
                         "attention archs (SSM decode via examples/)")
    if cfg.is_encdec:
        raise SystemExit("enc-dec serving requires audio frontends; see "
                         "examples/ for whisper decode")
    params = T.init_model(jax.random.PRNGKey(args.seed), cfg)
    if args.backend == "wgkv" and cfg.wgkv.enabled:
        # PR 8 knife-edge tau guard, now at startup: probe the gate-score
        # cluster with one short forward and surface check_tau_margin's
        # RuntimeWarning as a one-line stderr notice — a tau inside the
        # cluster flips admissions between numerically-equivalent prefill
        # paths, which shows up later as baffling parity failures.
        ptoks = jax.random.randint(jax.random.PRNGKey(args.seed + 99),
                                   (1, min(args.prompt_len, 32)), 0,
                                   cfg.vocab_size - 8)
        g = T.forward(params, cfg, ptoks, mode="gated",
                      with_logits=False).gates
        if g is not None:
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always", RuntimeWarning)
                margin = A.check_tau_margin(g, cfg.wgkv.tau)
            if any(issubclass(w.category, RuntimeWarning) for w in caught):
                print(f"WARNING: knife-edge admission tau={cfg.wgkv.tau}: "
                      f"min |g - tau| = {margin:.2e} over a "
                      f"{ptoks.shape[1]}-token probe; admission may flip "
                      "between numerically-equivalent prefill paths",
                      file=sys.stderr)
    opts = I.DecodeOptions(quest_pages=args.quest_pages,
                           evict_hard_budget=args.evict_budget)
    mesh = build_mesh(args.mesh)
    if mesh is not None:
        print(f"mesh: {dict(mesh.shape)} over {mesh.size} devices")
    eng = make_backend(args.backend, params, cfg, slots=args.slots,
                       capacity=args.capacity, opts=opts,
                       temperature=args.temperature, seed=args.seed,
                       selection=args.selection, mesh=mesh)
    print(f"backend: {eng.capabilities()}")
    tracer = None
    if args.trace_out or args.device_annotations:
        tracer = Tracer(capacity=args.trace_capacity,
                        annotate_device=args.device_annotations)
    prefix_cache = None
    if args.prefix_cache:
        from repro.serving.prefix_cache import PrefixCache
        prefix_cache = PrefixCache(quantum=args.chunk_tokens,
                                   budget_bytes=args.prefix_cache_mb << 20,
                                   free_fn=eng.release_prefix)
    session = ServeSession(
        eng,
        sched=SchedulerConfig(chunk_tokens=args.chunk_tokens,
                              dispatch_ahead=args.dispatch_ahead,
                              max_prefill_batch=args.max_prefill_batch),
        max_pending=args.max_pending,
        tracer=tracer,
        metrics_interval_s=args.metrics_interval,
        prefix_cache=prefix_cache)

    def on_token(rid: int, tok: int, is_last: bool) -> None:
        if not args.quiet_stream:
            print(f"  stream rid={rid} tok={tok}" + (" <eor>" if is_last else ""),
                  flush=True)

    def submit_bp(prompt, **kw):
        # backpressure: QueueFull is a typed response, so serve until the
        # queue has room instead of counting hammered retries as shed load
        while True:
            try:
                return session.submit(prompt, **kw)
            except QueueFull as qf:
                if not args.quiet_stream:
                    print(f"  backpressure: depth={qf.depth}/"
                          f"{qf.max_pending}, serving to drain")
                session.tick()

    key = jax.random.PRNGKey(args.seed + 7)
    handles = []
    for _ in range(args.requests):
        key, k = jax.random.split(key)
        prompt = jax.random.randint(k, (args.prompt_len,), 0,
                                    cfg.vocab_size - 8).tolist()
        h = submit_bp(prompt, max_new=args.max_new, on_token=on_token,
                      deadline_s=args.deadline_s)
        print(f"submitted rid={h.rid} prompt_len={len(prompt)}")
        handles.append(h)
    session.run()

    print("\nresults:")
    for h in handles:
        tag = " (cancelled: deadline)" if h.cancelled else ""
        print(f"req {h.rid}: state={h.state}{tag} -> out={h.tokens()}")
    print("\ntelemetry:")
    print(session.report())
    if eng.capabilities().paged:
        # verify_paged needs resident caches, and the pool is already empty
        # after the burst drains — so serve one extra request and check the
        # physical-vs-logical deviation while it is live
        vh = submit_bp([int(t) for t in
                        jax.random.randint(key, (args.prompt_len,), 0,
                                           cfg.vocab_size - 8)],
                       max_new=2)
        for _ in range(10_000):
            if vh.state in ("decode", "done", "cancelled"):
                break
            session.tick()
        session.orchestrator.drain()  # settle the mirror before verifying
        dev = eng.verify_paged() if any(eng.live) else 0.0
        print(f"\npaged-vs-logical max deviation (live request): {dev:.2e} "
              f"(limit {eng.PAGED_ATOL:.0e})")
        session.run()
    session.close()
    if args.trace_out and tracer is not None:
        obj = write_chrome_trace(
            tracer, args.trace_out,
            meta={"arch": args.arch, "backend": args.backend,
                  "requests": args.requests, "slots": args.slots,
                  "dispatch_ahead": args.dispatch_ahead})
        print(f"\ntrace: {args.trace_out} "
              f"({len(obj['traceEvents'])} events, "
              f"{obj['otherData']['spans_dropped']} dropped)")


if __name__ == "__main__":
    main()
