#!/usr/bin/env python3
"""Chip smoke test: serve qwen3-0.6b at full width on one TPU chip.

    python chip_smoke.py [--seed 0]      # one chip: wgkv + dense backends
    python chip_smoke.py --chips 4       # 1x4 mesh vs unsharded, 4 chips

One process, random weights from ``--seed``, nothing read from outside
the repository. The one-chip run serves the published qwen3-0.6b config
(28 layers, bfloat16) through ``ServeSession`` on the ``wgkv`` and
``dense`` backends with a prompt longer than the 256-token local window,
so admission, lazy promotion and the paged mirror all run, and checks:

  * every request ends ``done`` with ``max_new`` tokens;
  * the fused step's last-position logits are finite and agree with a
    float32 ``T.forward`` (full attention, highest matmul precision) of
    the same prompt and weights within ``LOGIT_RTOL``. The prompt is no
    longer than the local window, so WG-KV must equal full attention;
  * ``verify_paged`` (the compiled ``paged_decode`` kernel over the host
    pool) stays under ``Engine.PAGED_ATOL``;
  * ``CompileSentinel`` holds the fused step to its shape budget.

``--chips 4`` runs only the sharded path: a 1x4 ("data", "model") mesh
wgkv engine (8 KV heads over model=4) against the unsharded engine on
device 0, on the same prompts, comparing their last-position logits
with each other and with the float32 forward within ``LOGIT_RTOL``, and
reporting where the greedy streams first diverge.

Lines starting with ``info:`` are bring-up output, not a benchmark:
wall seconds, the programs compiled and the seconds spent compiling them,
generated tokens/s and the device's peak memory. The
last line is ``{"ok": true, "device": {...}}``; any failed check exits
non-zero, and the script refuses to run on anything but a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen3-0.6b"
SLOTS = 2
CAPACITY = 512
CHUNK = 64
MAX_NEW = 16
# prompt lengths: one past the 256-token local window (ring wrap, lazy
# promotion, global pages in the pool), the rest inside it
PROMPT_LENS = (300, 64, 200, 128)
# prompt of the logits check: inside the local window, so every token is
# local and WG-KV attention is exactly full attention
REF_PROMPT_LEN = 200
# served bfloat16 logits vs the float32 reference: max |served - ref| over
# the vocabulary, relative to max |ref|. bfloat16 keeps 8 significant bits
# (relative rounding 2**-9); through a 28-layer residual stream that
# compounds to about 3e-2 of the largest logit (a cut-width qwen3 on the
# CPU: 1.5e-2 at 2 layers, 2.1e-2 at 8, 2.7e-2 at 28), and 5e-2 leaves
# room above it. Sharded and unsharded bfloat16 runs differ only in
# reduction order, well inside the same bound.
LOGIT_RTOL = 5e-2


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def info(msg: str) -> None:
    print(f"info: {msg}", flush=True)


class CompileClock:
    """Programs compiled and seconds spent compiling them (reads of the
    persistent cache included), summed from ``jax.monitoring`` events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.programs, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.programs += 1
            self.seconds += secs

    def since(self, mark):
        return (f"{self.programs - mark[0]} programs compiled in "
                f"{self.seconds - mark[1]:.2f} s")

    def mark(self):
        return self.programs, self.seconds


def make_prompts(cfg, seed: int, lens):
    import numpy as np
    rng = np.random.default_rng(seed + 7)
    return [rng.integers(0, cfg.vocab_size - 8, n).tolist() for n in lens]


def init_params(cfg, seed: int):
    import jax
    from repro.models import transformer as T
    return jax.jit(T.init_model, static_argnums=1)(jax.random.PRNGKey(seed),
                                                   cfg)


def reference_logits(params, cfg, prompt):
    """float32 full-attention forward of ``prompt``; last-position logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer as T
    cfg32 = cfg.replace(dtype="float32")
    fwd = jax.jit(lambda p, t: T.forward(p, cfg32, t,
                                         mode="teacher").logits[0, -1])
    with jax.default_matmul_precision("highest"):
        out = fwd(params, jnp.asarray([prompt], jnp.int32))
    return np.asarray(out, np.float32)


def fused_logits(eng, prompt, slot: int = 0):
    """Drive one prompt through the engine's fused step (the tick the
    scheduler dispatches) in ``CHUNK``-token chunks on an idle ``slot``;
    returns (last-position logits [V] float32, first sampled token)."""
    import numpy as np
    task = eng.start_prefill(prompt)
    task.slot = slot
    step, out = None, {}
    while not task.done:
        step = eng.step_batch([task], CHUNK, decode=False)
        out = eng.collect(step)
    logits = np.asarray(step.logits[slot], np.float32)
    eng.free_slot(slot)
    return logits, out[slot]


def logits_deviation(got, ref) -> float:
    import numpy as np
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def serve(eng, prompts, *, max_new: int):
    """Serve ``prompts`` through a ServeSession under the compile-budget
    sentinel; returns (token lists, wall seconds, compiled shape counts)."""
    from repro.analysis import CompileSentinel
    from repro.serving.orchestrator import SchedulerConfig, ServeSession
    session = ServeSession(eng, sched=SchedulerConfig(chunk_tokens=CHUNK))
    with CompileSentinel(eng) as cs:
        t0 = time.perf_counter()
        handles = [session.submit(p, max_new=max_new) for p in prompts]
        session.run()
        wall = time.perf_counter() - t0
        counts = cs.check()
    for h in handles:
        check(h.state == "done", f"request {h.rid} ended {h.state!r}")
        check(len(h.tokens()) == max_new,
              f"request {h.rid}: {len(h.tokens())} tokens, want {max_new}")
    toks = [h.tokens() for h in handles]
    check(all(0 <= t < eng.cfg.vocab_size for ts in toks for t in ts),
          "sampled token outside the vocabulary")
    session.close()
    return toks, wall, counts


def paged_check(eng, prompt) -> float:
    """Serve one long request until it decodes, then recompute attention
    from the paged pool with the paged_decode kernel (verify_paged)."""
    import numpy as np
    from repro.serving.orchestrator import SchedulerConfig, ServeSession
    session = ServeSession(eng, sched=SchedulerConfig(chunk_tokens=CHUNK))
    h = session.submit(prompt, max_new=4)
    for _ in range(10_000):
        if h.state in ("decode", "done", "cancelled"):
            break
        session.tick()
    session.orchestrator.drain()
    check(h.state == "decode" and any(eng.live),
          f"verify request not resident (state {h.state!r})")
    gcnt = np.asarray(eng.caches["blocks"]["b0"].gcnt)
    check(int(gcnt.max()) > 0, "no token promoted to the global cache")
    dev = eng.verify_paged()
    session.run()
    session.close()
    return dev


def checked_logits(name, eng, ref_prompt, ref):
    """Fused-step logits of ``ref_prompt``: finite, greedy == argmax, and
    within ``LOGIT_RTOL`` of the float32 reference ``ref``."""
    import numpy as np
    got, tok = fused_logits(eng, ref_prompt)
    check(bool(np.isfinite(got).all()), f"{name}: non-finite logits")
    check(tok == int(got.argmax()),
          f"{name}: greedy token {tok} != argmax {int(got.argmax())}")
    dev = logits_deviation(got, ref)
    info(f"{name}: last-position logits vs float32 forward: relative max "
         f"deviation {dev:.3e} (limit {LOGIT_RTOL:.0e})")
    check(dev <= LOGIT_RTOL, f"{name}: logits deviation {dev:.3e}")
    return got


def run_backend(name, params, cfg, prompts, ref_prompt, ref, seed: int,
                clock: CompileClock):
    from repro.serving.backend import make_backend
    eng = make_backend(name, params, cfg, slots=SLOTS, capacity=CAPACITY,
                       seed=seed)
    mark = clock.mark()
    toks, wall1, counts = serve(eng, prompts, max_new=MAX_NEW)
    check(counts.get("fused_step", 0) >= 1, "fused step never compiled")
    compiled = clock.since(mark)
    _, wall2, _ = serve(eng, prompts, max_new=MAX_NEW)
    n_tok = len(prompts) * MAX_NEW
    info(f"{name}: first pass {wall1:.2f} s ({compiled}), "
         f"second pass {wall2:.2f} s, {n_tok / wall2:.1f} generated tok/s; "
         f"compiled shapes {counts}")
    checked_logits(name, eng, ref_prompt, ref)
    if eng.capabilities().paged:
        pdev = paged_check(eng, prompts[0])
        info(f"{name}: paged-vs-logical max deviation {pdev:.3e} "
             f"(limit {eng.PAGED_ATOL:.0e})")
        check(pdev < eng.PAGED_ATOL, f"{name}: paged deviation {pdev:.3e}")
    return toks


def setup(cfg, seed: int, clock: CompileClock):
    """Weights, prompts, the local reference prompt and its float32
    logits."""
    import numpy as np
    t0, mark = time.perf_counter(), clock.mark()
    params = init_params(cfg, seed)
    prompts = make_prompts(cfg, seed, PROMPT_LENS)
    ref_prompt = make_prompts(cfg, seed + 1, (REF_PROMPT_LEN,))[0]
    check(REF_PROMPT_LEN <= cfg.wgkv.w_local, "reference prompt not local")
    check(max(PROMPT_LENS) > cfg.wgkv.w_local, "no prompt past w_local")
    ref = reference_logits(params, cfg, ref_prompt)
    check(bool(np.isfinite(ref).all()), "non-finite reference logits")
    info(f"params + float32 reference: {time.perf_counter() - t0:.2f} s "
         f"({clock.since(mark)})")
    return params, prompts, ref_prompt, ref


def one_chip(cfg, seed: int, clock: CompileClock) -> None:
    params, prompts, ref_prompt, ref = setup(cfg, seed, clock)
    for name in ("wgkv", "dense"):
        run_backend(name, params, cfg, prompts, ref_prompt, ref, seed, clock)


def sharded_vs_flat(cfg, seed: int, clock: CompileClock,
                    mesh_spec: str = "1x4") -> None:
    import jax
    from repro.serving.backend import make_backend
    from repro.serving.sharded import build_mesh
    mesh = build_mesh(mesh_spec)
    params, prompts, ref_prompt, ref = setup(cfg, seed, clock)
    check(params["embed"]["tok"].sharding.device_set == {jax.devices()[0]},
          "unsharded params not on device 0")
    engines = {
        "mesh": make_backend("wgkv", params, cfg, slots=SLOTS,
                             capacity=CAPACITY, seed=seed, mesh=mesh,
                             mirror_paged=False),
        "flat": make_backend("wgkv", params, cfg, slots=SLOTS,
                             capacity=CAPACITY, seed=seed,
                             mirror_paged=False),
    }
    toks, logits = {}, {}
    for key, eng in engines.items():
        mark = clock.mark()
        toks[key], wall, counts = serve(eng, prompts, max_new=MAX_NEW)
        info(f"{key}: served in {wall:.2f} s ({clock.since(mark)}); "
             f"compiled shapes {counts}")
        logits[key] = checked_logits(key, eng, ref_prompt, ref)
    # the sharded engine's state must really span the mesh: KV heads split
    # over "model", one shard per device
    eng = engines["mesh"]
    gk = eng.caches["blocks"]["b0"].gk          # [R, B, H, C, hd]
    devs = {s.device for s in gk.addressable_shards}
    check(len(devs) == mesh.size, f"cache on {len(devs)} devices")
    heads = gk.sharding.shard_shape(gk.shape)[2]
    check(heads == cfg.n_kv_heads // mesh.shape["model"],
          f"cache shard holds {heads} KV heads")
    wq = eng.params["blocks"]["b0"]["attn"]["w_q"]
    check(len({s.device for s in wq.addressable_shards}) == mesh.size
          and wq.sharding.shard_shape(wq.shape)[-1] * mesh.shape["model"]
          == wq.shape[-1], "w_q not column-sharded over model")
    info(f"mesh {dict(mesh.shape)}: cache and w_q shards on {len(devs)} "
         f"devices, {heads} KV heads per shard")
    dev = logits_deviation(logits["mesh"], logits["flat"])
    info(f"sharded vs unsharded last-position logits: relative max "
         f"deviation {dev:.3e} (limit {LOGIT_RTOL:.0e})")
    check(dev <= LOGIT_RTOL, f"sharded logits deviation {dev:.3e}")
    for i, (a, b) in enumerate(zip(toks["mesh"], toks["flat"])):
        first = next((j for j, (x, y) in enumerate(zip(a, b)) if x != y),
                     None)
        info(f"request {i}: greedy streams "
             + ("identical" if first is None
                else f"first diverge at token {first}"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the 1x4 sharded-vs-unsharded phase")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform!r}); "
              "refusing to run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    info(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    cfg = get_config(ARCH)
    info(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
         f"{cfg.n_kv_heads} KV heads, {cfg.dtype}; device "
         f"{devices[0].device_kind} x{len(devices)}")
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_vs_flat(cfg, args.seed, clock)
    else:
        one_chip(cfg, args.seed, clock)
    stats = devices[0].memory_stats() or {}
    info(f"total {time.perf_counter() - t0:.2f} s ({clock.since((0, 0.0))}); "
         f"device 0 peak memory "
         f"{stats.get('peak_bytes_in_use', 'not reported')} bytes")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
