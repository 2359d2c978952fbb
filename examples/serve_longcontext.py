"""Serve a long-context batch through the continuous-batching orchestrator:
dual cache + paged physical memory + chunked prefill + token streaming.

    PYTHONPATH=src python examples/serve_longcontext.py

serving
-------
The orchestrator wraps the JetStream-style engine backend
(prefill/insert/dispatch-collect) with a request queue, a batched
chunked-prefill scheduler (every in-flight prefill advances in one
ragged jitted call per tick), per-request token streams, and latency
telemetry::

    from repro.serving.engine import Engine
    from repro.serving.orchestrator import Orchestrator, SchedulerConfig

    eng = Engine(params, cfg, slots=3, capacity=512)
    orch = Orchestrator(eng, sched=SchedulerConfig(chunk_tokens=64),
                        max_pending=32)           # queue backpressure
    rid = orch.submit(prompt, max_new=24,
                      on_token=lambda rid, tok, last: ...)  # streaming
    orch.run()                                    # tick until drained
    orch.tokens(rid)                              # full decoded output
    orch.telemetry.report()                       # TTFT/TPOT/throughput/
                                                  # admission/pool-util
"""
import jax

from repro.configs import get_reduced_config
from repro.configs.base import WGKVConfig
from repro.models import transformer as T
from repro.serving.engine import Engine
from repro.serving.orchestrator import Orchestrator, SchedulerConfig

cfg = get_reduced_config("phi4-mini-3.8b").replace(
    dtype="float32",
    wgkv=WGKVConfig(enabled=True, w_local=32, tau=0.1, gate_hidden=32,
                    global_budget_frac=0.4, sink=4))
params = T.init_model(jax.random.PRNGKey(0), cfg)

eng = Engine(params, cfg, slots=3, capacity=512, temperature=0.0)
orch = Orchestrator(eng, sched=SchedulerConfig(chunk_tokens=64))

key = jax.random.PRNGKey(7)
for i, plen in enumerate((320, 196, 96, 256)):  # ragged prompts
    key, k = jax.random.split(key)
    prompt = jax.random.randint(k, (plen,), 0, cfg.vocab_size - 8).tolist()
    stream_cb = (lambda r, tok, last:
                 print(f"  stream rid={r} tok={tok}"
                       + (" <eor>" if last else ""))) if plen == 96 else None
    rid = orch.submit(prompt, max_new=24, on_token=stream_cb)
    print(f"queued request {rid}: prompt_len={plen}")

step = 0
verified = None
while not orch.queue.all_done() and step < 400:
    orch.tick()
    step += 1
    if step % 8 == 0:
        live = sum(eng.live)
        print(f"tick {step:3d}: live={live} pool_pages={eng.pool.pages_in_use} "
              f"pool_util={eng.pool.utilization():.2f}")
    if verified is None and any(eng.live):
        verified = eng.verify_paged()  # check while caches are resident

print("\nresults:")
for rid, r in orch.queue.requests.items():
    print(f"  req {rid}: generated {len(r.out)} tokens, first 8 = {r.out[:8]}")
print("\ntelemetry:")
print(orch.telemetry.report())
print(f"\npaged-vs-logical verification (live batch): {verified:.2e}")
print(f"pool pages still allocated (should be 0): {eng.pool.pages_in_use}")
