"""Mesh-sharded serving (serving/sharded.py + sharding/rules.py).

Three layers of coverage:

  * spec-level: ``cache_shardings`` on DualCache trees — odd KV-head
    counts (phi3 10 KV heads, smollm 5) must fall back to replication on
    "model" under a (2,4) data x model axis map, ``seq_shard=True`` must put the
    global token axis on "data", and ``param_shardings`` must never split
    ``head_dim`` across "model" (whole-head column parallelism only).
    These need no devices: the rules run on an AbstractMesh.
  * end-to-end parity (subprocess, sets its own XLA_FLAGS): greedy
    tokens from the wgkv and dense backends under a (2,2) host-device
    mesh (a v5e host has four chips) must exactly match the unsharded
    backends on the same arrival trace.
  * in-process mesh tests (skipped unless >= 4 devices; CI provides
    them): sharded capabilities/memory_snapshot surface.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.specs import build_decode_caches
from repro.sharding import rules

pytestmark = pytest.mark.sharded

# axis map of the spec-level tests (an AbstractMesh: no devices needed)
SPEC_MESH_SHAPE = (2, 4)
# device mesh of the parity/in-process tests: four devices, like a v5e host
MESH_SHAPE = (2, 2)
N_DEVICES = MESH_SHAPE[0] * MESH_SHAPE[1]

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < N_DEVICES,
    reason=f"needs >= {N_DEVICES} devices (XLA_FLAGS="
           f"--xla_force_host_platform_device_count={N_DEVICES})")


def spec_mesh():
    """(2,4) data x model axis map for SPEC computation only (rules.py
    reads only axis_names / shape)."""
    return jax.sharding.AbstractMesh(SPEC_MESH_SHAPE, ("data", "model"))


def dual_cache_specs(cfg, *, batch=4, capacity=4096, seq_shard=False):
    """{path: PartitionSpec} for every DualCache gk/gv leaf of a decode
    cache tree (built under eval_shape: full-size configs, no memory)."""
    structs = jax.eval_shape(
        lambda: build_decode_caches(cfg, batch, capacity, use_wgkv=True))
    sh = rules.cache_shardings(structs, spec_mesh(), cfg,
                               seq_shard=seq_shard)
    flat = jax.tree_util.tree_flatten_with_path(sh)[0]
    out = {}
    for path, ns in flat:
        keys = rules._path_keys(path)
        if keys[-1] in ("gk", "gv"):
            out[keys] = ns.spec
    return out


# ==========================================================================
# cache_shardings: odd head counts fall back to replication on "model"
# ==========================================================================
@pytest.mark.parametrize("arch,kv_heads,want_model", [
    ("phi3-medium-14b", 10, None),     # 10 % 4 != 0 -> replicate
    ("smollm-360m", 5, None),          # 5 % 4 != 0 -> replicate
    ("qwen3-0.6b", 8, "model"),        # 8 % 4 == 0 -> shard KV heads
])
def test_dual_cache_head_axis(arch, kv_heads, want_model):
    cfg = get_config(arch)
    assert cfg.n_kv_heads == kv_heads
    specs = dual_cache_specs(cfg)
    assert specs, "no DualCache gk/gv leaves found"
    for keys, spec in specs.items():
        # stacked block leaves: [n_repeats, B, H, C, hd] -> head axis at 2
        assert spec[0] is None, (keys, spec)
        assert spec[2] == want_model, (keys, spec)
        assert spec[4] is None, (keys, spec)    # head_dim never sharded

def test_dual_cache_batch_axis_over_data():
    specs = dual_cache_specs(get_config("qwen3-0.6b"), batch=4)
    for keys, spec in specs.items():
        assert spec[1] == ("data",) or spec[1] == "data", (keys, spec)


def test_seq_shard_puts_global_tokens_on_data():
    """batch=1 long-context decode: the global token axis shards over
    "data" (context parallelism) instead of the (indivisible) batch."""
    cfg = get_config("phi3-medium-14b")
    specs = dual_cache_specs(cfg, batch=1, capacity=4096, seq_shard=True)
    for keys, spec in specs.items():
        assert spec[1] is None, (keys, spec)       # batch=1: not sharded
        assert spec[2] is None, (keys, spec)       # 10 heads: replicated
        assert spec[3] == "data", (keys, spec)     # token axis -> data


def test_param_shardings_never_split_head_dim():
    """w_q/w_k/w_v column parallelism is whole-head only: an arch whose
    KV-head count does not divide "model" must not shard the projection
    out-dim (phi3: 10 KV heads on model=4, though 10*128 divides 4)."""
    cfg = get_config("phi3-medium-14b")
    mesh = spec_mesh()
    hd = cfg.head_dim
    kv_out = cfg.n_kv_heads * hd
    spec = rules._param_spec(("blocks", "b0", "attn", "w_k"),
                             (1, cfg.d_model, kv_out), mesh, cfg)
    assert kv_out % mesh.shape["model"] == 0      # flattened dim DOES divide
    assert spec[2] is None, spec                   # ...but heads do not
    # q heads (40) divide model=4 -> column-parallel stays
    q_spec = rules._param_spec(("blocks", "b0", "attn", "w_q"),
                               (1, cfg.d_model, cfg.n_heads * hd), mesh, cfg)
    assert q_spec[2] == "model", q_spec


# ==========================================================================
# mesh construction from CLI specs
# ==========================================================================
def test_build_mesh_validation():
    from repro.serving.sharded import build_mesh, parse_mesh_shape

    assert build_mesh(None) is None
    assert parse_mesh_shape("2X4") == (2, 4)
    for bad in ("2x", "x4", "0x4", "2x4x2", "axb"):
        with pytest.raises(ValueError):
            parse_mesh_shape(bad)
    if len(jax.devices()) < 64:
        with pytest.raises(RuntimeError, match="devices"):
            build_mesh("8x8")


# ==========================================================================
# end-to-end parity: sharded == unsharded greedy tokens (subprocess owns
# its XLA_FLAGS, so this runs under the plain single-device tier-1 suite)
# ==========================================================================
PARITY_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
jax.config.update("jax_enable_x64", False)
from repro.configs import get_reduced_config
from repro.configs.base import WGKVConfig
from repro.models import transformer as T
from repro.analysis import CompileSentinel, SyncSentinel
from repro.serving.backend import make_backend
from repro.serving.orchestrator import Orchestrator, SchedulerConfig
from repro.serving.sharded import build_mesh

cfg = get_reduced_config("qwen3-0.6b").replace(dtype="float32")
cfg = cfg.replace(wgkv=WGKVConfig(enabled=True, w_local=16, tau=0.1,
                                  gate_hidden=32, global_budget_frac=1.0,
                                  sink=4))
cfg = cfg.replace(sliding_window=min(cfg.sliding_window, 32))
params = T.init_model(jax.random.PRNGKey(0), cfg)
mesh = build_mesh("2x2")
prompts = [list(range(7 + i, 39 + i)) for i in range(3)]

engines = {}  # engines are reusable: jit caches amortize across drivers

def serve(name, m, depth, selection=None):
    key = (name, m is not None, selection)
    if key not in engines:
        engines[key] = make_backend(name, params, cfg, slots=2, capacity=128,
                                    mirror_paged=False, mesh=m,
                                    selection=selection)
    eng = engines[key]
    orch = Orchestrator(eng, sched=SchedulerConfig(
        chunk_tokens=16, dispatch_ahead=depth))
    for p in prompts:
        orch.submit(p, max_new=4)
    # every parity drive runs under both contract sentinels: the shape
    # budget and the no-sync-between-dispatch-and-collect discipline must
    # hold on the mesh exactly as they do unsharded
    with CompileSentinel(eng) as cs, SyncSentinel(eng) as ss:
        orch.run()
        counts = cs.check()
    return {"tokens": [orch.tokens(r) for r in range(len(prompts))],
            "sharded": eng.capabilities().sharded,
            "devices": eng.memory_snapshot().get("mesh_devices"),
            "compiled": counts, "collect_syncs": ss.syncs_in_collect}

out = {}
for name in ("wgkv", "dense"):
    out[name] = {"mesh": serve(name, mesh, 0), "flat": serve(name, None, 0),
                 "mesh_async": serve(name, mesh, 1)}
    if name == "wgkv":   # dense has no page metadata to select against
        # capacity 128 = 8 pages: quest:8 selects every page, so the
        # gathered decode path must stream byte-identical, sharded too
        out[name]["mesh_sel_all"] = serve(name, mesh, 1,
                                          selection="quest:8")
        out[name]["flat_sel_all"] = serve(name, None, 1,
                                          selection="quest:8")

# prefix-cache round on the mesh: the same prompts served twice through a
# shared store — round 2 admits every request off a cached prefix (the
# splice re-enters the memoized sharded insert path, so the cached tree
# lands under the mesh sharding) and must stream the cold bytes
from repro.serving.prefix_cache import PrefixCache
eng = engines[("wgkv", True, None)]
pc = PrefixCache(quantum=16, free_fn=eng.release_prefix)
rounds = []
for _ in range(2):
    orch = Orchestrator(eng, sched=SchedulerConfig(chunk_tokens=16),
                        prefix_cache=pc)
    for p in prompts:
        orch.submit(p, max_new=4)
    orch.run()
    rounds.append([orch.tokens(r) for r in range(len(prompts))])
out["prefix_mesh"] = {"rounds": rounds, "hits": pc.hits,
                      "misses": pc.misses}
print("RESULT" + json.dumps(out))
"""


def _run_subproc(code, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=timeout,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


@pytest.mark.slow
def test_sharded_parity_vs_unsharded():
    out = _run_subproc(PARITY_SUBPROC)
    for name in ("wgkv", "dense"):
        mesh_run, flat_run = out[name]["mesh"], out[name]["flat"]
        assert mesh_run["sharded"] is True
        assert flat_run["sharded"] is False
        assert mesh_run["devices"] == 4.0
        assert flat_run["devices"] is None
        assert mesh_run["tokens"] == flat_run["tokens"], name
        assert all(len(t) == 4 for t in mesh_run["tokens"])
        # sentinel evidence rides back: the fused shape budget held on the
        # mesh (CompileSentinel.check() raised otherwise -> nonzero exit)
        # and collect() accounted at least one sanctioned host pull
        assert mesh_run["compiled"]["fused_step"] <= 2, name
        assert mesh_run["collect_syncs"] > 0, name
        # the async dispatch/collect driver on the mesh streams the same
        # bytes: the on-device sampled-token feed survives SPMD placement
        assert out[name]["mesh_async"]["tokens"] == flat_run["tokens"], name
    # gathered top-K page selection at K = all resident pages streams
    # byte-identical to the full decode path — on the mesh AND unsharded
    # (ascending-sorted top-K at K = P is the identity permutation)
    assert out["wgkv"]["mesh_sel_all"]["tokens"] == \
        out["wgkv"]["mesh"]["tokens"]
    assert out["wgkv"]["flat_sel_all"]["tokens"] == \
        out["wgkv"]["flat"]["tokens"]
    # prefix-cache round on the mesh: round 1 misses and captures, round 2
    # hits for every request — and both rounds stream the cold bytes
    pfx = out["prefix_mesh"]
    assert pfx["misses"] == 3 and pfx["hits"] == 3
    assert pfx["rounds"][0] == out["wgkv"]["mesh"]["tokens"]
    assert pfx["rounds"][1] == out["wgkv"]["mesh"]["tokens"]


# ==========================================================================
# sharded A/B smoke: bench_serving --mesh completes with per-backend
# metrics (needs the cached bench substrate; trains it on first run)
# ==========================================================================
@pytest.mark.slow
def test_bench_serving_smoke_mesh(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"   # tests leave it off
    json_path = str(tmp_path / "BENCH_serving.json")
    env["BENCH_SERVING_JSON"] = json_path
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_serving",
         "--backends", "wgkv,dense", "--smoke", "--mesh", "2x2"],
        capture_output=True, text=True, env=env, timeout=1200,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert r.returncode == 0, r.stderr[-3000:]
    rec = json.load(open(json_path))
    assert rec["trace"]["mesh"] == "2x2"
    for name in ("wgkv", "dense"):
        m = rec["backends"][name]
        assert m["requests"] == 4
        assert m["ttft_p50_s"] is not None and m["ttft_p99_s"] is not None
        assert m["kv_bytes_per_shard_peak"] is not None
        assert m["kv_bytes_per_shard_peak"] <= m["kv_bytes_peak"]
        # async driver metrics ride along (sync baseline + speedup ratio)
        assert m["sync_tokens_per_s"] is not None
        assert m["async_speedup_vs_sync"] > 0
    # the selection A/B rides the mesh smoke too (paged backends only):
    # all-pages parity ran, the timed K sweep carries needle accuracy
    sel = rec["backends"]["wgkv"]["selection"]
    assert sel["parity_k"] == 12
    for v in sel["per_k"].values():
        assert v["needle_accuracy"] is not None
    assert "selection" not in rec["backends"]["dense"]
    assert "ab" in rec and "wgkv" in rec["ab"]


# ==========================================================================
# in-process mesh tests (run under CI's 4 host devices)
# ==========================================================================
@needs_mesh
def test_sharded_memory_snapshot_and_free():
    from conftest import make_cfg
    from repro.launch.mesh import make_debug_mesh
    from repro.models import transformer as T
    from repro.serving.backend import make_backend

    cfg = make_cfg("qwen3-0.6b")
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    eng = make_backend("wgkv", params, cfg, slots=2, capacity=128,
                       mirror_paged=False, mesh=make_debug_mesh(MESH_SHAPE))
    prefix = eng.prefill(list(range(32)))
    eng.insert(prefix, 0)
    snap = eng.memory_snapshot()
    assert snap["mesh_devices"] == float(N_DEVICES)
    assert 0 < snap["kv_bytes_per_shard"] <= snap["kv_bytes"]
    out = eng.collect(eng.step_batch([]))
    assert set(out) == {0}
    eng.free_slot(0)
    assert eng.last_token[0] == 0
