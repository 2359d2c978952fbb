"""AOT compiles for a TPU v5e chip that is described, not attached.

The TPU compiler is installed with jaxlib, so the serving path's kernels
and the full-width qwen3-0.6b fused step compile here for one chip of a
``v5e:2x2`` topology. A compile refused here (a block shape not aligned
to the tiling, more fast memory than a kernel may use, a program that
does not fit the chip) would be refused on the chip too. Nothing runs:
these say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and under pytest-xdist every
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import expected_scopes, scopes_in
from repro.configs import get_config
from repro.kernels.paged_decode import paged_decode, paged_decode_selected
from repro.launch.specs import alloc_batched_caches
from repro.models import transformer as T
from repro.serving.backend import make_backend

SLOTS, CAPACITY, CHUNK = 2, 512, 64
HD, PAGE, POOL_PAGES, STREAMS, MAX_PAGES, K_PAGES = 128, 16, 4096, 16, 40, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _is_mosaic(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_decode_compiles(one_chip, dtype):
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((STREAMS, HD), dtype),
        jax.ShapeDtypeStruct((POOL_PAGES, PAGE, HD), dtype),
        jax.ShapeDtypeStruct((POOL_PAGES, PAGE, HD), dtype),
        jax.ShapeDtypeStruct((STREAMS, MAX_PAGES), jnp.int32),
        jax.ShapeDtypeStruct((STREAMS,), jnp.int32)))
    compiled = jax.jit(
        lambda *a: paged_decode(*a, interpret=False)).lower(*args).compile()
    assert _is_mosaic(compiled)


def test_paged_decode_selected_compiles(one_chip):
    dt = jnp.bfloat16
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((STREAMS, HD), dt),
        jax.ShapeDtypeStruct((POOL_PAGES, PAGE, HD), dt),
        jax.ShapeDtypeStruct((POOL_PAGES, PAGE, HD), dt),
        jax.ShapeDtypeStruct((STREAMS, MAX_PAGES), jnp.int32),
        jax.ShapeDtypeStruct((STREAMS,), jnp.int32),
        jax.ShapeDtypeStruct((STREAMS, K_PAGES), jnp.int32),
        jax.ShapeDtypeStruct((STREAMS,), jnp.int32)))
    compiled = jax.jit(
        lambda *a: paged_decode_selected(*a, interpret=False)
    ).lower(*args).compile()
    assert _is_mosaic(compiled)


@pytest.mark.parametrize("backend", ["wgkv", "dense"])
@pytest.mark.parametrize("s", [CHUNK, 1], ids=["chunk", "decode"])
def test_fused_step_full_width_compiles(one_chip, backend, s):
    """The fused serving tick of the published qwen3-0.6b config (28
    layers, bfloat16) in both of its compiled shapes, [slots, chunk] and
    [slots, 1], fits one 16 GB chip, and its operations keep the named
    scopes a device trace attributes their time by."""
    cfg = get_config("qwen3-0.6b")
    assert cfg.dtype == "bfloat16"
    params = jax.eval_shape(lambda k: T.init_model(k, cfg),
                            jax.random.PRNGKey(0))
    eng = make_backend(backend, params, cfg, slots=SLOTS, capacity=CAPACITY,
                       mirror_paged=False)
    caches = jax.eval_shape(
        lambda: alloc_batched_caches(eng._build_empty_caches(), SLOTS))
    feed = (jax.ShapeDtypeStruct((SLOTS, s), jnp.int32),
            jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
            jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
            jax.ShapeDtypeStruct((SLOTS,), jnp.bool_),
            jax.ShapeDtypeStruct((1, 2), jnp.uint32))
    compiled = eng._fused.lower(*_on(one_chip, (params, feed, caches))
                                ).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < mem.peak_memory_in_bytes and used < 16e9, mem
    want = expected_scopes(backend)
    found = scopes_in(compiled.as_text())
    assert want <= found, want - found


@pytest.mark.parametrize("s", [CHUNK, 1], ids=["chunk", "decode"])
def test_fused_step_write_kernel_compiles(one_chip, monkeypatch, s):
    """On one chip the decode write is the Pallas kernel
    (``kernels/cache_write.py``): the full-width fused step compiles with
    it, every per-slot leaf it writes stays in HBM around the call (XLA
    would otherwise stage the smaller stacked leaves in on-chip memory at
    every layer), and the step still updates the tree in place."""
    import re
    from repro.core import dual_cache as DC
    from repro.launch.specs import cache_tree_bytes
    from repro.models.inference import DecodeOptions
    monkeypatch.setattr(DC, "interpret_mode", lambda: False)
    cfg = get_config("qwen3-0.6b")
    params = jax.eval_shape(lambda k: T.init_model(k, cfg),
                            jax.random.PRNGKey(0))
    eng = make_backend("wgkv", params, cfg, slots=SLOTS, capacity=CAPACITY,
                       mirror_paged=False,
                       opts=DecodeOptions(write_kernel=True))
    caches = jax.eval_shape(
        lambda: alloc_batched_caches(eng._build_empty_caches(), SLOTS))
    feed = (jax.ShapeDtypeStruct((SLOTS, s), jnp.int32),
            jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
            jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
            jax.ShapeDtypeStruct((SLOTS,), jnp.bool_),
            jax.ShapeDtypeStruct((1, 2), jnp.uint32))
    compiled = eng._fused.lower(*_on(one_chip, (params, feed, caches))
                                ).compile()
    text = compiled.as_text()
    assert _is_mosaic(compiled)
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "cache_write/pallas_call" in line]
    assert len(calls) == 1, len(calls)
    outs = re.findall(r"\w+\[[\d,]*\]\{[^}]*\}", calls[0].split(
        " custom-call(")[0])
    # lk lv gk gv pkmin pkmax lg lpos gpos, then the two counters
    assert len(outs) == 11 and not any("S(1)" in o for o in outs[:9]), outs
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        cache_tree_bytes(caches)
