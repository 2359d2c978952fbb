import jax
import pytest

# Tests run on the single host CPU device. (The multi-device dry-run tests
# spawn subprocesses with XLA_FLAGS; never set it here.)
jax.config.update("jax_enable_x64", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests (subprocess dry-runs etc.)")
    config.addinivalue_line(
        "markers", "backends: EngineBackend protocol, backend parity, and "
                   "serving A/B tests (pytest -m backends)")
    config.addinivalue_line(
        "markers", "sharded: mesh-sharded serving tests; in-process variants "
                   "need >= 4 devices (CI runs the suite under XLA_FLAGS="
                   "--xla_force_host_platform_device_count=4), subprocess "
                   "variants set the flag themselves")


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


def small_wgkv(**kw):
    from repro.configs.base import WGKVConfig

    defaults = dict(enabled=True, w_local=16, tau=0.1, gate_hidden=32,
                    global_budget_frac=1.0, sink=4)
    defaults.update(kw)
    return WGKVConfig(**defaults)


def make_cfg(arch: str = "qwen3-0.6b", **wgkv_kw):
    """Reduced fp32 config with a small WG-KV window for fast CPU tests."""
    from repro.configs import get_reduced_config

    cfg = get_reduced_config(arch).replace(dtype="float32")
    if cfg.wgkv.enabled:
        cfg = cfg.replace(wgkv=small_wgkv(**wgkv_kw))
    return cfg.replace(sliding_window=min(cfg.sliding_window, 32))


# the named scopes of the fused serving step (serving/sharded.py and the
# model code under it), each of which the compiled step's op_name
# metadata must hold; the dense cache has no write gate and reads its
# cache inside attention
FUSED_SCOPES = ("embed", "proj", "write_gate", "cache_write", "kv_read",
                "attention", "mlp", "lm_head", "row_keep", "layer_scan",
                "position_scan", "sample", "fused_step")
DENSE_ABSENT_SCOPES = ("write_gate", "kv_read")


def scopes_in(hlo_text: str) -> set:
    """Every name-stack component of the op_name metadata in an HLO text."""
    import re
    return {part for name in re.findall(r'op_name="([^"]*)"', hlo_text)
            for part in name.split("/")}


def expected_scopes(backend: str) -> set:
    absent = DENSE_ABSENT_SCOPES if backend == "dense" else ()
    return set(FUSED_SCOPES) - set(absent)
