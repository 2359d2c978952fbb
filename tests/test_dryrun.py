"""Dry-run machinery: HLO collective parsing + small-mesh lowering (in a
subprocess so host-device-count flags never pollute this process)."""
import json
import os
import subprocess
import sys

import pytest

from repro.roofline.hlo_parse import parse_collectives, shape_bytes

HLO_SAMPLE = """
ENTRY %main {
  %p0 = f32[512]{0} parameter(0)
  %ar = f32[512]{0} all-reduce(f32[512]{0} %p0), replica_groups=[4,16]<=[64]
  %ag = bf16[16,1024]{1,0} all-gather(bf16[2,1024]{1,0} %x), replica_groups=[8,8]<=[64]
  %rs = f32[64]{0} reduce-scatter(f32[512]{0} %y), replica_groups=[8,8]<=[64]
  %cp.1 = bf16[2,64]{1,0} collective-permute(bf16[2,64]{1,0} %z)
  %a2a = (f32[8]{0}, f32[8]{0}) all-to-all(f32[8]{0} %u, f32[8]{0} %w), replica_groups=[16,4]<=[64]
}
"""


def test_shape_bytes():
    assert shape_bytes("f32[512]") == 2048
    assert shape_bytes("bf16[2,1024]") == 4096
    assert shape_bytes("pred[8]") == 8


def test_parse_collectives_kinds():
    total, detail = parse_collectives(HLO_SAMPLE, 64)
    assert set(detail) == {"all-reduce", "all-gather", "reduce-scatter",
                           "collective-permute", "all-to-all"}
    # all-reduce: 2 * 2048 * 15/16
    assert detail["all-reduce"]["bytes"] == pytest.approx(2 * 2048 * 15 / 16)
    # all-gather: out 16*1024*2 bytes * 7/8
    assert detail["all-gather"]["bytes"] == pytest.approx(32768 * 7 / 8)
    # reduce-scatter: out 256 bytes * (8-1)
    assert detail["reduce-scatter"]["bytes"] == pytest.approx(256 * 7)
    # permute: out bytes
    assert detail["collective-permute"]["bytes"] == pytest.approx(256)
    # all-to-all tuple: 2 * 32 bytes * 3/4
    assert detail["all-to-all"]["bytes"] == pytest.approx(64 * 3 / 4)
    assert total == pytest.approx(sum(d["bytes"] for d in detail.values()))


def test_parse_ignores_async_done():
    txt = """
  %ag-s = bf16[4,8]{1,0} all-gather-start(bf16[1,8]{1,0} %x), replica_groups=[2,4]<=[8]
  %ag-d = bf16[4,8]{1,0} all-gather-done(bf16[4,8]{1,0} %ag-s)
"""
    total, detail = parse_collectives(txt, 8)
    assert detail["all-gather"]["count"] == 1


SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, jax
from repro.configs import get_config, get_shape
from repro.launch.mesh import make_debug_mesh
from repro.launch.steps import make_bundle
mesh = make_debug_mesh((2, 2))
out = {}
for arch, shp, wg in [("qwen3-0.6b", "train_4k", True),
                      ("qwen3-0.6b", "decode_32k", True),
                      ("xlstm-350m", "long_500k", False),
                      ("recurrentgemma-9b", "decode_32k", True)]:
    cfg = get_config(arch)
    bundle = make_bundle(cfg, get_shape(shp), mesh, use_wgkv=wg)
    with mesh:
        compiled = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                           donate_argnums=bundle.donate_argnums
                           ).lower(*bundle.args).compile()
    out[f"{arch}/{shp}"] = compiled.memory_analysis().peak_memory_in_bytes
print(json.dumps(out))
"""


@pytest.mark.slow
def test_small_mesh_lowering_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SUBPROC], capture_output=True,
                       text=True, env=env, timeout=1200)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert len(out) == 4
    assert all(v > 0 for v in out.values())
