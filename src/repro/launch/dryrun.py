import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x input-shape) on the
production mesh and record memory/cost/collective evidence.

    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch qwen3-moe-235b-a22b --shape train_4k --mesh single

Proves the distribution config is coherent without hardware: sharding
mismatches, compile-time OOM, and unsupported collectives all fail here.
Results append to benchmarks/artifacts/dryrun.json (one record per run).
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax

from repro.configs import ARCH_NAMES, get_config, get_shape, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_bundle
from repro.roofline.hlo_parse import parse_collectives

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "benchmarks", "artifacts")


def run_dryrun(arch: str, shape_name: str, *, multi_pod: bool = False,
               use_wgkv: Optional[bool] = None, scan_unroll: bool = False,
               n_repeats_override: Optional[int] = None,
               collect_hlo: bool = False, mesh=None,
               knob_overrides: Optional[Dict[str, Any]] = None,
               cfg_override=None, lower_only: bool = False) -> Dict[str, Any]:
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    shape = get_shape(shape_name)
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": reason}
    if use_wgkv is None:
        use_wgkv = cfg.wgkv.enabled
    if n_repeats_override is not None:
        over = {"n_repeats": n_repeats_override, "stem_pattern": ()}
        if cfg.is_encdec:
            over["n_enc_repeats"] = n_repeats_override
        cfg = cfg.replace(**over)
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    bundle = make_bundle(cfg, shape, mesh, use_wgkv=use_wgkv,
                         scan_unroll=scan_unroll,
                         knob_overrides=knob_overrides)
    t0 = time.time()
    with mesh:
        jitted = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                         donate_argnums=bundle.donate_argnums)
        lowered = jitted.lower(*bundle.args)
        t_lower = time.time() - t0
        if lower_only:
            # pre-optimization analysis: GLOBAL (unpartitioned) flops/bytes,
            # linear in depth (no fusion/propagation noise) — the roofline
            # FLOP source (roofline/analysis.py)
            cost = lowered.cost_analysis()
            return {
                "arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "devices": n_dev, "wgkv": bool(use_wgkv),
                "kind": shape.kind, "lower_only": True,
                "n_repeats_override": n_repeats_override,
                "knobs": bundle.knobs, "lower_s": round(t_lower, 1),
                "cost_global": {
                    "flops": cost.get("flops"),
                    "bytes_accessed": cost.get("bytes accessed"),
                    "transcendentals": cost.get("transcendentals"),
                },
            }
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll_bytes, coll_detail = parse_collectives(hlo, n_dev)
    rec: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": n_dev,
        "wgkv": bool(use_wgkv),
        "kind": shape.kind,
        "n_repeats_override": n_repeats_override,
        "scan_unroll": scan_unroll,
        "knobs": bundle.knobs,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.peak_memory_in_bytes,
        },
        "cost": {
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
            "transcendentals": cost.get("transcendentals"),
        },
        "collectives": {"per_chip_bytes": coll_bytes, "detail": coll_detail},
    }
    if collect_hlo:
        rec["hlo_text"] = hlo
    return rec


def append_record(rec: Dict[str, Any], path: Optional[str] = None) -> None:
    path = path or os.path.join(ARTIFACTS, "dryrun.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    records = []
    if os.path.exists(path):
        with open(path) as f:
            records = json.load(f)
    key = (rec["arch"], rec["shape"], rec.get("mesh"), rec.get("wgkv"),
           rec.get("n_repeats_override"))
    records = [r for r in records
               if (r["arch"], r["shape"], r.get("mesh"), r.get("wgkv"),
                   r.get("n_repeats_override")) != key]
    records.append(rec)
    with open(path, "w") as f:
        json.dump(records, f, indent=1, default=str)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    choices=list(ARCH_NAMES) + ["all"])
    ap.add_argument("--shape", required=True,
                    choices=["train_4k", "prefill_32k", "decode_32k",
                             "long_500k", "all"])
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--wgkv", default="auto", choices=["auto", "on", "off"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    archs = list(ARCH_NAMES) if args.arch == "all" else [args.arch]
    shapes = (["train_4k", "prefill_32k", "decode_32k", "long_500k"]
              if args.shape == "all" else [args.shape])
    wg = None if args.wgkv == "auto" else (args.wgkv == "on")
    mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    for arch in archs:
        for shp in shapes:
            try:
                rec = run_dryrun(arch, shp, multi_pod=args.mesh == "multi",
                                 use_wgkv=wg, mesh=mesh)
            except Exception as e:  # record failures — they are bugs to fix
                rec = {"arch": arch, "shape": shp,
                       "mesh": "2x16x16" if args.mesh == "multi" else "16x16",
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
            append_record(rec, args.out)
            status = ("SKIP " + rec.get("reason", "")[:40] if rec.get("skipped")
                      else ("ERROR " + rec.get("error", "")[:80] if "error" in rec
                            else f"ok mem={rec['memory']['peak_bytes']}"))
            print(f"[dryrun] {arch} x {shp} ({rec.get('mesh')}): {status}",
                  flush=True)


if __name__ == "__main__":
    main()
