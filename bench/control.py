"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 51

For each seed, in one process: a run of the cell exactly as
``bench/run.py`` makes it (same window, same sample), then, on the same
sampled prompts and served tokens, the control: the plain reference
computed with float8 e4m3 weights and activations (the precision step
below the served bfloat16), taking at each position the token it puts
first. Prints one JSON line per seed with the program's gaps (the lower
readings) and the control's (the upper readings). The benchmark's own
runs never compute the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import configure_jax  # noqa: E402  (also puts the repo on sys.path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax
    from bench import harness, spec
    bm = spec.load_benchmark()
    cell = spec.cell(bm, args.workload)
    try:
        harness.check_devices(jax.devices(), cell["chips"])
    except harness.DeviceError as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    configure_jax(jax)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res = harness.run_cell(bm, cell, seed, args.seconds, False,
                               t_start=t0, control=True)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": res["correct"],
            "program": {k: res["checks"][k]["value"]
                        for k in ("max_logit_gap", "mean_logit_gap")},
            "control": {k: c["value"] for k, c in
                        res["control"]["checks"].items()},
            "control_correct": res["control"]["correct"],
            "compared": res["checks"]["served_tokens_compared"]["value"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
