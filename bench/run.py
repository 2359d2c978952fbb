"""Benchmark entry point: one cell, one seed, one process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs on the accelerator JAX finds and refuses anything but a TPU with as
many chips as the cell asks for (exit 3, no result). The cell, its
configuration, traffic mix, correctness limits and per-layer metrics are
all found by name from ``BENCHMARK.json`` (``bench/spec.py``).

Prints progress and, as its last lines on standard error, each number
compared for ``correct`` beside its limit; the last line on standard
output is the result as one JSON object. ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` records a stretch of the window with
the profiler and reports the per-layer metrics instead.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

# the persistent compilation cache: JAX_COMPILATION_CACHE_DIR where set,
# else a fixed directory inside the checkout
CACHE_DIR = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
    or os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def configure_jax(jax) -> None:
    """Keep every compiled program in the persistent cache, so that only
    the first run of a cell in a checkout compiles."""
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    from bench import harness, spec
    try:
        bm = spec.load_benchmark()
        cell = spec.cell(bm, args.workload)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax
    try:
        harness.check_devices(jax.devices(), cell["chips"])
    except harness.DeviceError as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 3
    configure_jax(jax)
    result = harness.run_cell(bm, cell, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START,
                              trace_dir=TRACE_DIR)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
