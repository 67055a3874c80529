#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``:
each number compared beside its limit, which also end standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits 1
and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    import jax
    from bench.harness import NoChip, find_devices, print_checks, run_cell
    from bench.spec import load_cell
    from repro.serving.runtime import enable_compile_cache

    cell = load_cell(args.workload)
    try:
        devices = find_devices(cell.chips)
    except NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, T_START,
                      log=lambda s: print(s, file=sys.stderr, flush=True))
    print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
