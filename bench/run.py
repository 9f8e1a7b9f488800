#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of a short steady window.  Both
check the run against the plain reference and print each compared number
beside its limit, last on stderr and last in the result line.  The last
line of stdout is the result, one JSON object.  Without a TPU, or with
fewer chips than the cell needs, it exits 1 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
# JAX's compile cache sits at a fixed path inside this checkout, whatever
# the environment names: the path is part of a cached entry's key, and
# two checkouts share nothing
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
    os.path.dirname(BENCH), ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    try:
        cell = harness.Cell(harness.load_json(os.pardir, "BENCHMARK.json"),
                            args.workload)
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START,
                                  log=lambda s: print(s, flush=True))
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    harness.print_checks(result)
    print(harness.result_line(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
