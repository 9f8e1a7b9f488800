#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from.  Not part of
a benchmark run.

    python3 bench/control.py --workload <name> --seeds 1,2,...,12 \
        --control-seeds 1,2,3

For every seed of ``--seeds`` the program runs the cell's checked blocks
exactly as a benchmark run's set-up does (``harness.run_checked_blocks``)
and is compared with the reference: the lower readings.  For every seed
of ``--control-seeds`` two stand-ins are put in the program's place and
compared with the same reference:

  control     the reference computed in bfloat16 (the configuration states
              float32): the precision a later change might be tempted by
  half_batch  the reference with half of every minibatch left out and the
              mean taken over the rest

One JSON line per reading goes to stdout and to ``--out``.  Everything
runs in one process, which holds the chip.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
# JAX's compile cache sits at a fixed path inside this checkout, whatever
# the environment names: the path is part of a cached entry's key, and
# two checkouts share nothing
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
    os.path.dirname(BENCH), ".jax_cache")


def seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def first_norms(prog, ref, block):
    """Each leaf's change norm over the first block, the program's beside
    the reference's: where a gap comes from."""
    import check
    p0 = ref["params"][0]
    mine = check.leaf_norms(prog["params"][block], p0)
    return {f"first_norms.{k}": [mine[k], v]
            for k, v in check.leaf_norms(ref["params"][block], p0).items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import check
    import harness
    from reference.fedsae import Reference

    cell = harness.Cell(harness.load_json(os.pardir, "BENCHMARK.json"),
                        args.workload)
    try:
        harness.require_chips(jax, cell.chips)
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    block, check_rounds = cell.block, cell.check_blocks * cell.block
    out = open(args.out, "a") if args.out else None

    def emit(kind, seed, numbers, seconds):
        line = json.dumps({"workload": cell.name, "kind": kind, "seed": seed,
                           "seconds": round(seconds, 3), **numbers})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    snaps_at = (0, block, check_rounds)
    for seed in args.seeds:
        t0 = time.perf_counter()
        ds, srv = harness.build(cell, seed, harness.make_clock(block))
        ckpt = tempfile.mkdtemp(prefix="fedsae-control-")
        try:
            snaps, _ = harness.run_checked_blocks(cell, srv, ckpt)
        finally:
            shutil.rmtree(ckpt, ignore_errors=True)
        prog = harness.program_outputs(srv.history, srv.cohorts, snaps,
                                       check_rounds)
        del srv
        gc.collect()
        t_prog = time.perf_counter() - t0
        t0 = time.perf_counter()
        ref = Reference(ds.clients_x, ds.clients_y, cell.config,
                        cell.flat_traffic(), seed).run(check_rounds,
                                                       snaps_at)
        emit("program", seed, {**check.compare(prog, ref, block,
                                               check_rounds),
                                **first_norms(prog, ref, block)}, t_prog)
        emit("reference", seed, {}, time.perf_counter() - t0)
        if seed not in args.control_seeds:
            continue
        for kind, kw in (("control", {"dtype": jnp.bfloat16}),
                         ("half_batch", {"half_batch": True})):
            t0 = time.perf_counter()
            stand_in = Reference(ds.clients_x, ds.clients_y, cell.config,
                                 cell.flat_traffic(), seed, **kw).run(
                check_rounds, snaps_at)
            emit(kind, seed, check.compare(stand_in, ref, block,
                                           check_rounds),
                 time.perf_counter() - t0)
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
