"""Rounds/sec: the seed round path vs the device-resident RoundEngine.

Seed path (pre-refactor `FedSAEServer.run_round` + `core.rounds`): restack
the selected cohort on the host and re-upload O(K * max_n * feature_dim)
padded samples every round (~37 MB/round at paper-scale MNIST with K=30),
then run local SGD over a per-round epoch permutation obtained by a vmapped
argsort (as expensive on CPU as the restack itself).

Engine paths (`RoundEngine.make_packed_round`): the packed federation is
uploaded once and the cohort is gathered on device — only [K] ids/budgets
cross the host edge.  Two legs are timed so the two wins are attributable
separately:

  engine+shuffle  seed-exact minibatch rule (bit-identical results to the
                  seed path) — isolates the data-movement win alone
  engine+iid      `sampling="iid"` with-replacement minibatches (standard
                  SGD, opt-in via ServerConfig.sampling / --sampling) —
                  additionally drops the per-round epoch-permutation argsort

Pallas-backend legs (ISSUE 2, `backend="pallas"`) time the fused-kernel
round path so the perf trajectory captures the kernel work:

  pallas+shuffle  fed_gather kernel + XLA scan SGD (bit-identical to
                  engine+shuffle)
  pallas+iid      fed_gather + fed_local_sgd kernels (fp-tolerance parity)

The platform decides how the kernels run (repro.kernels.ops): compiled on a
TPU, in the Pallas interpreter on any other backend, where the pallas_call
grid serialises the vmapped client axis — a CPU recording of the pallas
rounds/s measures interpreter overhead, not the TPU win the kernels target.

Scan-driver legs (ISSUE 3, `RoundEngine.make_segment_fn`) time the fused
multi-round path: BLOCK_SIZE rounds per jitted lax.scan — selection,
heterogeneity draws, workload bookkeeping and the round itself all on
device, one host pull per block (host_syncs_per_round == 1/BLOCK_SIZE):

  engine_scan_path         xla backend, iid sampling; the round body indexes
                           minibatches straight out of the packed arrays, so
                           no [K, max_n, feat] cohort shard is materialized
  engine_scan_pallas_path  the fed_gather + fed_local_sgd kernels composed
                           under the scan (interpret-mode caveat above)

The scan legs run the fixed-workload baseline (algo="fedprox" with
fixed_epochs == the bench's --epochs) so every leg executes the same
masked iteration count per round; cohorts are selected on device
(uniform Gumbel-top-k) instead of replayed from the host list, which is
exactly the work the fused driver eliminates.

Sharded legs (ISSUES 4+5, opt-in via --shards N on an N-device host):

  engine_scan_sharded_path           masked full-K sharded execution
                                     (cohort_capacity="full") — data
                                     residency, no compute scaling
  engine_scan_sharded_capacity_path  capacity-compacted execution
                                     (cohort_capacity="auto"): each shard
                                     runs only ~K/S owned cohort lanes;
                                     its speedup_vs_masked_sharded is the
                                     ISSUE-5 acceptance number (>= 1.5x on
                                     a quiet 8-simulated-device CPU mesh;
                                     recorded 1.6x reduced / 2.8x paper).
                                     scripts/check_bench.py gates it
                                     against regression vs the recorded
                                     ratio plus an absolute 1.2x floor
                                     (below the 1.6-1.9x clean-run noise
                                     band, so runner contention cannot
                                     flake CI while a genuine loss of the
                                     compaction win still turns it red)

Compressed-upload leg (ISSUE 6, ``upload_compress="topk_q8"``):

  engine_scan_compress_path  the scan leg with the upload-transform stage
                             enabled: every surviving client's delta is
                             top-k-sparsified + int8-quantized (k = ceil(
                             0.1 * n_params)) with the error-feedback
                             residual riding the lax.scan carry.  Every
                             engine/scan/sharded leg records its simulated
                             ``upload_bytes_per_round`` (benchmarks/common
                             .upload_bytes_per_round); the compressed
                             leg's ratio vs the dense legs is the ISSUE-6
                             acceptance number (<= 0.15x at the default
                             topk_frac) and scripts/check_bench.py gates
                             it statically from the recorded file.

Fault-screen overhead leg (ISSUE 8, ``repro.faults``):

  scan_faults_screen  two runs of the xla scan leg — once plain and once
                      with the finite/norm upload screen forced on
                      (upload_screen="on": screen_uploads + the
                      optimization-barrier fence in RoundEngine._finish,
                      exactly the hardened-aggregation program a faulted
                      run compiles, minus injection).  ``overhead_frac =
                      1 - screened/plain`` is the recorded cost of
                      screening every round; the ISSUE-8 acceptance bar
                      is <= 0.05 and scripts/check_bench.py gates it
                      statically from the recorded file.

Model-generic legs (ISSUE 9 seam, ISSUE 10 fused generic driver):

  engine_scan_mlp_path        the xla scan leg with a NON-MCLR local step
                              (the built-in 2-layer tanh MLP) and the
                              fused generic driver OFF
                              (``fused_generic=False``): per-iteration
                              minibatch index walk + XLA autodiff, the
                              pre-ISSUE-10 generic baseline the fused
                              speedup is measured against.
  engine_scan_mlp_fused_path  the same MLP leg at the DEFAULT config: the
                              hoisted [K, max_iters, B] batch-view walk
                              (one gather per round) + budget-slot
                              compaction — lanes stable-sorted by budget,
                              each scanned iteration slot executes only
                              the power-of-two lane prefix covering its
                              active budgets, skipping the masked
                              identity-update slots that dominate under
                              FedSAE's self-adaptive budgets.
                              ``speedup_vs_unfused`` is the ISSUE-10
                              acceptance number (>= 1.5x) and
                              ``slowdown_vs_mclr_scan`` the remaining
                              generic-model gap (<= 2.4x vs the also-
                              compacted mclr leg); both gated statically
                              by scripts/check_bench.py.
  engine_scan_pallas_mlp_path backend="pallas": the MLP dispatches to the
                              fused dense two-layer kernel
                              (``fed_local_sgd_dense``) under the scan
                              (interpret-mode caveat above applies —
                              tracked honestly, flips on TPU).

Prefetch leg (ISSUE 10, ``ComputeConfig.prefetch="double_buffer"``):

  engine_scan_prefetch_path  the xla scan leg with the double-buffered
                             cohort pipeline: round t+1's selection +
                             cohort gather are issued in the same program
                             region as round t's train/aggregate, so the
                             scheduler is free to overlap them.  On this
                             CPU host the payoff is ~neutral (no async
                             copy engine); ``ratio_vs_scan`` is gated
                             >= ~0.95x so the pipeline can never cost
                             real throughput unnoticed.

Telemetry-overhead legs (ISSUE 7, ``repro.obs``):

  telemetry_overhead  two runs of the xla scan leg with device-side metric
                      accumulation ON (make_segment_fn(telemetry=True)) and
                      per-block RoundRecord emission — once into a NullSink
                      (baseline) and once into a JsonlSink writing a real
                      trace file.  ``overhead_frac = 1 - jsonl/null`` is the
                      recorded cost of durable telemetry; the ISSUE-7
                      acceptance bar is <= 0.05 and scripts/check_bench.py
                      gates it statically from the recorded file.

``--only <group>`` records just one leg group (sharded | telemetry |
faults | models | prefetch — unambiguous prefixes accepted) and MERGES its
entries into the existing scale record, so the other legs keep their
committed numbers.  The legacy ``--sharded-only`` / ``--telemetry-only`` /
``--faults-only`` / ``--models-only`` flags are aliases:

  REPRO_FORCE_HOST_DEVICES=8 PYTHONPATH=src python \
      benchmarks/bench_round_engine.py --scale both --shards 8 --only sharded
  PYTHONPATH=src python benchmarks/bench_round_engine.py --only models

Same masked iteration count, same rng discipline in all legs.

  PYTHONPATH=src python benchmarks/bench_round_engine.py --scale reduced
  PYTHONPATH=src python benchmarks/bench_round_engine.py --scale both

Results are merged into BENCH_round_engine.json at the repo root, one entry
per scale, so the perf trajectory is tracked across PRs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.hostdev import force_from_env  # noqa: E402

# before jax initializes: lets --shards N time the sharded scan leg on a
# simulated multi-device host
force_from_env()

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (host_bytes_per_round,  # noqa: E402
                               upload_bytes_per_round)
from repro.core.aggregation import get_aggregator
from repro.core.compression import n_params_of
from repro.core.engine import RoundEngine
from repro.core.heterogeneity import HeterogeneitySim
from repro.core.server import ComputeConfig, ServerConfig
from repro.data.federated import make_mnist_like
from repro.obs import JsonlSink, NullSink, records_from_block_stats

OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_round_engine.json")

BLOCK_SIZE = 10   # rounds fused per lax.scan segment in the scan legs
TOPK_FRAC = 0.1   # kept-coordinate fraction in the compressed-upload leg

# --only <group>: the legs each partial re-record times (groups that report
# a ratio against the plain scan leg re-time it too, so the ratio is from
# one machine state, not mixed runs)
ONLY_GROUPS = {
    "sharded": ("scan_sharded", "scan_sharded_capacity"),
    "telemetry": ("scan_telemetry_null", "scan_telemetry_jsonl"),
    "faults": ("scan", "scan_screen"),
    "models": ("scan", "scan_mlp", "scan_mlp_fused", "scan_pallas_mlp"),
    "prefetch": ("scan", "scan_prefetch"),
}

# K=30 selected per round as in the paper's MNIST runs.  The reduced scale
# keeps the paper's max client size (400 samples) so the data path carries a
# representative share of the round; batch size is scaled with the client
# size to hold the local-SGD budget at the same fraction of an epoch.
SCALES = {
    "reduced": dict(n_clients=100, total=12000, dim=64, max_size=400, k=30,
                    batch_size=40),
    "paper": dict(n_clients=1000, total=69035, dim=784, max_size=400, k=30,
                  batch_size=40),
}


def _seed_round_fn(model, lr, batch_size, max_iters):
    """Verbatim copy of the pre-refactor core/rounds.py round (the baseline
    this benchmark tracks; tests/test_engine.py proves make_round_fn still
    reproduces it bit-for-bit)."""
    B = batch_size

    def local_train(global_params, xk, yk, maskk, nk, iters, key):
        M = xk.shape[0]
        perm = jnp.argsort(jax.random.uniform(key, (M,)) + (1.0 - maskk) * 1e9)
        nk_safe = jnp.maximum(nk, 1)

        def step(params, i):
            idx = perm[(i * B + jnp.arange(B)) % nk_safe]
            batch = {"x": xk[idx], "y": yk[idx],
                     "mask": maskk[idx] * (jnp.arange(B) < nk_safe)}
            g = jax.grad(model.loss)(params, batch)
            active = (i < iters).astype(jnp.float32)
            params = jax.tree.map(lambda p, gg: p - lr * active * gg,
                                  params, g)
            return params, None

        params, _ = jax.lax.scan(step, global_params, jnp.arange(max_iters))
        final_loss = model.loss(params, {"x": xk, "y": yk, "mask": maskk})
        return params, final_loss

    @jax.jit
    def round_fn(global_params, x, y, mask, n, n_iters, rng):
        keys = jax.random.split(rng, x.shape[0])
        params_k, losses = jax.vmap(
            local_train, in_axes=(None, 0, 0, 0, 0, 0, 0))(
            global_params, x, y, mask, n, n_iters, keys)
        wk = n.astype(jnp.float32) * (n_iters > 0).astype(jnp.float32)
        tot = wk.sum()
        coef = jnp.where(tot > 0, wk / jnp.maximum(tot, 1e-9), 0.0)

        def agg(stacked, g0):
            mixed = jnp.tensordot(coef.astype(stacked.dtype), stacked, axes=1)
            return jnp.where(tot > 0, mixed, g0)

        return jax.tree.map(agg, params_k, global_params), losses, tot > 0

    return round_fn


SCREEN_NORM_BOUND = 1e4   # the screened leg's norm bound (config default)


def bench_scale(scale: str, rounds: int, epochs: float, seed: int = 0,
                reps: int = 3, shards: int = 0, gate_only: bool = False,
                only: str = ""):
    from repro.core.selection import resolve_capacity
    from repro.models.fl_models import make_mclr, make_mlp

    spec = SCALES[scale]
    ds = make_mnist_like(seed=seed, n_clients=spec["n_clients"],
                         total=spec["total"], dim=spec["dim"],
                         max_size=spec["max_size"])
    model = make_mclr(spec["dim"], ds.n_classes)
    params = model.init(jax.random.PRNGKey(seed))
    # ISSUE 9: a non-MCLR LocalStep on the same driver — XLA autodiff step,
    # pytree params through the [K, P] ravel contract
    mlp = make_mlp(spec["dim"], ds.n_classes)
    mlp_params = mlp.init_params(jax.random.PRNGKey(seed))
    mlp_n_params = n_params_of(mlp_params)
    K = spec["k"]
    batch_size = spec["batch_size"]
    max_n = int(ds.sizes.max())
    max_iters = int(np.ceil(epochs * np.ceil(max_n / batch_size)))
    sizes = np.asarray(ds.sizes)

    seed_fn = _seed_round_fn(model, 0.03, batch_size, max_iters)
    engine = RoundEngine(lr=0.03, aggregator=get_aggregator("fedavg"))
    engine_c = RoundEngine(lr=0.03, aggregator=get_aggregator("fedavg"),
                           compress="topk_q8", topk_frac=TOPK_FRAC)
    # ISSUE 8: the hardened-aggregation program (finite/norm screen +
    # aggregator fence) without injection — pure screening cost
    engine_s = RoundEngine(lr=0.03, aggregator=get_aggregator("fedavg"),
                           screen_norm=SCREEN_NORM_BOUND)
    n_params = n_params_of(params)
    packed = ds.packed(max_n)
    packed_fns = {
        (sampling, backend): engine.make_packed_round(
            model, batch_size, max_iters, packed.max_n,
            sampling=sampling, backend=backend)
        for sampling in ("shuffle", "iid")
        for backend in ("xla", "pallas")}

    sel = np.random.default_rng(seed)
    cohorts = [sel.choice(ds.n_clients, K, replace=False)
               for _ in range(rounds)]
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), rounds)

    def budgets(n):
        return np.minimum(np.round(epochs * np.ceil(n / batch_size)),
                          max_iters)

    def seed_path_round(p, ids, key):
        """Pre-refactor dataflow: host restack + per-round upload."""
        x, y, mask, n = ds.stacked(ids, max_n)
        p, losses, _ = seed_fn(
            p, jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask),
            jnp.asarray(n, jnp.int32),
            jnp.asarray(budgets(n), jnp.int32), key)
        return p, losses

    def engine_round(packed_fn):
        def round_(p, ids, key):
            """Device-resident dataflow: ids/budgets cross the host edge."""
            n = np.minimum(sizes[ids], max_n)
            p, losses, _ = packed_fn(
                p, packed.x, packed.y, packed.offsets, packed.lengths,
                jnp.asarray(ids, jnp.int32),
                jnp.asarray(budgets(n), jnp.int32), key)
            return p, losses
        return round_

    def timed(round_fn):
        def run():
            p = jax.tree.map(jnp.copy, params)
            p, losses = round_fn(p, cohorts[0], keys[0])   # compile warmup
            jax.block_until_ready(losses)
            t0 = time.perf_counter()
            for ids, key in zip(cohorts, keys):
                p, losses = round_fn(p, ids, key)
            jax.block_until_ready(losses)
            dt = time.perf_counter() - t0
            return rounds / dt, p
        return run

    # scan-driver legs: the fixed-workload baseline keeps every leg's masked
    # iteration count identical (e_eff == epochs for ~every drawn E)
    het = HeterogeneitySim(spec["n_clients"], seed=seed)
    mu_dev, sigma_dev = het.device_params()
    block = min(BLOCK_SIZE, rounds)
    n_blocks = -(-rounds // block)

    def scan_cfg(backend, capacity="full", fused=True, prefetch="off"):
        # the real ServerConfig (not a hand-built namespace) so the
        # benchmarked segment sees exactly the fields the server passes
        # cohort_capacity resolves against the mesh make_segment_fn is
        # given, so the cfg carries only the spec ("full" | "auto" | int)
        return ServerConfig(
            algo="fedprox", n_selected=K, selection="random",
            h_cap=max(24.0, epochs), fixed_epochs=epochs,
            sampling="iid",
            compute=ComputeConfig(backend=backend, driver="scan",
                                  block_size=block,
                                  cohort_capacity=capacity,
                                  fused_generic=fused, prefetch=prefetch))

    def init_state(p0=None):
        return {
            "params": jax.tree.map(jnp.copy, params if p0 is None else p0),
            "L": jnp.full(spec["n_clients"], 1.0, jnp.float32),
            "H": jnp.full(spec["n_clients"], 2.0, jnp.float32),
            "theta": jnp.full(spec["n_clients"], 1.5, jnp.float32),
            "values": jnp.asarray(np.sqrt(sizes) * 2.0, jnp.float32),
            "data_rng": jax.random.PRNGKey(seed + 1),
            "sel_rng": jax.random.PRNGKey(seed),
        }

    def timed_scan(backend, mesh=None, pk=None, capacity="full",
                   eng=None, step=None, p0=None, fused=True,
                   prefetch="off"):
        pk = packed if pk is None else pk
        seg = (eng or engine).make_segment_fn(
            step or model, batch_size, max_iters, pk.max_n,
            scan_cfg(backend, capacity, fused, prefetch), mesh=mesh)

        def run_blocks(state):
            for b in range(n_blocks):
                ts = jnp.arange(b * block, (b + 1) * block, dtype=jnp.int32)
                state, stats = seg(state, ts, pk.x, pk.y,
                                   pk.offsets, pk.lengths,
                                   mu_dev, sigma_dev)
                jax.device_get(stats)   # the driver's one host pull / block
            return state

        def run():
            # compile warmup: ONE block — every block shares the [block]
            # ts shape, so the jit cache is already hot for the timed loop
            st, _ = seg(init_state(p0), jnp.arange(block, dtype=jnp.int32),
                        pk.x, pk.y, pk.offsets, pk.lengths,
                        mu_dev, sigma_dev)
            jax.block_until_ready(st["params"])
            state = init_state(p0)
            t0 = time.perf_counter()
            state = run_blocks(state)
            jax.block_until_ready(state["params"])
            dt = time.perf_counter() - t0
            return n_blocks * block / dt, state["params"]
        return run

    def timed_scan_compress(backend="xla"):
        # the upload-transform stage under the fused driver: the [N, P]
        # error-feedback residual joins the segment signature and the
        # lax.scan carry
        seg = engine_c.make_segment_fn(model, batch_size, max_iters,
                                       packed.max_n, scan_cfg(backend))

        def init_residual():
            return jnp.zeros((spec["n_clients"], n_params), jnp.float32)

        def run():
            st, _, _ = seg(init_state(), jnp.arange(block, dtype=jnp.int32),
                           packed.x, packed.y, packed.offsets,
                           packed.lengths, mu_dev, sigma_dev,
                           init_residual())
            jax.block_until_ready(st["params"])
            state, res = init_state(), init_residual()
            t0 = time.perf_counter()
            for b in range(n_blocks):
                ts = jnp.arange(b * block, (b + 1) * block, dtype=jnp.int32)
                state, res, stats = seg(state, ts, packed.x, packed.y,
                                        packed.offsets, packed.lengths,
                                        mu_dev, sigma_dev, res)
                jax.device_get(stats)
            jax.block_until_ready(state["params"])
            dt = time.perf_counter() - t0
            return n_blocks * block / dt, state["params"]
        return run

    def timed_scan_telemetry(sink_factory):
        # ISSUE 7: the xla scan leg with device-side metric accumulation on
        # and per-block RoundRecord emission into a sink — the telemetry
        # extras ride the block's one existing stats pull, so the only added
        # costs are the extra device arithmetic and the sink itself
        seg = engine.make_segment_fn(model, batch_size, max_iters,
                                     packed.max_n, scan_cfg("xla"),
                                     telemetry=True)

        def run():
            st, _ = seg(init_state(), jnp.arange(block, dtype=jnp.int32),
                        packed.x, packed.y, packed.offsets, packed.lengths,
                        mu_dev, sigma_dev)
            jax.block_until_ready(st["params"])
            sink = sink_factory()
            state = init_state()
            t0 = time.perf_counter()
            for b in range(n_blocks):
                ts = jnp.arange(b * block, (b + 1) * block, dtype=jnp.int32)
                state, stats = seg(state, ts, packed.x, packed.y,
                                   packed.offsets, packed.lengths,
                                   mu_dev, sigma_dev)
                stats = jax.device_get(stats)
                for rec in records_from_block_stats(stats, b * block, block):
                    sink.emit(rec)
            jax.block_until_ready(state["params"])
            dt = time.perf_counter() - t0
            sink.close()
            return n_blocks * block / dt, state["params"]
        return run

    def jsonl_sink():
        return JsonlSink(os.path.join(
            tempfile.mkdtemp(prefix="bench_telemetry_"), "trace.jsonl"))

    legs = {"seed": timed(seed_path_round),
            "shuffle": timed(engine_round(packed_fns[("shuffle", "xla")])),
            "iid": timed(engine_round(packed_fns[("iid", "xla")])),
            "pallas_shuffle":
                timed(engine_round(packed_fns[("shuffle", "pallas")])),
            "pallas_iid": timed(engine_round(packed_fns[("iid", "pallas")])),
            "scan": timed_scan("xla"),
            "scan_mlp": timed_scan("xla", step=mlp, p0=mlp_params,
                                   fused=False),
            "scan_mlp_fused": timed_scan("xla", step=mlp, p0=mlp_params),
            "scan_pallas_mlp": timed_scan("pallas", step=mlp,
                                          p0=mlp_params),
            "scan_prefetch": timed_scan("xla", prefetch="double_buffer"),
            "scan_screen": timed_scan("xla", eng=engine_s),
            "scan_pallas": timed_scan("pallas"),
            "scan_compress": timed_scan_compress("xla"),
            "scan_telemetry_null": timed_scan_telemetry(NullSink),
            "scan_telemetry_jsonl": timed_scan_telemetry(jsonl_sink)}
    if shards:
        # opt-in sharded legs (ISSUES 4+5): the same fused scan driver with
        # the client axis sharded over an N-way data mesh (needs N devices
        # — REPRO_FORCE_HOST_DEVICES simulates them on CPU).  Two legs so
        # the capacity win is attributable:
        #
        #   scan_sharded           masked full-K execution (cohort_capacity
        #                          ="full") — every shard computes all K
        #                          cohort slots with non-owned budgets
        #                          zeroed; data residency only, and on fake
        #                          CPU devices it additionally pays SPMD
        #                          overhead, so expect NO win vs `scan`
        #   scan_sharded_capacity  capacity-compacted (cohort_capacity=
        #                          "auto"): each shard executes only ~K/S
        #                          owned lanes, so total round compute
        #                          drops ~S-fold — the leg the >=1.5x
        #                          acceptance gate tracks, real even on a
        #                          simulated CPU mesh because the fake
        #                          devices timeshare the same cores
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(shards)
        pk_sharded = ds.packed(max_n, shards=shards).shard_to(mesh)
        legs["scan_sharded"] = timed_scan("xla", mesh=mesh, pk=pk_sharded)
        legs["scan_sharded_capacity"] = timed_scan(
            "xla", mesh=mesh, pk=pk_sharded, capacity="auto")
    if shards and (gate_only or only == "sharded"):
        # the capacity gate / --only sharded recording consume only the
        # masked-vs-compacted pair
        legs = {k: legs[k] for k in ("scan_sharded",
                                     "scan_sharded_capacity")}
    elif only:
        # --only <group> re-records one leg group (plus the scan baseline
        # the group's ratios are normalized against) and merges its
        # entries into the existing scale record
        legs = {k: legs[k] for k in ONLY_GROUPS[only]}
    elif gate_only:
        # scripts/check_bench.py consumes only the scan/engine ratio — time
        # exactly those two legs so the CI gate pays for nothing else
        legs = {"iid": legs["iid"], "scan": legs["scan"]}
    # interleave repetitions so machine drift hits every leg equally; report
    # the median rep per leg (robust to contention spikes either way)
    samples = {name: [] for name in legs}
    final_p = {}
    for _ in range(reps):
        for name, fn in legs.items():
            r, final_p[name] = fn()
            samples[name].append(r)
    rps = {name: float(np.median(v)) for name, v in samples.items()}
    for name in set(rps) & {"iid", "pallas_iid", "scan", "scan_pallas",
                            "scan_mlp", "scan_mlp_fused", "scan_pallas_mlp",
                            "scan_prefetch", "scan_screen", "scan_compress",
                            "scan_telemetry_null", "scan_telemetry_jsonl",
                            "scan_sharded", "scan_sharded_capacity"}:
        for leaf in jax.tree.leaves(final_p[name]):
            assert np.isfinite(np.asarray(leaf)).all()

    dense_upload = upload_bytes_per_round(K, n_params)

    def sharded_entries():
        cap = resolve_capacity("auto", K, shards)
        masked, compact = rps["scan_sharded"], rps["scan_sharded_capacity"]
        return {
            "engine_scan_sharded_path": {
                "driver": "scan", "sampling": "iid", "backend": "xla",
                "block_size": block, "mesh_shards": shards,
                "cohort_capacity": "full",
                "data": "client axis sharded over the data mesh "
                        "(shard_map); masked full-K execution",
                "upload_bytes_per_round": dense_upload,
                "rounds_per_sec": round(masked, 3)},
            "engine_scan_sharded_capacity_path": {
                "driver": "scan", "sampling": "iid", "backend": "xla",
                "block_size": block, "mesh_shards": shards,
                "cohort_capacity": "auto", "capacity_lanes": cap,
                "data": "capacity-compacted shards: each shard executes "
                        "only its owned cohort lanes (overflow -> "
                        "deterministic drop)",
                "upload_bytes_per_round": dense_upload,
                "rounds_per_sec": round(compact, 3),
                "speedup_vs_masked_sharded": round(compact / masked, 3)},
        }

    def telemetry_entry():
        null = rps["scan_telemetry_null"]
        jsonl = rps["scan_telemetry_jsonl"]
        return {"telemetry_overhead": {
            "driver": "scan", "sampling": "iid", "backend": "xla",
            "block_size": block, "telemetry": True,
            "data": "make_segment_fn(telemetry=True) + per-block "
                    "RoundRecord emission; overhead_frac = 1 - jsonl/null "
                    "(ISSUE-7 acceptance: <= 0.05, gated statically by "
                    "scripts/check_bench.py)",
            "null_sink_rounds_per_sec": round(null, 3),
            "jsonl_sink_rounds_per_sec": round(jsonl, 3),
            "overhead_frac": round(1.0 - jsonl / null, 4)}}

    def models_entry():
        # fused vs unfused is pure data movement — the bench itself pins
        # the bitwise contract the parity suite tests at training scale
        for a, b in zip(jax.tree.leaves(final_p["scan_mlp"]),
                        jax.tree.leaves(final_p["scan_mlp_fused"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        plain = rps["scan"]
        mlp_rps = rps["scan_mlp"]
        fused_rps = rps["scan_mlp_fused"]
        mlp_upload = upload_bytes_per_round(K, mlp_n_params)
        common = {"driver": "scan", "sampling": "iid",
                  "block_size": block, "local_step": "mlp",
                  "n_params": int(mlp_n_params),
                  "upload_bytes_per_round": mlp_upload}
        return {
            "engine_scan_mlp_path": {
                **common, "backend": "xla", "fused_generic": False,
                "data": "non-MCLR LocalStep (2-layer tanh MLP, XLA "
                        "autodiff local step) with the fused generic "
                        "driver OFF: per-iteration minibatch index walk — "
                        "the pre-ISSUE-10 generic baseline; "
                        "slowdown_vs_mclr_scan tracks what leaving the "
                        "MCLR fast path used to cost",
                "rounds_per_sec": round(mlp_rps, 3),
                "slowdown_vs_mclr_scan": round(plain / mlp_rps, 3)},
            "engine_scan_mlp_fused_path": {
                **common, "backend": "xla",
                "data": "same MLP leg at the default config: hoisted "
                        "[K, max_iters, B] batch-view walk + budget-slot "
                        "compaction (lanes stable-sorted by budget, each "
                        "iteration slot runs only a power-of-two prefix "
                        "covering its active lanes — ISSUE 10); "
                        "bitwise-identical params to the unfused leg "
                        "(asserted here every run)",
                "rounds_per_sec": round(fused_rps, 3),
                "speedup_vs_unfused": round(fused_rps / mlp_rps, 3),
                "slowdown_vs_mclr_scan": round(plain / fused_rps, 3)},
            "engine_scan_pallas_mlp_path": {
                **common, "backend": "pallas",
                "kernels": "fed_local_sgd_dense under lax.scan",
                "data": "the MLP dispatched to the fused dense two-layer "
                        "pallas kernel (closed-form backprop, VMEM-"
                        "resident params; interpret-mode on CPU — see "
                        "pallas_mode)",
                "rounds_per_sec": round(rps["scan_pallas_mlp"], 3)},
        }

    def prefetch_entry():
        # prefetch off/on is the same operation sequence — bitwise at
        # training scale, asserted every time the pair is timed
        for a, b in zip(jax.tree.leaves(final_p["scan"]),
                        jax.tree.leaves(final_p["scan_prefetch"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        plain = rps["scan"]
        pf = rps["scan_prefetch"]
        return {"engine_scan_prefetch_path": {
            "driver": "scan", "sampling": "iid", "backend": "xla",
            "block_size": block, "prefetch": "double_buffer",
            "data": "double-buffered cohort pipeline: round t+1's "
                    "selection + cohort gather issued in the same program "
                    "region as round t's train/aggregate (p0 (e p)* e "
                    "scan); ~neutral on CPU (no async copy engine), "
                    "ratio_vs_scan gated >= ~0.95x by "
                    "scripts/check_bench.py so the pipeline can never "
                    "cost real throughput unnoticed",
            "upload_bytes_per_round": dense_upload,
            "rounds_per_sec": round(pf, 3),
            "ratio_vs_scan": round(pf / plain, 3)}}

    def faults_entry():
        plain = rps["scan"]
        screened = rps["scan_screen"]
        return {"scan_faults_screen": {
            "driver": "scan", "sampling": "iid", "backend": "xla",
            "block_size": block, "upload_screen": "on",
            "screen_norm_bound": SCREEN_NORM_BOUND,
            "data": "finite/norm upload screen + aggregator fence in "
                    "every round (the hardened-aggregation program minus "
                    "injection); overhead_frac = 1 - screened/plain "
                    "(ISSUE-8 acceptance: <= 0.05, gated statically by "
                    "scripts/check_bench.py)",
            "plain_rounds_per_sec": round(plain, 3),
            "screened_rounds_per_sec": round(screened, 3),
            "overhead_frac": round(1.0 - screened / plain, 4)}}

    if shards and (gate_only or only == "sharded"):
        out = sharded_entries()
        if gate_only:
            out.update(scale=scale, rounds_timed=rounds,
                       epochs_per_round=epochs, gate_only=True)
        return out
    if only:
        builders = {"telemetry": telemetry_entry, "faults": faults_entry,
                    "models": models_entry, "prefetch": prefetch_entry}
        return builders[only]()
    if gate_only:
        return {
            "scale": scale, "rounds_timed": rounds,
            "epochs_per_round": epochs, "gate_only": True,
            "engine_path": {"sampling": "iid",
                            "rounds_per_sec": round(rps["iid"], 3)},
            "engine_scan_path": {"driver": "scan", "sampling": "iid",
                                 "block_size": block,
                                 "rounds_per_sec": round(rps["scan"], 3)},
        }
    seed_rps, shuffle_rps, iid_rps = rps["seed"], rps["shuffle"], rps["iid"]
    p_seed, p_shuf, p_iid = final_p["seed"], final_p["shuffle"], final_p["iid"]
    # engine+shuffle AND pallas+shuffle are bit-identical to the seed path
    # (same cohorts/rng; gather padding contributes exactly 0)
    for other in ("shuffle", "pallas_shuffle"):
        for a, b in zip(jax.tree.leaves(p_seed),
                        jax.tree.leaves(final_p[other])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    itemsize = np.dtype(np.float32).itemsize
    restack_bytes = K * max_n * (spec["dim"] + 2) * itemsize  # x + y + mask
    sharded_leg = sharded_entries() if shards else {}
    return {
        **sharded_leg,
        "scale": scale,
        "n_clients": spec["n_clients"],
        "k_selected": K,
        "max_n": max_n,
        "feature_dim": spec["dim"],
        "batch_size": batch_size,
        "rounds_timed": rounds,
        "max_iters": max_iters,
        "epochs_per_round": epochs,
        "seed_path": {"sampling": "shuffle", "data": "host restack/upload",
                      "rounds_per_sec": round(seed_rps, 3)},
        "n_params": int(n_params),
        "engine_shuffle_path": {"sampling": "shuffle",
                                "data": "device-resident gather",
                                "upload_bytes_per_round": dense_upload,
                                "rounds_per_sec": round(shuffle_rps, 3)},
        "engine_path": {"sampling": "iid", "data": "device-resident gather",
                        "upload_bytes_per_round": dense_upload,
                        "rounds_per_sec": round(iid_rps, 3)},
        "engine_pallas_shuffle_path": {
            "sampling": "shuffle", "backend": "pallas",
            "kernels": "fed_gather",
            "upload_bytes_per_round": dense_upload,
            "rounds_per_sec": round(rps["pallas_shuffle"], 3)},
        "engine_pallas_path": {
            "sampling": "iid", "backend": "pallas",
            "kernels": "fed_gather + fed_local_sgd",
            "upload_bytes_per_round": dense_upload,
            "rounds_per_sec": round(rps["pallas_iid"], 3)},
        "engine_scan_path": {
            "driver": "scan", "sampling": "iid", "backend": "xla",
            "block_size": block,
            "data": "device-resident, direct packed indexing (no cohort "
                    "shard materialized)",
            "host_syncs_per_round": round(1.0 / block, 4),
            "upload_bytes_per_round": dense_upload,
            "rounds_per_sec": round(rps["scan"], 3)},
        "engine_scan_pallas_path": {
            "driver": "scan", "sampling": "iid", "backend": "pallas",
            "block_size": block,
            "kernels": "fed_gather + fed_local_sgd under lax.scan",
            "host_syncs_per_round": round(1.0 / block, 4),
            "upload_bytes_per_round": dense_upload,
            "rounds_per_sec": round(rps["scan_pallas"], 3)},
        "engine_scan_compress_path": {
            "driver": "scan", "sampling": "iid", "backend": "xla",
            "block_size": block,
            "upload_compress": "topk_q8", "topk_frac": TOPK_FRAC,
            "data": "top-k + int8 upload transform with error-feedback "
                    "residual in the lax.scan carry",
            "upload_bytes_per_round": upload_bytes_per_round(
                K, n_params, "topk_q8", TOPK_FRAC),
            "upload_compression_ratio": round(
                upload_bytes_per_round(K, n_params, "topk_q8", TOPK_FRAC)
                / dense_upload, 4),
            "rounds_per_sec": round(rps["scan_compress"], 3)},
        **models_entry(),
        **prefetch_entry(),
        **telemetry_entry(),
        **faults_entry(),
        "pallas_mode": "interpret" if jax.default_backend() == "cpu"
        else "compiled",
        "pallas_speedup_vs_engine": round(rps["pallas_iid"] / iid_rps, 3),
        "scan_speedup_vs_engine": round(rps["scan"] / iid_rps, 3),
        "seed_path_rounds_per_sec": round(seed_rps, 3),
        "engine_rounds_per_sec": round(iid_rps, 3),
        "speedup": round(iid_rps / seed_rps, 3),
        "speedup_data_path_only": round(shuffle_rps / seed_rps, 3),
        "seed_path_host_bytes_per_round": int(restack_bytes),
        "engine_host_bytes_per_round": host_bytes_per_round(K),
        "backend": jax.default_backend(),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", choices=("reduced", "paper", "both"),
                    default="reduced")
    ap.add_argument("--rounds", type=int, default=30,
                    help="timed rounds per path")
    ap.add_argument("--reps", type=int, default=3,
                    help="interleaved repetitions per path (best kept)")
    ap.add_argument("--epochs", type=float, default=0.25,
                    help="local epochs per client per round (kept small so "
                         "the round's data path, which this benchmark "
                         "tracks, is not drowned by local-SGD compute)")
    ap.add_argument("--shards", type=int, default=0,
                    help="also time the sharded scan legs (masked full-K + "
                         "capacity-compacted) on an N-way data mesh (needs "
                         "N devices; simulate on CPU via "
                         "REPRO_FORCE_HOST_DEVICES=N — the masked leg "
                         "measures SPMD overhead there, the compacted leg "
                         "a real compute win)")
    ap.add_argument("--only", default="", metavar="GROUP",
                    help="time only one leg group and MERGE its entries "
                         "into the existing scale record — the other legs "
                         "keep their committed numbers.  Groups: "
                         f"{', '.join(ONLY_GROUPS)} (unambiguous prefixes "
                         "accepted; groups reporting a ratio vs the plain "
                         "scan leg re-time that baseline too)")
    # legacy spellings of --only <group>, kept so recorded invocations in
    # docs/CI keep working
    for group in ("sharded", "telemetry", "faults", "models"):
        ap.add_argument(f"--{group}-only", dest="only",
                        action="store_const", const=group,
                        help=f"alias for --only {group}")
    ap.add_argument("--gate-only", action="store_true",
                    help="time only the gate legs (iid-engine + scan, or "
                         "the sharded masked/compacted pair with --shards) "
                         "and write just their entries (the check_bench.py "
                         "CI gate); never merged into the committed "
                         "trajectory file")
    ap.add_argument("--out", default=OUT_PATH)
    args = ap.parse_args()
    use_compile_cache()
    if args.gate_only and os.path.abspath(args.out) == \
            os.path.abspath(OUT_PATH):
        ap.error("--gate-only writes a partial record; pass --out elsewhere")
    if args.only:
        hits = [g for g in ONLY_GROUPS if g.startswith(args.only)]
        if len(hits) != 1:
            ap.error(f"--only {args.only!r}: "
                     + ("ambiguous, matches " + "/".join(hits) if hits
                        else "no such leg group")
                     + f"; groups: {', '.join(ONLY_GROUPS)}")
        args.only = hits[0]
    if args.only == "sharded" and not args.shards:
        ap.error("--only sharded requires --shards")
    if args.only and args.only != "sharded" and (args.shards
                                                 or args.gate_only):
        ap.error(f"--only {args.only} times a 1-device leg group alone; "
                 "drop --shards/--gate-only")
    if args.only and args.gate_only:
        ap.error("--only and --gate-only are exclusive recording modes")
    scales = ("reduced", "paper") if args.scale == "both" else (args.scale,)
    merged = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            merged = json.load(f)
    if args.only:
        # merging into a missing entry would leave a partial record that
        # check_bench.py's scan/engine gate crashes on
        missing = [s for s in scales if "engine_scan_path"
                   not in merged.get(s, {})]
        if missing:
            ap.error(f"--only {args.only} merges into existing entries, "
                     f"but {args.out} has no full record for {missing}; "
                     f"run the full bench for those scales first")
    for scale in scales:
        res = bench_scale(scale, args.rounds, args.epochs, reps=args.reps,
                          shards=args.shards, gate_only=args.gate_only,
                          only=args.only)
        if args.only:
            entry = merged.get(scale, {})
            entry.update(res)
            merged[scale] = entry
        else:
            merged[scale] = res
        if args.shards and (args.gate_only or args.only == "sharded"):
            cap = res["engine_scan_sharded_capacity_path"]
            print(f"[{scale}] sharded legs (S={args.shards}): masked "
                  f"{res['engine_scan_sharded_path']['rounds_per_sec']:.2f}"
                  f" rounds/s   compacted (capacity="
                  f"{cap['capacity_lanes']}) "
                  f"{cap['rounds_per_sec']:.2f} rounds/s   "
                  f"{cap['speedup_vs_masked_sharded']:.2f}x")
            continue
        if args.gate_only:
            print(f"[{scale}] gate legs: engine "
                  f"{res['engine_path']['rounds_per_sec']:.2f} rounds/s   "
                  f"scan {res['engine_scan_path']['rounds_per_sec']:.2f} "
                  f"rounds/s")
            continue
        full = not args.only
        if full:
            print(f"[{scale}] seed path: "
                  f"{res['seed_path_rounds_per_sec']:.2f} "
                  f"rounds/s   engine: {res['engine_rounds_per_sec']:.2f} "
                  f"rounds/s   speedup: {res['speedup']:.2f}x   scan: "
                  f"{res['engine_scan_path']['rounds_per_sec']:.2f} "
                  f"rounds/s ({res['scan_speedup_vs_engine']:.2f}x engine)"
                  f"   pallas ({res['pallas_mode']}): "
                  f"{res['engine_pallas_path']['rounds_per_sec']:.2f} "
                  f"rounds/s")
            comp = res["engine_scan_compress_path"]
            print(f"[{scale}] scan+topk_q8: {comp['rounds_per_sec']:.2f} "
                  f"rounds/s   upload {comp['upload_bytes_per_round']} "
                  f"B/round vs dense "
                  f"{res['engine_scan_path']['upload_bytes_per_round']}"
                  f" B/round ({comp['upload_compression_ratio']:.3f}x)")
        if full or args.only == "models":
            ml = res["engine_scan_mlp_path"]
            mf = res["engine_scan_mlp_fused_path"]
            pd = res["engine_scan_pallas_mlp_path"]["rounds_per_sec"]
            print(f"[{scale}] scan+mlp: unfused "
                  f"{ml['rounds_per_sec']:.2f} rounds/s   fused "
                  f"{mf['rounds_per_sec']:.2f} rounds/s "
                  f"({mf['speedup_vs_unfused']:.2f}x; "
                  f"{mf['slowdown_vs_mclr_scan']:.2f}x off mclr scan; "
                  f"{ml['n_params']} params)   pallas dense: "
                  f"{pd:.2f} rounds/s")
        if full or args.only == "prefetch":
            pf = res["engine_scan_prefetch_path"]
            print(f"[{scale}] scan+prefetch: {pf['rounds_per_sec']:.2f} "
                  f"rounds/s ({pf['ratio_vs_scan']:.2f}x plain scan)")
        if full or args.only == "telemetry":
            tel = res["telemetry_overhead"]
            print(f"[{scale}] scan+telemetry: null sink "
                  f"{tel['null_sink_rounds_per_sec']:.2f} rounds/s   jsonl "
                  f"sink {tel['jsonl_sink_rounds_per_sec']:.2f} rounds/s   "
                  f"overhead {tel['overhead_frac']:.1%}")
        if full or args.only == "faults":
            fs = res["scan_faults_screen"]
            print(f"[{scale}] scan+screen: plain "
                  f"{fs['plain_rounds_per_sec']:.2f} rounds/s   screened "
                  f"{fs['screened_rounds_per_sec']:.2f} rounds/s   "
                  f"overhead {fs['overhead_frac']:.1%}")
    with open(args.out, "w") as f:
        json.dump(merged, f, indent=2)
    print(f"wrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    main()
