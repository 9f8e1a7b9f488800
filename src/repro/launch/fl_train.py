"""Federated training driver — the paper's system end-to-end.

  # paper-style FL over synthetic federated datasets (MCLR/LSTM):
  PYTHONPATH=src python -m repro.launch.fl_train --dataset femnist \
      --algo ira --rounds 50

  # the fused multi-round driver: blocks of 16 rounds in one lax.scan
  PYTHONPATH=src python -m repro.launch.fl_train --dataset femnist \
      --algo ira --rounds 64 --driver scan --block-size 16 --sampling iid

  # a real architecture as the per-client local step, on the packed/scan/
  # mesh fast path with compressed uploads (LocalStep seam, ISSUE 9):
  PYTHONPATH=src python -m repro.launch.fl_train --dataset sent140 \
      --model llama3.2-3b --driver scan --shards 2 --compress topk_q8

  # cross-silo FL over a production architecture (smoke scale on CPU):
  PYTHONPATH=src python -m repro.launch.fl_train --silo-arch llama3.2-3b \
      --silos 4 --rounds 5
"""
from __future__ import annotations

import argparse
import json
import os

from repro.launch.compile_cache import use_compile_cache
from repro.launch.hostdev import force_from_env

# before the jax backend initializes: lets --shards N run on a simulated
# multi-device host (the CI multi-device smoke)
force_from_env()

import jax.numpy as jnp
import numpy as np

from repro.core import (CommConfig, ComputeConfig, FedSAEServer,
                        HeterogeneitySim, RobustnessConfig, ServerConfig)
from repro.core.silo import SiloFedSAE
from repro.data.federated import DATASETS
from repro.models.api import build_model
from repro.models.fl_models import LOCAL_STEPS
from repro.obs import JsonlSink, trace_if


#: --faults CLI spellings -> FaultModel corrupt modes
FAULT_MODES = {"none": "none", "crash": "crash", "nan_upload": "nan",
               "inf_upload": "inf", "sign_flip_upload": "sign_flip",
               "explode_upload": "explode"}


def make_sink(args, resume_round=None, **meta):
    """--metrics-out -> a JsonlSink with a run-meta header (else None).

    On --resume, an existing trace is truncated to the rounds before the
    checkpoint (the resumed run re-emits everything from there — dropping
    them first keeps the trace free of duplicate rounds) and reopened in
    append mode, preserving the original header line.
    """
    if not args.metrics_out:
        return None
    append = False
    if resume_round is not None and os.path.exists(args.metrics_out):
        with open(args.metrics_out) as f:
            lines = [ln for ln in f if ln.strip()]
        kept = [ln for ln in lines
                if "_meta" in (row := json.loads(ln))
                or row.get("round", 0) < resume_round]
        with open(args.metrics_out, "w") as f:
            f.writelines(kept)
        append = True
    return JsonlSink(args.metrics_out, meta=dict(
        rounds=args.rounds, driver=args.driver, backend=args.backend,
        **meta), append=append)


def build_faults(args):
    """The CLI's fault axes -> a FaultModel (None when everything is off,
    so a fault-free run compiles the exact pre-ISSUE-8 round program)."""
    corrupt = FAULT_MODES[args.faults]
    if (corrupt == "none" and args.dropout_prob <= 0
            and args.availability == "always" and args.straggler == "none"):
        return None
    from repro.faults import FaultModel
    return FaultModel(seed=args.fault_seed, availability=args.availability,
                      day_rounds=args.day_rounds,
                      duty_cycle=args.duty_cycle, straggler=args.straggler,
                      pareto_alpha=args.pareto_alpha,
                      dropout_prob=args.dropout_prob, corrupt=corrupt,
                      corrupt_prob=args.fault_prob,
                      explode_factor=args.explode_factor)


def run_flat(args):
    make = DATASETS[args.dataset]
    ds = make() if args.paper_scale else {
        "mnist": lambda: make(n_clients=100, total=7000, dim=64, max_size=120),
        "femnist": lambda: make(n_clients=60, total=4500, dim=64, max_size=120),
        "synthetic": lambda: make(n_clients=40, total=3000, max_size=150),
        "sent140": lambda: make(n_clients=60, total=3000, vocab=300,
                                max_size=100),
    }[args.dataset]()
    # lr defaults follow the dataset's classical model; a real architecture
    # (--model <arch id>) trains the causal LM and needs a small step
    if args.dataset == "sent140":
        lr = 0.3
    else:
        lr = 0.03 if args.dataset != "synthetic" else 0.01
    if args.model is not None and args.model not in LOCAL_STEPS:
        lr = 5e-3
    if args.lr is not None:
        lr = args.lr
    cfg = ServerConfig(algo=args.algo, rounds=args.rounds, lr=lr,
                       n_selected=min(10, ds.n_clients),
                       al_rounds=args.al_rounds, h_cap=24.0,
                       aggregator=args.aggregator,
                       trim_ratio=args.trim_ratio,
                       agg_weighted=args.agg_weighted,
                       n_byzantine=args.n_byzantine,
                       selection=args.selection,
                       sampling=args.sampling,
                       model=args.model,
                       compute=ComputeConfig(
                           backend=args.backend,
                           driver=args.driver,
                           block_size=args.block_size,
                           mesh_shards=args.shards,
                           cohort_capacity=args.cohort_capacity,
                           prefetch=args.prefetch),
                       comm=CommConfig(
                           upload_compress=args.compress,
                           topk_frac=args.topk_frac),
                       robustness=RobustnessConfig(
                           faults=build_faults(args),
                           upload_screen=args.screen,
                           screen_norm_bound=args.screen_norm_bound,
                           quarantine_threshold=args.quarantine_threshold,
                           quarantine_rounds=args.quarantine_rounds,
                           quarantine_min_tries=args.quarantine_min_tries))
    resume_round = None
    if args.resume:
        from repro.checkpoint import list_checkpoints
        if not args.checkpoint_dir:
            raise SystemExit("--resume needs --checkpoint-dir")
        ckpts = list_checkpoints(args.checkpoint_dir)
        if not ckpts:
            raise SystemExit(f"--resume: no ckpt_*.msgpack under "
                             f"{args.checkpoint_dir!r}")
        resume_round = ckpts[-1][0]
    sink = make_sink(args, resume_round=resume_round, path="flat",
                     dataset=args.dataset, algo=args.algo, model=args.model)
    srv = FedSAEServer(ds, cfg=cfg,
                       het=HeterogeneitySim(ds.n_clients, seed=cfg.seed),
                       sink=sink)
    with trace_if(args.trace_dir):
        hist = srv.run(verbose=not args.quiet,
                       checkpoint_dir=args.checkpoint_dir,
                       checkpoint_every=args.checkpoint_every,
                       resume=args.resume)
    if sink is not None:
        sink.close()
        print(f"metrics: {sink.path}")
    # overflow drops would otherwise be invisible outside the engine: a
    # compacted run always reports how many cohort slots it sacrificed
    ovf = "" if srv.capacity is None else (
        f" overflowed={np.sum(hist['overflowed']):.0f}"
        f"/{len(hist['overflowed']) * cfg.n_selected:.0f} slots"
        f" (capacity={srv.capacity})")
    recs = srv._records.records
    scr = [r.screened for r in recs if r.screened is not None]
    flt = "" if not scr else f" screened={np.sum(scr):.0f} uploads"
    q = [r.quarantined for r in recs if r.quarantined is not None]
    if q:
        flt += f" quarantined={q[-1]:.0f} clients"
    print(f"final: acc={hist['acc'][-1]:.3f} "
          f"mean_dropout={np.nanmean(hist['dropout']):.3f}"
          f" dropped={np.sum(hist['dropped']):.0f}{ovf}{flt}")


def run_silo(args):
    from repro.configs import get_config
    acfg = get_config(args.silo_arch, smoke=True)
    model = build_model(acfg)
    agg_kwargs = ({"trim_ratio": args.trim_ratio}
                  if args.aggregator == "trimmed_mean" else {})
    sink = make_sink(args, path="silo", arch=args.silo_arch,
                     silos=args.silos)
    fed = SiloFedSAE(model, args.silos, lr=5e-3, max_steps=args.max_steps,
                     aggregator=args.aggregator, sink=sink, **agg_kwargs)
    ri = np.random.default_rng(0)
    K, S = args.silos, 64
    sizes = np.asarray(ri.integers(100, 1000, K))
    # each silo has its own token distribution (silo id biases the tokens)
    with trace_if(args.trace_dir):
        for r in range(args.rounds):
            toks = np.stack([
                ri.integers(0, acfg.vocab_size // (1 + (k % 3)),
                            (fed.max_steps, 2, S))
                for k in range(K)])
            batches = {"tokens": jnp.asarray(toks, jnp.int32),
                       "labels": jnp.asarray(toks, jnp.int32)}
            stats = fed.run_round(batches, sizes)
            if not args.quiet:
                print(f"round {r}: loss={stats['loss'][-1]:.4f} "
                      f"dropout={stats['dropout'][-1]:.2f} "
                      f"uploaded_steps={stats['uploaded_steps'][-1]:.1f}")
    if sink is not None:
        sink.close()
        print(f"metrics: {sink.path}")
    assert np.isfinite(stats["loss"][-1])
    print("silo FL done")


def parse_capacity(spec: str):
    """--cohort-capacity accepts "full", "auto" or an int lane count.
    Used as the argparse ``type`` so a typo dies as a clean usage error."""
    return spec if spec in ("full", "auto") else int(spec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="femnist", choices=list(DATASETS))
    ap.add_argument("--algo", default="ira",
                    choices=("fedavg", "fedprox", "ira", "fassa", "oracle"))
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--al-rounds", type=int, default=0)
    ap.add_argument("--aggregator", default="fedavg",
                    choices=("fedavg", "fedprox", "trimmed_mean", "median",
                             "krum", "geometric_median", "bulyan"))
    ap.add_argument("--trim-ratio", type=float, default=0.1,
                    help="fraction trimmed per end (trimmed_mean only)")
    ap.add_argument("--agg-weighted", action="store_true",
                    help="robust aggregators weight the surviving uploads "
                         "by client sample counts n_k instead of uniformly")
    ap.add_argument("--n-byzantine", type=int, default=0,
                    help="assumed byzantine uploads (krum / bulyan)")
    ap.add_argument("--selection", default="random",
                    choices=("random", "active", "loss_proportional"),
                    help="cohort selection after the AL warm-up rounds")
    ap.add_argument("--model", default=None,
                    help="local step trained on each client: mclr | mlp | "
                         "lstm, or a repro.configs arch id (e.g. "
                         "llama3.2-3b) adapted via models.api.from_model "
                         "(text datasets only; trains the causal LM on the "
                         "client token streams).  Default: lstm for sent140, "
                         "mclr elsewhere — bitwise the pre-ISSUE-9 runs")
    ap.add_argument("--lr", type=float, default=None,
                    help="override the dataset/model default learning rate")
    ap.add_argument("--sampling", default="shuffle",
                    choices=("shuffle", "iid"),
                    help="local minibatch rule: shuffle reproduces the seed "
                         "bit-for-bit; iid is the faster with-replacement "
                         "path (see BENCH_round_engine.json)")
    ap.add_argument("--backend", default="xla",
                    choices=("xla", "pallas"),
                    help="round compute backend: pallas runs the fused "
                         "cohort-gather / local-SGD kernels (repro.kernels), "
                         "falling back to XLA for stages with no kernel; "
                         "the platform decides how they run: compiled on "
                         "a TPU, interpreted elsewhere")
    ap.add_argument("--driver", default="host", choices=("host", "scan"),
                    help="round loop driver: host runs one python iteration "
                         "per round (bitwise seed-compatible); scan fuses "
                         "--block-size rounds into one jitted lax.scan with "
                         "a single host sync per block (the fast path)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="rounds per fused segment (driver=scan)")
    ap.add_argument("--shards", type=int, default=0,
                    help="shard the client axis over an N-way data mesh "
                         "(0 = replicated; needs N devices — set "
                         "REPRO_FORCE_HOST_DEVICES/XLA_FLAGS to simulate "
                         "them on CPU before jax initializes)")
    ap.add_argument("--cohort-capacity", default="full",
                    type=parse_capacity,
                    help="per-shard executed cohort lanes (with --shards): "
                         "'full' = masked K-lane parity mode, 'auto' = "
                         "ceil(K/S)*slack capped at K, or an explicit int; "
                         "owned slots past capacity are dropped "
                         "deterministically through the Ira/Fassa crash "
                         "branch and reported per round as overflowed")
    ap.add_argument("--prefetch", default="off",
                    choices=("off", "double_buffer"),
                    help="scan-driver cohort prefetch: double_buffer "
                         "prepares round t+1 (selection, budgets, data "
                         "gather) in the same scan step round t trains in "
                         "— bit-identical results, overlapped data "
                         "movement (replicated runs only)")
    ap.add_argument("--compress", default="none",
                    choices=("none", "topk_q8"),
                    help="upload transform between local SGD and "
                         "aggregation: topk_q8 ships each client's delta as "
                         "top-k int8 coordinates with a per-client scale "
                         "and carries the quantization error as an error-"
                         "feedback residual; none is bitwise the "
                         "uncompressed round (needs --driver host/scan on "
                         "the packed path; composes with --shards and "
                         "--cohort-capacity)")
    ap.add_argument("--topk-frac", type=float, default=0.1,
                    help="kept coordinate fraction for --compress topk_q8: "
                         "k = ceil(frac * n_params) per client per round")
    ap.add_argument("--faults", default="none",
                    choices=list(FAULT_MODES),
                    help="corrupted-upload fault injection (repro.faults): "
                         "crash = the corrupt client silently dies; "
                         "*_upload = its upload is garbage (NaN/Inf/"
                         "sign-flipped/1e8-amplified delta).  Schedules "
                         "are a pure function of (--fault-seed, round), "
                         "identical across drivers and across --resume")
    ap.add_argument("--fault-prob", type=float, default=0.1,
                    help="per-(client, round) corruption probability")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the fault schedule (independent of the "
                         "training/selection rng streams)")
    ap.add_argument("--explode-factor", type=float, default=1e8,
                    help="delta amplification for --faults explode_upload")
    ap.add_argument("--dropout-prob", type=float, default=0.0,
                    help="per-(client, round) mid-round crash probability "
                         "(DROPPED outcome; Ira/Fassa halves the task "
                         "pair)")
    ap.add_argument("--availability", default="always",
                    choices=("always", "diurnal"),
                    help="diurnal: each client is on duty for --duty-cycle "
                         "of every --day-rounds rounds, with a seeded "
                         "per-client phase")
    ap.add_argument("--day-rounds", type=int, default=24)
    ap.add_argument("--duty-cycle", type=float, default=0.5)
    ap.add_argument("--straggler", default="none",
                    choices=("none", "pareto"),
                    help="pareto: heavy-tailed per-round slowdowns divide "
                         "the simulated workloads (tail --pareto-alpha)")
    ap.add_argument("--pareto-alpha", type=float, default=2.0)
    ap.add_argument("--screen", default="auto",
                    choices=("auto", "on", "off"),
                    help="server-side upload screen (finite + delta-norm "
                         "check before ANY aggregator; rejected uploads "
                         "are demoted to the zero-budget crash branch).  "
                         "auto = on whenever faults are configured")
    ap.add_argument("--screen-norm-bound", type=float, default=1e4,
                    help="max accepted upload delta l2 norm (--screen)")
    ap.add_argument("--quarantine-threshold", type=float, default=0.0,
                    help="> 0: suspend clients whose screened-upload rate "
                         "exceeds this fraction of their attempts for "
                         "--quarantine-rounds rounds (needs the screen and "
                         "rng-impl device selection; off by default)")
    ap.add_argument("--quarantine-rounds", type=int, default=16)
    ap.add_argument("--quarantine-min-tries", type=int, default=3)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="write atomic whole-server checkpoints "
                         "(ckpt_<round>.msgpack: params, Ira/Fassa state, "
                         "rng, compression residual, telemetry trace) into "
                         "this directory")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="checkpoint cadence in rounds (0 = only with "
                         "--checkpoint-dir at the end; on the scan driver "
                         "align it with --block-size — checkpoints land on "
                         "block boundaries)")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest checkpoint in "
                         "--checkpoint-dir; the completed run is bitwise "
                         "identical to an uninterrupted one, and an "
                         "existing --metrics-out trace is truncated at the "
                         "checkpoint round and appended to")
    ap.add_argument("--metrics-out", default=None,
                    help="write per-round telemetry as JSONL RoundRecords "
                         "(repro.obs) to this path; render the trace with "
                         "scripts/fl_report.py.  Also switches on on-device "
                         "metric accumulation (histograms, byte ledger, "
                         "per-client upload outcomes) — metrics ride the "
                         "scan driver's existing per-block stats pull, so "
                         "host syncs are unchanged")
    ap.add_argument("--trace-dir", default=None,
                    help="capture a jax.profiler trace of the run into this "
                         "directory (TensorBoard/perfetto); the four round "
                         "pipeline stages appear as fed.* regions")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress per-round/block progress lines (the "
                         "final summary still prints)")
    ap.add_argument("--paper-scale", action="store_true")
    ap.add_argument("--silo-arch", default=None)
    ap.add_argument("--silos", type=int, default=4)
    ap.add_argument("--max-steps", type=int, default=8)
    args = ap.parse_args()
    use_compile_cache()
    if args.silo_arch:
        run_silo(args)
    else:
        run_flat(args)


if __name__ == "__main__":
    main()
