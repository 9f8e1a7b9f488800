"""FedSAE server: the full training loop of Fig. 2, behind two drivers.

Per round the server must (1) predict task pairs from history (Ira/Fassa),
(2) convert training values to selection probabilities (AL) or select
uniformly, then run the four-stage round pipeline — GATHER the cohort's
samples from the packed federation, masked budgeted LOCAL SGD, the UPLOAD
TRANSFORM (``upload_compress="topk_q8"``: top-k + int8 delta compression
with error feedback; ``"none"`` is the identity), and AGGREGATE — and
finally update history.  Baselines: FedAvg (fixed workload, stragglers
upload nothing), FedProx (ideal partial work) and an oracle skyline.

The model seam (ISSUE 9): the server trains any ``LocalStep``
(``repro.models.fl_models``) — the paper's MCLR/LSTM, the MLP, or a real
``repro/models`` architecture adapted by ``models.api.from_model`` — on
the SAME packed/scan/mesh fast path; params are an arbitrary pytree and
the engine flattens client updates to the ``[K, P]`` vector contract at
the upload boundary, so compression, screening, every aggregator and the
checkpoints are model-agnostic.  Select the model with ``cfg.model`` (or
pass an instance); the fused pallas local-SGD kernel applies iff the step
is MCLR with iid sampling, anything else takes XLA autodiff.

Upload compression (ISSUE 6): with ``upload_compress="topk_q8"`` every
uploading client's delta is top-k-sparsified (k = ceil(topk_frac *
n_params)) and int8-quantized with a per-client scale; the discarded mass
is carried as a per-client error-feedback residual added to the NEXT
round's delta before selection, so the compressed path converges like the
dense one (the telescoping identity ``transmitted + residual' == delta +
residual`` is exact — repro.core.compression).  The residual is client-axis
state: [N, P] in server state for the host driver, joined to the
``lax.scan`` carry by the scan driver, and sharded [S, C, P] with the
client blocks under ``mesh_shards`` (each shard updates only its own
clients' rows; capacity-compacted lanes reach them through the lane map).
Crashed, overflowed and unselected clients transmit nothing and keep their
residuals bit-unchanged.  The server aggregates the dense reconstruction,
so every aggregator stays pluggable; ``"none"`` (default) keeps the round
bitwise-identical to the uncompressed PR-5 pipeline.

Two drivers execute that loop (``ServerConfig.driver``):

  host  (default) one python iteration per round: numpy Ira/Fassa
        prediction, numpy selection, one jitted round dispatch, a host
        sync per round to read losses.  Bitwise seed-compatible with every
        pre-ISSUE-3 run.  With ``rng_impl="device"`` the host loop instead
        draws heterogeneity/selection and updates history through the
        float32 device twins (repro.core.{prediction,selection,
        heterogeneity}) — still one round per dispatch, but arithmetically
        bit-identical to the scan driver, which is what the parity tests
        exercise.

  scan  the fast path: ``RoundEngine.make_segment_fn`` fuses
        ``block_size`` consecutive rounds into ONE jitted ``lax.scan``
        carrying (params, L, H, theta, values, data_rng, sel_rng), so the
        whole server algorithm — heterogeneity draws, Gumbel-top-k
        selection, workload prediction, budgeted local SGD, aggregation,
        ValueTracker refresh — runs on device and zero bytes cross the
        host boundary inside a block.  Metrics are pulled once per block
        (host_syncs_per_round == 1/block_size) and the test-set eval runs
        at most once per block, at block ends where ``eval_every`` made a
        round due; history state is synced back to numpy only when ``run``
        returns.  The ``backend="pallas"`` kernels compose under the scan
        unchanged.

The scan driver forces ``rng_impl="device"``; its PRNG streams (threefry)
necessarily differ from the numpy generators, so a scan run is NOT bitwise
comparable to a default host run — it IS bitwise comparable (same cohorts,
same budgets) to a host run with ``rng_impl="device"`` and the same seeds
(tests/test_scan_driver.py).

Mesh sharding (``ServerConfig.mesh_shards``, ISSUE 4): with ``mesh_shards
= S`` the client axis is sharded over an S-way 1-D ``data`` mesh
(``launch.mesh.make_data_mesh``) instead of replicated.  The packed
federation is built in the sharded [S, ...] layout (shard s owns the
contiguous client block [s*C, (s+1)*C), ghost-padded when S does not
divide the population) and device_put with the ``clients -> data`` rule
from ``sharding.rules``; both drivers then run their round inside
``shard_map``: each shard gathers and trains ONLY the cohort slots it
owns, cohort selection becomes a local-top-k -> all-gather -> global
merge (bitwise the replicated Gumbel-top-k), and aggregation consumes the
per-slot stack rebuilt by an ownership-masked ``psum`` (every slot owned
by exactly one shard, exact zeros elsewhere) so arbitrary aggregators
stay pluggable.  Sharded runs are BITWISE identical to replicated runs on
shuffle sampling and within 2e-5 on iid (observed bitwise there too, but
only the tolerance is guaranteed — tests/test_sharding.py), on both
drivers and both backends; history state (L/H/theta/values) stays
replicated — O(N) floats.  Needs S
devices: on CPU simulate them with REPRO_FORCE_HOST_DEVICES=S (or
``launch.hostdev.force_host_devices``) before jax initializes, as the CI
``multi-device`` job does.  True multi-host (process-spanning mesh,
per-host data loading) remains future work — see ROADMAP.

Observability (ISSUE 7, ``repro.obs``): every executed round is emitted as
a typed :class:`repro.obs.schema.RoundRecord` through ONE shared code path
(``_emit_round`` — NaN-fill, record construction and progress printing are
identical for both drivers, so the two loops cannot drift on keys or
formatting).  Records land in two sinks: an in-memory
:class:`~repro.obs.sinks.RingBufferSink` that backs the backward-compatible
``history`` property (the same dict-of-lists every pre-ISSUE-7 consumer
reads — it is now a VIEW derived from the records, not a second
bookkeeping path), plus an optional caller-supplied sink
(``FedSAEServer(..., sink=JsonlSink(path))`` / ``fl_train --metrics-out``)
for durable JSONL traces that ``scripts/fl_report.py`` renders into a
straggler/health report.

Supplying a sink (or ``telemetry=True``) additionally enables on-device
metric accumulation: the scan driver's per-round stats gain per-client
upload outcomes, fixed-bin loss/workload histograms and the
compressed-vs-dense upload-byte ledger, computed inside the fused
``lax.scan`` so they ride the block's ONE existing host pull —
``host_syncs_per_round`` is unchanged by telemetry (asserted by
tests/test_telemetry.py), and with telemetry off the traced programs (and
therefore the runs) are bitwise identical to untelemetered PR-6 on both
drivers and both backends.  The host driver computes the same extras in
numpy with identical binning (``repro.obs.schema.histogram_counts``).
Named ``fed.*`` scopes (predict / select / gather / local SGD / upload
transform / aggregate — ``repro.obs.profiling``) mark the round's device
ops; ``segment_stage_map`` maps the compiled segment's instructions to
them.  The scan loop's host phases run under ``fed.host.*`` spans
(``host_spans``), and every round's stats count the local-SGD steps it
trained (``local_steps``).  Capture a trace via ``fl_train --trace-dir``.

Capacity compaction (``ServerConfig.cohort_capacity``, ISSUE 5): how much
of the cohort each shard actually EXECUTES.  The default "full" runs all
K slots on every shard with non-owned budgets masked — bitwise the PR-4
round, but zero compute scaling.  "auto" (ceil(K/S) * slack, capped at K)
or an explicit int compacts each shard's owned slots into a dense
capacity-sized lane block, so per-shard round compute drops to ~K/S lanes
— the mesh now scales round time, not just data residency.  Owned slots
past capacity OVERFLOW deterministically (slot-index order,
``core.selection.cohort_overflow``): the overflowed client runs nothing,
its E~ is forced to 0 so the Ira/Fassa update takes the existing crash
branch (the self-adaptive estimator absorbs the drop exactly like a
paper-style straggler), and both drivers surface the per-round
``overflowed``/``dropped`` counters through ``run_round`` stats and the
``history`` dict so capacity drops are never silent.  Any ``capacity >=
max owned slots per shard`` remains bitwise-identical to "full"
(tests/test_capacity.py).

Failure handling (ISSUE 8, ``repro.faults`` + ``repro.checkpoint``):
every failure the server tolerates funnels into ONE mechanism — the
zero-budget crash branch of the Ira/Fassa history update (E = 0 ->
outcome DROPPED -> L/H halved -> zero uploaded epochs -> aggregation
weight 0).  The taxonomy, in the order a round encounters it:

  availability / stragglers  ``ServerConfig.faults`` (a seeded
        FaultModel) reshapes the affordable-workload draw BEFORE
        selection: diurnal off-duty clients get E~ = 0, Pareto-slowed
        clients get E~ / slowdown.  To the self-adaptive estimator these
        are just weaker clients — no special path.
  paper crashes / overflow / dropouts  the pre-existing branches
        (affordable < assigned-L, capacity overflow) plus seeded
        mid-round dropouts (``dropout_prob``) — all force E = 0 into the
        workload update.
  corrupted uploads  drawn per-round from the decoupled fault stream
        (``fold_in(PRNGKey(fault_seed), t)``).  Screened modes
        (nan/inf/explode) train with their real budget and transmit
        garbage; the finite/norm screen (``upload_screen``, on by
        default whenever faults are configured) runs before EVERY
        registry aggregator and demotes each caught row to the crash
        outcome — weight 0 plus the global-params row value, which is
        exactly what a crashed client's row holds, so the hardened run's
        global params are provably bitwise the crash-twin run's and an
        all-faulty round degenerates to the existing no-participant
        no-op.  ``sign_flip`` is indistinguishable at the server (finite,
        honest norm) and is left to the robust aggregators
        (krum/median/trimmed_mean/geometric_median/bulyan).
  repeat offenders  ``quarantine_threshold`` suspends clients whose
        screened-failure rate trips the threshold for
        ``quarantine_rounds`` rounds (eligibility masks the Gumbel-top-k
        scores); counters ride the scan carry / host mirrors and reset
        on trip so clients re-earn trust.
  server crashes  ``run(checkpoint_dir=..., checkpoint_every=N)``
        writes atomic whole-state checkpoints (params, L/H/theta,
        values, both rng keys, compression residuals, quarantine
        counters, emitted records); ``run(..., resume=True)`` continues
        bitwise — and because fault draws are stateless in t, a resumed
        run replays the exact fault schedule (tests/test_checkpoint.py).

Per-round ``screened`` / ``quarantined`` counts surface through the
stats dict, RoundRecords and ``scripts/fl_report.py``, so silent
mitigation never masks a sick federation.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import prediction as pred
from repro.core.aggregation import get_aggregator
from repro.core.engine import RoundEngine, budget_iters
from repro.core.heterogeneity import HeterogeneitySim, sample_workloads_device
from repro.core.rounds import make_eval_fn
from repro.core.selection import (ValueTracker, cohort_overflow,
                                  get_selection, resolve_capacity,
                                  select_active, select_cohort_device,
                                  value_update_device)
from repro.data.federated import FederatedDataset
from repro.obs import profiling
from repro.obs.schema import (HISTORY_KEYS, LOSS_HIST_BINS, LOSS_HIST_MAX,
                              WORKLOAD_HIST_BINS, RoundRecord,
                              histogram_counts, record_from_row,
                              records_from_block_stats)
from repro.obs.sinks import NullSink, RingBufferSink, Sink

DRIVERS = ("host", "scan")
RNG_IMPLS = ("numpy", "device")
# entries kept in FedSAEServer.host_spans: five phases a block
HOST_SPAN_LOG = 4096


@dataclasses.dataclass
class ComputeConfig:
    """How the round executes: driver, backend, mesh and lane budget."""
    backend: str = "xla"         # xla | pallas
    driver: str = "host"         # host | scan
    block_size: int = 16         # rounds per fused segment (driver="scan")
    rng_impl: str = ""           # "" auto | numpy | device
    mesh_shards: int = 0         # 0 = replicated clients
    cohort_capacity: object = "full"
    prefetch: str = "off"        # off | double_buffer (ISSUE 10: scan
                                 # driver prepares cohort t+1 — selection,
                                 # budgets, data gather — while cohort t
                                 # trains; bitwise "off", replicated only)
    fused_generic: bool = True   # fused iid local SGD for generic
                                 # LocalStep bodies (pre-gathered batch
                                 # views + budget-slot compaction;
                                 # bitwise the per-iteration walk)


@dataclasses.dataclass
class CommConfig:
    """What crosses the wire: the upload-transform stage."""
    upload_compress: str = "none"   # none | topk_q8
    topk_frac: float = 0.1


@dataclasses.dataclass
class RobustnessConfig:
    """Fault injection and the defenses in front of aggregation."""
    faults: object = None           # Optional[repro.faults.FaultModel]
    upload_screen: str = "auto"     # auto | on | off
    screen_norm_bound: float = 1e4
    quarantine_threshold: float = 0.0
    quarantine_rounds: int = 16
    quarantine_min_tries: int = 3


# grouped sub-config -> the flat ServerConfig fields it owns (the flat
# spellings stay accepted for back-compat; see ServerConfig.__post_init__)
_CONFIG_GROUPS = {
    "compute": ComputeConfig,
    "comm": CommConfig,
    "robustness": RobustnessConfig,
}


@dataclasses.dataclass
class ServerConfig:
    algo: str = "ira"            # ira | fassa | fedavg | fedprox
    n_selected: int = 10         # K
    lr: float = 0.03
    batch_size: int = 10
    rounds: int = 100
    fixed_epochs: float = 15.0   # FedAvg/FedProx assigned workload E
    h_cap: float = 24.0          # cap on predicted H (bounds the scan)
    init_pair: tuple = (1.0, 2.0)
    U: float = 10.0              # Ira inverse-ratio increment
    alpha: float = 0.95          # Fassa EMA smoothing
    gamma1: float = 3.0
    gamma2: float = 1.0
    al_rounds: int = 0           # use AL selection for the first n rounds
    beta: float = 0.01           # AL softmax scale
    prox_mu: float = 0.1         # FedProx proximal weight
    aggregator: str = "fedavg"   # fedavg | fedprox | trimmed_mean | median
    trim_ratio: float = 0.1      # trimmed_mean band (fraction cut per end)
    selection: str = "random"    # post-AL-phase strategy (core.selection)
    sampling: str = "shuffle"    # shuffle (seed-exact, default) | iid (the
                                 # fast path: with-replacement minibatches,
                                 # no per-round epoch-permutation argsort)
    backend: str = "xla"         # round compute backend: xla | pallas (the
                                 # fused repro.kernels path; stages with no
                                 # applicable kernel fall back to XLA)
    driver: str = "host"         # host (per-round loop, bitwise seed-compat)
                                 # | scan (block_size rounds fused into one
                                 # jitted lax.scan — the fast path)
    block_size: int = 16         # rounds per fused segment (driver="scan")
    mesh_shards: int = 0         # 0 = replicated clients (default); N >= 1
                                 # shards the client axis over an N-way
                                 # `data` mesh (needs N devices; on CPU
                                 # simulate via hostdev.force_host_devices)
    cohort_capacity: object = "full"
                                 # per-shard executed cohort lanes (sharded
                                 # runs only): "full" = masked K-lane mode
                                 # (bitwise PR-4 parity), "auto" =
                                 # ceil(K/S)*slack capped at K, or an int;
                                 # owned slots past capacity overflow ->
                                 # dropped via the Ira/Fassa crash branch
                                 # (core.selection.resolve_capacity)
    prefetch: str = "off"        # "off" | "double_buffer" — scan-driver
                                 # cohort prefetch (ISSUE 10): prepare
                                 # round t+1 (selection, budgets, data
                                 # gather) in the same scan step as round
                                 # t's training.  Bitwise "off"; replicated
                                 # driver only (sharded mesh raises)
    fused_generic: bool = True   # fused iid data walk for generic
                                 # LocalStep bodies on the scan driver:
                                 # pre-gather all [max_iters, B] batch
                                 # views, scan pure compute (ISSUE 10).
                                 # False = per-iteration fetch (bitwise
                                 # identical, slower; kept as the
                                 # generic-gap baseline)
    upload_compress: str = "none"
                                 # upload transform between local SGD and
                                 # aggregation: "none" (dense f32 deltas,
                                 # bitwise PR-5) | "topk_q8" (top-k + int8
                                 # with error feedback — core.compression)
    topk_frac: float = 0.1       # kept-coordinate fraction for "topk_q8"
                                 # (k = ceil(topk_frac * n_params))
    agg_weighted: bool = False   # robust aggregators weight surviving
                                 # uploads by n_k instead of uniformly
                                 # (trimmed_mean/median/krum/
                                 # geometric_median/bulyan)
    n_byzantine: int = 0         # assumed byzantine uploads (krum/bulyan)
    faults: object = None        # Optional[repro.faults.FaultModel] —
                                 # deterministic fault injection (ISSUE 8):
                                 # diurnal availability, Pareto stragglers,
                                 # seeded dropouts and corrupted uploads.
                                 # None (default) leaves the traced round
                                 # programs bitwise PR-7.
    upload_screen: str = "auto"  # finite/norm screen before aggregation:
                                 # "auto" = on iff faults is set, "on",
                                 # "off" (screened rows demote to the
                                 # zero-budget crash branch — faults.screen)
    screen_norm_bound: float = 1e4
                                 # reject uploads whose delta l2 norm
                                 # exceeds this (plus any non-finite row)
    quarantine_threshold: float = 0.0
                                 # suspend clients whose screened-failure
                                 # rate exceeds this (0 = quarantine off;
                                 # needs the screen + device rng, not
                                 # supported on a sharded mesh)
    quarantine_rounds: int = 16  # suspension length (rounds)
    quarantine_min_tries: int = 3
                                 # attempts on record before a client can
                                 # trip the quarantine
    rng_impl: str = ""           # "" auto (numpy for host, device for scan)
                                 # | numpy | device — which PRNG streams
                                 # drive heterogeneity/selection
    seed: int = 0
    selection_seed: int = 1234   # fixed across frameworks (paper §IV-A)
    eval_every: int = 1
    model: object = None         # LocalStep selection: None = dataset
                                 # default (mclr, or lstm on text), a name
                                 # ("mclr"|"mlp"|"lstm"), an arch id from
                                 # repro.configs (via models.api.from_model),
                                 # or a LocalStep/FLModel instance —
                                 # resolved against the dataset by
                                 # models.fl_models.resolve_local_step
    # grouped sub-configs (the coherent surface; ``None`` = derive from the
    # flat fields above).  Passing a group sets its flat twins; passing a
    # flat grouped kwarg without the group still works but warns.
    compute: Optional[ComputeConfig] = None
    comm: Optional[CommConfig] = None
    robustness: Optional[RobustnessConfig] = None

    # ------------------------------------------------------------------
    def __post_init__(self):
        """Reconcile grouped sub-configs with their flat twins.

        For every grouped field the effective value is resolved as:

          * group given, flat at its default          -> group value
          * group given, flat explicitly set          -> flat value iff the
            group left that field at ITS default (a ``dataclasses.replace``
            on the flat spelling keeps working); conflicting explicit
            values raise
          * group omitted, flat explicitly set        -> flat value, with a
            ``DeprecationWarning`` steering callers to the group
          * neither                                   -> shared default

        Afterwards the group attributes are (re)materialized from the
        final flat values, so ``cfg.compute.driver`` and ``cfg.driver``
        can never disagree.
        """
        import warnings

        for group_name, group_cls in _CONFIG_GROUPS.items():
            group = getattr(self, group_name)
            deprecated = []
            for f in dataclasses.fields(group_cls):
                flat = getattr(self, f.name)
                flat_default = f.default
                flat_set = not _cfg_eq(flat, flat_default)
                if group is not None:
                    gval = getattr(group, f.name)
                    gset = not _cfg_eq(gval, f.default)
                    if flat_set and gset and not _cfg_eq(flat, gval):
                        raise ValueError(
                            f"ServerConfig: {f.name}={flat!r} conflicts "
                            f"with {group_name}.{f.name}={gval!r} — set it "
                            "in one place")
                    if not flat_set:
                        object.__setattr__(self, f.name, gval)
                elif flat_set:
                    deprecated.append(f.name)
            if deprecated:
                warnings.warn(
                    f"flat ServerConfig kwarg(s) {deprecated} are "
                    f"deprecated; group them in {group_name}="
                    f"{group_cls.__name__}(...)",
                    DeprecationWarning, stacklevel=3)
            object.__setattr__(self, group_name, group_cls(**{
                f.name: getattr(self, f.name)
                for f in dataclasses.fields(group_cls)}))


def _cfg_eq(a, b) -> bool:
    """Identity-tolerant equality for config values (FaultModel instances
    may not define __eq__; None-vs-None and is-comparison cover them)."""
    if a is b:
        return True
    try:
        return bool(a == b)
    except Exception:
        return False


class FedSAEServer:
    """The FedSAE training loop over any ``LocalStep`` model.

    ``model`` may be omitted: it is then resolved from ``cfg.model`` (a
    built-in step name, an arch id, or a LocalStep instance) against the
    dataset by ``repro.models.fl_models.resolve_local_step`` — ``None``
    picks the dataset default (mclr; lstm on text tasks).  An explicitly
    passed model object wins over ``cfg.model``.  Every model runs the
    same packed/scan/mesh fast path; only the fused pallas local-SGD
    kernel is MCLR-specific (kernel-eligibility dispatch in
    ``repro.kernels.ops``), everything else is pytree-generic."""

    def __init__(self, dataset: FederatedDataset, model=None,
                 cfg: Optional[ServerConfig] = None,
                 het: Optional[HeterogeneitySim] = None,
                 sink: Optional[Sink] = None,
                 telemetry: Optional[bool] = None):
        from repro.models.fl_models import resolve_local_step

        cfg = cfg if cfg is not None else ServerConfig()
        model = resolve_local_step(
            model if model is not None else cfg.model, dataset)
        if cfg.driver not in DRIVERS:
            raise ValueError(
                f"unknown driver {cfg.driver!r}; choose from {DRIVERS}")
        self.rng_impl = cfg.rng_impl or (
            "device" if cfg.driver == "scan" else "numpy")
        if self.rng_impl not in RNG_IMPLS:
            raise ValueError(
                f"unknown rng_impl {cfg.rng_impl!r}; choose from {RNG_IMPLS}")
        if cfg.driver == "scan" and self.rng_impl != "device":
            raise ValueError("driver='scan' requires the device rng streams")
        # ISSUE 8: fault injection + defenses.  "auto" turns the upload
        # screen on exactly when a fault model is configured, so fault-free
        # runs keep the bitwise-PR-7 round programs.
        if cfg.upload_screen not in ("auto", "on", "off"):
            raise ValueError(
                f"unknown upload_screen {cfg.upload_screen!r}; choose "
                f"from ('auto', 'on', 'off')")
        self.screening = cfg.upload_screen == "on" or (
            cfg.upload_screen == "auto" and cfg.faults is not None)
        self._quarantine = float(cfg.quarantine_threshold or 0.0) > 0.0
        if self._quarantine:
            if not self.screening:
                raise ValueError(
                    "quarantine_threshold > 0 requires the upload screen "
                    "(it counts screened failures) — set upload_screen="
                    "'on' or configure faults")
            if self.rng_impl != "device":
                raise ValueError(
                    "quarantine needs the device rng streams (eligibility "
                    "masks thread through the device Gumbel-top-k); set "
                    "rng_impl='device'")
            if cfg.mesh_shards:
                raise ValueError(
                    "quarantine is not supported on a sharded mesh — run "
                    "it on the replicated drivers")
        self.ds = dataset
        self.model = model
        self.cfg = cfg
        self.het = het or HeterogeneitySim(dataset.n_clients, seed=cfg.seed)
        N = dataset.n_clients
        self.L = np.full(N, cfg.init_pair[0], np.float64)
        self.H = np.full(N, cfg.init_pair[1], np.float64)
        self.theta = np.full(N, 0.5 * sum(cfg.init_pair), np.float64)
        self.values = ValueTracker(N, dataset.sizes.astype(np.float64))
        # reliability quarantine counters (host mirrors; the scan driver
        # carries them on device and syncs back per block)
        self.q_fail = np.zeros(N, np.int32)
        self.q_try = np.zeros(N, np.int32)
        self.q_susp = np.zeros(N, np.int32)
        self.sel_rng = np.random.default_rng(cfg.selection_seed)
        self.sel_key = jax.random.PRNGKey(cfg.selection_seed)
        self.data_rng = jax.random.PRNGKey(cfg.seed)
        self.params = model.init(jax.random.PRNGKey(cfg.seed + 7))

        self.sizes = dataset.sizes          # cached: the property recomputes
        self.max_n = int(self.sizes.max())
        tau_max = math.ceil(self.max_n / cfg.batch_size)
        budget = max(cfg.h_cap, cfg.fixed_epochs)
        self.max_iters = int(math.ceil(budget * tau_max))

        # one-time device upload: rounds gather their cohort on device.
        # With mesh_shards set the client axis is sharded over the `data`
        # mesh (ISSUE 4): each device holds only its block of clients and
        # the round runs under shard_map.
        if cfg.mesh_shards:
            from repro.launch.mesh import make_data_mesh
            self.mesh = make_data_mesh(cfg.mesh_shards)
            self.packed = dataset.packed(
                self.max_n, shards=cfg.mesh_shards).shard_to(self.mesh)
        else:
            self.mesh = None
            self.packed = dataset.packed(self.max_n)
        # ISSUE 5: per-shard executed lane count (None = masked "full"
        # mode); validates the config (non-"full" requires mesh_shards)
        self.capacity = resolve_capacity(
            cfg.cohort_capacity, cfg.n_selected, cfg.mesh_shards)
        self._mu_dev, self._sigma_dev = self.het.device_params()
        # per-client diurnal phase offsets (seeded, drawn once — the scan
        # driver derives the identical array at trace time)
        self._phases = None
        if cfg.faults is not None:
            ph = cfg.faults.phases(N)
            if ph is not None:
                self._phases = jnp.asarray(ph)
        agg_kwargs = {}
        if cfg.aggregator == "trimmed_mean":
            agg_kwargs.update(trim_ratio=cfg.trim_ratio,
                              weighted=cfg.agg_weighted)
        elif cfg.aggregator == "fedprox":
            agg_kwargs["prox_mu"] = cfg.prox_mu
        elif cfg.aggregator in ("median", "geometric_median"):
            agg_kwargs["weighted"] = cfg.agg_weighted
        elif cfg.aggregator in ("krum", "bulyan"):
            agg_kwargs.update(n_byzantine=cfg.n_byzantine,
                              weighted=cfg.agg_weighted)
        aggregator = get_aggregator(cfg.aggregator, **agg_kwargs)
        self.engine = RoundEngine(
            lr=cfg.lr, aggregator=aggregator,
            prox_mu=cfg.prox_mu if cfg.algo == "fedprox" else None,
            compress=cfg.upload_compress, topk_frac=cfg.topk_frac,
            faults=cfg.faults,
            screen_norm=cfg.screen_norm_bound if self.screening else None,
            fused_generic=cfg.fused_generic)
        # error-feedback residual state (upload_compress="topk_q8"): one
        # [P] float32 row per client, sharded with the client blocks when
        # the mesh is; None disables the upload-transform stage entirely
        if self.engine.compressing:
            from repro.core.compression import n_params_of
            n_params = n_params_of(self.params)
            if self.mesh is not None:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P
                self.residual = jax.device_put(
                    jnp.zeros((cfg.mesh_shards,
                               self.packed.clients_per_shard, n_params),
                              jnp.float32),
                    NamedSharding(self.mesh, P("data")))
            else:
                self.residual = jnp.zeros((N, n_params), jnp.float32)
        else:
            self.residual = None
        # telemetry (ISSUE 7): records always flow into the ring buffer
        # backing the ``history`` view; a caller-supplied sink (JSONL, ...)
        # additionally receives every record, and its presence switches on
        # device-side metric accumulation unless overridden
        self.sink: Sink = sink if sink is not None else NullSink()
        self.telemetry = bool(telemetry) if telemetry is not None \
            else sink is not None
        self._records = RingBufferSink()
        from repro.core.compression import (n_params_of,
                                            upload_bytes_per_client)
        n_params = n_params_of(self.params)
        self._bytes_per_client = upload_bytes_per_client(
            n_params, cfg.upload_compress, cfg.topk_frac)
        self._dense_bytes_per_client = upload_bytes_per_client(
            n_params, "none")
        self.round_fn = self.engine.make_packed_round(
            model, cfg.batch_size, self.max_iters, self.packed.max_n,
            sampling=cfg.sampling, backend=cfg.backend, mesh=self.mesh,
            capacity=self.capacity)
        self.segment_fn = self.engine.make_segment_fn(
            model, cfg.batch_size, self.max_iters, self.packed.max_n,
            cfg, mesh=self.mesh, telemetry=self.telemetry) \
            if cfg.driver == "scan" else None
        self.block_size = max(1, int(cfg.block_size))
        self.select_fn = get_selection(cfg.selection)
        self.eval_fn = make_eval_fn(model)
        self.cohorts: List[np.ndarray] = []   # [K] ids per executed round
        self.host_syncs = 0                   # device->host pulls
        # the scan loop's host phases, (name, block, t0, t1) on
        # time.perf_counter (repro.obs.profiling.host_span)
        self.host_spans = collections.deque(maxlen=HOST_SPAN_LOG)

    # ------------------------------------------------------------------
    # telemetry (ISSUE 7): the single record path both drivers share
    # ------------------------------------------------------------------
    @property
    def history(self) -> Dict[str, List]:
        """Legacy dict-of-lists view over the recorded rounds — same keys,
        key order and NaN-fill as the pre-ISSUE-7 bookkeeping, but derived
        from the RoundRecord ring buffer instead of a second code path."""
        recs = self._records.records
        return {k: [getattr(r, k) for r in recs] for k in HISTORY_KEYS}

    def _emit_round(self, record: RoundRecord):
        """Every executed round flows through here, on both drivers."""
        self._records.emit(record)
        self.sink.emit(record)

    def _lane_occupancy(self, ids: np.ndarray) -> Optional[List[float]]:
        """Per-shard executed-lane occupancy, computed host-side from the
        already-pulled cohort ids (no extra device traffic)."""
        if self.mesh is None:
            return None
        S = self.cfg.mesh_shards
        counts = np.bincount(
            np.asarray(ids) // self.packed.clients_per_shard,
            minlength=S)[:S]
        if self.capacity is not None:
            return (np.minimum(counts, self.capacity)
                    / float(self.capacity)).tolist()
        return (counts / float(self.cfg.n_selected)).tolist()

    def _progress_line(self, tag: str, label: str, acc: float,
                       dropout: float, loss: float,
                       overflowed: float) -> str:
        """The one progress-line formatter (both drivers print through it)."""
        ovf = "" if self.capacity is None else f" overflowed={overflowed:.0f}"
        return (f"[{tag}] {label} acc={acc:.3f} dropout={dropout:.2f} "
                f"loss={loss:.3f}{ovf}")

    # ------------------------------------------------------------------
    def _wl_kwargs(self):
        cfg = self.cfg
        return dict(U=cfg.U, alpha=cfg.alpha, gamma1=cfg.gamma1,
                    gamma2=cfg.gamma2, h_cap=cfg.h_cap,
                    fixed_epochs=cfg.fixed_epochs)

    def _workloads(self, ids: np.ndarray, E_true: np.ndarray):
        """Per-participant uploaded epochs + history update. Returns
        (e_eff, outcome, assigned)."""
        cfg = self.cfg
        if self.rng_impl == "device":
            # the scan driver's float32 math, run eagerly — bit-identical
            # history trajectories between the two drivers
            e_eff, outcome, assigned, L, H, theta = \
                pred.workload_update_device(
                    cfg.algo, self.L, self.H, self.theta,
                    jnp.asarray(ids, jnp.int32), E_true,
                    **self._wl_kwargs())
            self.L = np.asarray(L, np.float64)
            self.H = np.asarray(H, np.float64)
            self.theta = np.asarray(theta, np.float64)
            return (np.asarray(e_eff), np.asarray(outcome),
                    np.asarray(assigned))
        if cfg.algo == "oracle":
            # skyline: the server magically knows E~ in advance and assigns
            # exactly the affordable workload (upper bound for any predictor;
            # unrealizable — it is what FedProx implicitly assumes)
            e_eff = np.minimum(E_true, cfg.h_cap)
            outcome = np.where(e_eff > 0, pred.COMPLETED_H, pred.DROPPED)
            assigned = e_eff.copy()
        elif cfg.algo == "fedavg":
            ok = E_true >= cfg.fixed_epochs
            e_eff = np.where(ok, cfg.fixed_epochs, 0.0)
            outcome = np.where(ok, pred.COMPLETED_H, pred.DROPPED)
            assigned = np.full(len(ids), cfg.fixed_epochs)
        elif cfg.algo == "fedprox":
            e_eff = np.minimum(E_true, cfg.fixed_epochs)
            outcome = np.where(E_true >= cfg.fixed_epochs, pred.COMPLETED_H,
                               np.where(e_eff > 0, pred.COMPLETED_L,
                                        pred.DROPPED))
            assigned = np.full(len(ids), cfg.fixed_epochs)
        else:
            L, H = self.L[ids], self.H[ids]
            assigned = H.copy()
            e_eff = pred.uploaded_epochs(L, H, E_true)
            if cfg.algo == "ira":
                L2, H2, outcome = pred.ira_predict(L, H, E_true, U=cfg.U,
                                                   h_cap=cfg.h_cap)
            elif cfg.algo == "fassa":
                L2, H2, outcome = pred.fassa_predict(
                    L, H, E_true, self.theta[ids], cfg.gamma1, cfg.gamma2,
                    h_cap=cfg.h_cap)
                self.theta[ids] = pred.fassa_threshold(
                    self.theta[ids], E_true, cfg.alpha)
            else:
                raise ValueError(cfg.algo)
            self.L[ids], self.H[ids] = L2, H2
        return e_eff, outcome, assigned

    # ------------------------------------------------------------------
    def _draw_round_inputs(self, t: int):
        """(E_true_all [N], ids [K]) for round t from the configured rng."""
        from repro.faults import apply_availability_stragglers, eligibility

        cfg = self.cfg
        fm = cfg.faults
        if self.rng_impl == "device":
            # identical key discipline to the scan carry: one split for
            # (selection, heterogeneity) per round
            self.sel_key, k_sel, k_het = jax.random.split(self.sel_key, 3)
            E_dev = sample_workloads_device(k_het, self._mu_dev,
                                            self._sigma_dev)
            if fm is not None:
                # same eager f32 ops the scan body traces — bit-identical
                # availability/straggler adjustments across drivers
                E_dev = apply_availability_stragglers(fm, self._phases, t,
                                                      E_dev)
            E_true_all = np.asarray(E_dev)
            elig = (eligibility(jnp.asarray(self.q_susp), t)
                    if self._quarantine else None)
            ids = np.asarray(select_cohort_device(
                k_sel, self.values.v, cfg.n_selected, cfg.selection,
                cfg.beta, use_al=t < cfg.al_rounds, elig=elig))
            return E_true_all, ids
        E_true_all = self.het.sample_round()
        if fm is not None:
            # float64 numpy twin of the device adjustment (the fault
            # streams themselves are threefry-keyed either way, so the
            # SCHEDULE matches the device drivers; only the float widths
            # follow the host driver's numpy math)
            E_true_all = self._host_availability_stragglers(fm, t,
                                                            E_true_all)
        if t < cfg.al_rounds:
            ids = select_active(self.sel_rng, self.values.v, cfg.n_selected,
                                cfg.beta)
        else:
            ids = self.select_fn(self.sel_rng, self.values.v,
                                 self.ds.n_clients, cfg.n_selected, cfg.beta)
        return E_true_all, ids

    def _host_availability_stragglers(self, fm, t: int,
                                      E_all: np.ndarray) -> np.ndarray:
        """Numpy (float64) twin of faults.apply_availability_stragglers."""
        from repro.faults import availability_mask
        from repro.faults.inject import round_fault_key
        from repro.core.heterogeneity import pareto_slowdowns

        if fm.straggler == "pareto":
            slow = np.asarray(pareto_slowdowns(
                jax.random.fold_in(round_fault_key(fm.seed, t), 0),
                fm.pareto_alpha, E_all.shape), np.float64)
            E_all = E_all / slow
        if fm.availability == "diurnal":
            on = np.asarray(availability_mask(fm, self._phases, t))
            E_all = np.where(on, E_all, 0.0)
        return E_all

    # ------------------------------------------------------------------
    def run_round(self, t: int) -> Dict:
        from repro.faults import (corrupt_mask, dropout_mask,
                                  quarantine_update)

        cfg = self.cfg
        fm = cfg.faults
        E_true_all, ids = self._draw_round_inputs(t)
        E_true = E_true_all[ids]
        # capacity overflow (ISSUE 5): slots dropped by the per-shard lane
        # budget never run — force E~ = 0 so the workload update takes the
        # existing crash branch (same masking the scan driver applies)
        if self.capacity is not None:
            ovf = np.asarray(cohort_overflow(
                ids, self.packed.clients_per_shard, self.capacity))
        else:
            ovf = np.zeros(len(ids), bool)
        E_run = np.where(ovf, 0.0, E_true)
        # ISSUE 8: seeded mid-round dropouts zero the workload like an
        # overflow; screened corruption modes zero the OBSERVED workload so
        # Ira/Fassa evolves bitwise like the crash-twin run, while the
        # faulty client still trains with the un-demoted budget (the
        # garbage it would actually transmit)
        N = self.ds.n_clients
        if fm is not None and fm.dropout_prob > 0.0:
            E_run = np.where(np.asarray(dropout_mask(fm, t, N))[ids],
                             0.0, E_run)
        corrupt = (np.asarray(corrupt_mask(fm, t, N))[ids]
                   if fm is not None and fm.corrupts else None)
        demote = fm is not None and fm.demotes
        E_obs = np.where(corrupt, 0.0, E_run) if demote else E_run
        if demote and self.engine.injecting:
            snap = (self.L.copy(), self.H.copy(), self.theta.copy())
            e_eff, outcome, assigned = self._workloads(ids, E_obs)
            new_hist = (self.L, self.H, self.theta)
            self.L, self.H, self.theta = snap
            e_train = self._workloads(ids, E_run)[0]
            self.L, self.H, self.theta = new_hist
        else:
            e_eff, outcome, assigned = self._workloads(ids, E_obs)
            e_train = e_eff

        # no host restack: only the [K] cohort ids / budgets cross to device;
        # the packed federation was uploaded once at construction
        n = np.minimum(self.sizes[ids], self.max_n)
        if self.rng_impl == "device":
            n_iters = np.asarray(budget_iters(e_train, n, cfg.batch_size,
                                              self.max_iters))
        else:
            tau = np.ceil(n / cfg.batch_size)
            n_iters = np.minimum(np.round(e_train * tau), self.max_iters)
        self.data_rng, sub = jax.random.split(self.data_rng)
        args = (self.params, self.packed.x, self.packed.y,
                self.packed.offsets, self.packed.lengths,
                jnp.asarray(ids, jnp.int32),
                jnp.asarray(n_iters, jnp.int32), sub)
        if self.residual is not None:
            args = args + (self.residual,)
        if self.engine.injecting:
            args = args + (jnp.asarray(corrupt),)
        out = self.round_fn(*args)
        self.params, losses = out[0], out[1]
        if self.residual is not None:
            self.residual = out[3]
        bad = np.asarray(out[-1]) if self.engine.screening else None
        uploaders = np.asarray(n_iters) > 0
        if demote and self.engine.injecting:
            # the observed upload set — screened rows count as crashes
            uploaders = uploaders & ~corrupt
        if self.rng_impl == "device":
            self.values.v = np.asarray(value_update_device(
                self.values.v, self.sizes, jnp.asarray(ids, jnp.int32),
                losses, jnp.asarray(uploaders)), np.float64)
        losses = np.asarray(losses)
        self.host_syncs += 1      # the per-round loss readback
        self.cohorts.append(np.asarray(ids))

        if self.rng_impl != "device" and uploaders.any():
            self.values.update(ids[uploaders], losses[uploaders])

        stats = {
            "round": t,
            "ids": np.asarray(ids),
            "dropout": float((outcome == pred.DROPPED).mean()),
            "dropped": float((outcome == pred.DROPPED).sum()),
            "overflowed": float(ovf.sum()),
            "train_loss": float(losses[uploaders].mean()) if uploaders.any()
            else float("nan"),
            "assigned": float(np.mean(assigned)),
            "uploaded": float(np.mean(e_eff)),
            "true_workload": float(np.mean(E_true)),
            "local_steps": int(np.sum(n_iters)),
        }
        if self.engine.screening:
            stats["screened"] = float(bad.sum())
        if self._quarantine:
            qf, qt, qs, n_susp = quarantine_update(
                jnp.asarray(self.q_fail), jnp.asarray(self.q_try),
                jnp.asarray(self.q_susp), jnp.asarray(ids, jnp.int32),
                jnp.asarray(np.asarray(n_iters) > 0), jnp.asarray(bad), t,
                float(cfg.quarantine_threshold),
                int(cfg.quarantine_rounds), int(cfg.quarantine_min_tries))
            self.q_fail = np.asarray(qf, np.int32)
            self.q_try = np.asarray(qt, np.int32)
            self.q_susp = np.asarray(qs, np.int32)
            stats["quarantined"] = float(n_susp)
        if self.telemetry:
            # ISSUE 7: the host-driver twin of the scan driver's
            # device-accumulated extras — same byte ledger and identical
            # float32 binning (schema.histogram_counts <-> _device_hist)
            upf = uploaders.astype(np.float32)
            n_up = float(upf.sum())
            stats["client_uploaded"] = uploaders.astype(np.int32)
            stats["upload_bytes"] = n_up * self._bytes_per_client
            stats["dense_upload_bytes"] = n_up * self._dense_bytes_per_client
            stats["loss_hist"] = histogram_counts(
                losses, upf, 0.0, LOSS_HIST_MAX, LOSS_HIST_BINS)
            stats["workload_hist"] = histogram_counts(
                e_eff, upf, 0.0, cfg.h_cap, WORKLOAD_HIST_BINS)
            occ = self._lane_occupancy(ids)
            if occ is not None:
                stats["lane_occupancy"] = occ
        return stats

    # ------------------------------------------------------------------
    # scan driver: device-resident state blocks
    # ------------------------------------------------------------------
    def device_state(self) -> Dict:
        """The scan carry, built from the host-side history (float32)."""
        state = {
            "params": self.params,
            "L": jnp.asarray(self.L, jnp.float32),
            "H": jnp.asarray(self.H, jnp.float32),
            "theta": jnp.asarray(self.theta, jnp.float32),
            "values": jnp.asarray(self.values.v, jnp.float32),
            "data_rng": self.data_rng,
            "sel_rng": self.sel_key,
        }
        if self._quarantine:
            state["q_fail"] = jnp.asarray(self.q_fail, jnp.int32)
            state["q_try"] = jnp.asarray(self.q_try, jnp.int32)
            state["q_susp"] = jnp.asarray(self.q_susp, jnp.int32)
        if self.mesh is not None:
            # the sharded segment returns its carry replicated over the
            # mesh; a carry placed any other way compiles the segment again
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            state = jax.device_put(state, NamedSharding(self.mesh, P()))
        return state

    def _absorb_state(self, state: Dict):
        """Sync the scan carry back into the host-side mirrors (the float32
        values are stored exactly; float64 containers keep the host driver
        interchangeable round-for-round)."""
        self.params = state["params"]
        self.L = np.asarray(state["L"], np.float64)
        self.H = np.asarray(state["H"], np.float64)
        self.theta = np.asarray(state["theta"], np.float64)
        self.values.v = np.asarray(state["values"], np.float64)
        self.data_rng = state["data_rng"]
        self.sel_key = state["sel_rng"]
        if self._quarantine:
            self.q_fail = np.asarray(state["q_fail"], np.int32)
            self.q_try = np.asarray(state["q_try"], np.int32)
            self.q_susp = np.asarray(state["q_susp"], np.int32)

    def _segment_args(self, state: Dict, ts) -> tuple:
        """The scan segment's arguments for the carry ``state`` and the
        round indices ``ts``."""
        pk = self.packed
        args = (state, ts, pk.x, pk.y, pk.offsets, pk.lengths,
                self._mu_dev, self._sigma_dev)
        return args if self.residual is None else args + (self.residual,)

    def segment_stage_map(self):
        """``(module name, {instruction: fed.* stage or None})`` of the
        compiled scan segment of one full block
        (``repro.obs.profiling.stage_map``): what the device trace's
        ``XLA Ops`` events, named by instruction, belong to.  Compiles the
        program ``_run_scan`` runs, on arguments built by the same code (a
        compile-cache hit once the segment has run)."""
        if self.segment_fn is None:
            raise ValueError("segment_stage_map needs driver='scan'")
        ts = jnp.arange(self.block_size, dtype=jnp.int32)
        lowered = self.segment_fn.lower(
            *self._segment_args(self.device_state(), ts))
        return profiling.stage_map(lowered.compile().as_text())

    def _run_scan(self, T: int, verbose: bool, t_start: int = 0,
                  checkpoint_dir: Optional[str] = None,
                  checkpoint_every: int = 0):
        """Blocks of ``block_size`` rounds, one dispatch and one host pull
        each.  Every block runs under a ``fed.block`` step annotation and
        its host phases under ``fed.host.*`` spans, logged to
        ``host_spans``; each phase is opened every block, done or not."""
        cfg = self.cfg
        tx, ty = jnp.asarray(self.ds.test_x), jnp.asarray(self.ds.test_y)
        state = self.device_state()
        t0 = t_start
        while t0 < T:
            b = min(self.block_size, T - t0)
            blk = t0 // self.block_size
            span = functools.partial(profiling.host_span,
                                     log=self.host_spans, block=blk)
            with jax.profiler.StepTraceAnnotation(profiling.HOST_BLOCK,
                                                  step_num=blk):
                blk_start = time.perf_counter()
                with span(profiling.HOST_DISPATCH):
                    ts = jnp.arange(t0, t0 + b, dtype=jnp.int32)
                    out = self.segment_fn(*self._segment_args(state, ts))
                    if self.residual is not None:
                        state, self.residual, stats = out
                    else:
                        state, stats = out
                with span(profiling.HOST_PULL):
                    # the block's single host pull
                    stats = jax.device_get(stats)
                    self.host_syncs += 1
                wall = time.perf_counter() - blk_start
                with span(profiling.HOST_EVAL):
                    # eval at most once per block (with the block-end
                    # params), and only when a round inside the block was
                    # due per eval_every
                    due = (t0 + b == T) or any(
                        (t0 + i) % cfg.eval_every == 0 for i in range(b))
                    prev = self._records.last
                    prev_acc = prev.acc if prev is not None else float("nan")
                    acc, tl = prev_acc, float("nan")
                    if due:
                        acc, tl = self.eval_fn(state["params"], tx, ty)
                        acc, tl = float(acc), float(tl)
                        self.host_syncs += 1    # ...plus the eval readback
                with span(profiling.HOST_RECORDS):
                    self.cohorts.extend(np.asarray(stats["ids"]))
                    recs = records_from_block_stats(stats, t0, b)
                    for i, rec in enumerate(recs):
                        last = i == b - 1
                        rec.acc = acc if last else prev_acc
                        rec.test_loss = tl if last else float("nan")
                        rec.wall_time_s = wall / b
                        if self.telemetry and self.mesh is not None:
                            rec.lane_occupancy = self._lane_occupancy(
                                np.asarray(stats["ids"])[i])
                        self._emit_round(rec)
                    if verbose:
                        print(self._progress_line(
                            f"{cfg.algo}/scan",
                            f"rounds {t0:3d}-{t0 + b - 1:3d}", acc,
                            recs[-1].dropout, recs[-1].train_loss,
                            float(np.sum(stats["overflowed"]))))
                t0 += b
                with span(profiling.HOST_CHECKPOINT):
                    if checkpoint_dir and (
                            (checkpoint_every > 0
                             and t0 % checkpoint_every == 0) or t0 == T):
                        # the scan driver checkpoints at block boundaries
                        # only; align checkpoint_every with block_size for
                        # a resumed trace whose eval cadence matches the
                        # uninterrupted run
                        from repro.checkpoint import save_server_state
                        self._absorb_state(state)
                        save_server_state(self, checkpoint_dir, t0)
        self._absorb_state(state)
        return self.history

    # ------------------------------------------------------------------
    def run(self, rounds: Optional[int] = None, verbose: bool = False,
            checkpoint_dir: Optional[str] = None,
            checkpoint_every: int = 0, resume: bool = False):
        """Execute the training loop.

        ``checkpoint_dir`` + ``checkpoint_every`` (ISSUE 8) write an
        atomic whole-server checkpoint every N rounds (scan driver: at
        the enclosing block boundary; ``checkpoint_every=0`` saves only
        the final state); ``resume=True`` restores the
        latest checkpoint from ``checkpoint_dir`` before running — the
        resumed run's params, history state and records are bitwise the
        uninterrupted run's (tests/test_checkpoint.py)."""
        T = rounds or self.cfg.rounds
        t_start = 0
        if resume:
            if not checkpoint_dir:
                raise ValueError("resume=True requires checkpoint_dir")
            from repro.checkpoint import restore_server_state
            t_start = restore_server_state(self, checkpoint_dir)
        if self.cfg.driver == "scan":
            return self._run_scan(T, verbose, t_start=t_start,
                                  checkpoint_dir=checkpoint_dir,
                                  checkpoint_every=int(checkpoint_every))
        tx, ty = jnp.asarray(self.ds.test_x), jnp.asarray(self.ds.test_y)
        for t in range(t_start, T):
            rnd_start = time.perf_counter()
            row = self.run_round(t)
            if t % self.cfg.eval_every == 0 or t == T - 1:
                acc, tl = self.eval_fn(self.params, tx, ty)
                row["acc"], row["test_loss"] = float(acc), float(tl)
            else:
                prev = self._records.last
                row["acc"] = prev.acc if prev is not None else float("nan")
                row["test_loss"] = float("nan")
            row["wall_time_s"] = time.perf_counter() - rnd_start
            rec = record_from_row(t, row)
            self._emit_round(rec)
            if verbose and (t % 10 == 0 or t == T - 1):
                print(self._progress_line(
                    self.cfg.algo, f"round {t:3d}", rec.acc, rec.dropout,
                    rec.train_loss, rec.overflowed))
            if checkpoint_dir and (
                    (checkpoint_every > 0
                     and (t + 1) % checkpoint_every == 0) or t + 1 == T):
                from repro.checkpoint import save_server_state
                save_server_state(self, checkpoint_dir, t + 1)
        return self.history
