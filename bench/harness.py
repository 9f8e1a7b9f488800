"""One run of one benchmark cell: set-up, a timed window of scan blocks,
the optional traced window, and the correctness check.

Everything that belongs to a cell is found by name: ``BENCHMARK.json``
names the cell, its configuration (``configs/<config>.json``) and its
traffic (``traffic/<traffic>.json``); the limits of its check are in
``limits/<cell>.json``; each per-layer metric is read by
``metrics/<metric>.py``.

The program is driven through its public entry: ``FedSAEServer`` built by
its normal constructor and ``FedSAEServer.run`` on the scan driver, with
a ``Sink`` of the benchmark's own (``telemetry=False``, so the device
program is the one users run).  ``run`` restarts its round indices at 0
on every call unless it resumes from a checkpoint, so the harness chains
three calls through the program's own checkpoints, and the round indices
run on without a restart:

  A  rounds [0, block)                  compile + the first checked block
  B  rounds [block, check)              the rest of the checked blocks
  C  rounds [check, check + block)      warm-up of the resumed loop
     rounds [check + block, ...)        the timed window: W whole blocks

The params after A and after B, the per-round stats of A and B and the
cohorts are what the check compares with the reference.  W is sized from
the time of B's last block so that the window lasts about ``--seconds``:
``run`` needs its round count up front.  Block ends are stamped by the
sink, after the block's single host pull (and its eval); the window runs
from the stamp that ends C's first block to the stamp of its last, and
``rounds_per_s`` is the window's rounds over that time.  ``setup_s`` runs
from the start of the process to the start of the window.
"""
from __future__ import annotations

import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))

_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILE_ALL = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration", _COMPILE)
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(*parts, base: str = BENCH) -> Dict:
    with open(os.path.join(base, *parts)) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its configuration, traffic and
    limits, read from their files under ``base``."""

    def __init__(self, spec: Dict, name: str, base: str = BENCH):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; choose from "
                           f"{sorted(cells)}")
        self.spec = spec
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.config = load_json("configs", f"{self.entry['config']}.json",
                                base=base)
        self.traffic = load_json("traffic", f"{self.entry['traffic']}.json",
                                 base=base)
        self.limits = load_json("limits", f"{name}.json", base=base)

    @property
    def block(self) -> int:
        return int(self.traffic["compute"]["block_size"])

    @property
    def check_blocks(self) -> int:
        return int(self.traffic["check_blocks"])

    def flat_traffic(self) -> Dict:
        """The traffic's server, compute and comm settings in one dict."""
        t = self.traffic
        return {**t["server"], **t["compute"], **t.get("comm", {})}

    def per_layer(self) -> List[Dict]:
        return [m for m in self.spec["per_layer"]
                if self.name in m.get("workloads", [self.name])]

    def end_to_end(self) -> List[Dict]:
        return [m for m in self.spec["end_to_end"]
                if self.name in m.get("workloads", [self.name])]


class CompileMeter:
    """Programs JAX compiles, seconds it spends tracing, lowering and
    compiling, and its persistent-cache hits (``jax.monitoring``)."""

    def __init__(self, jax):
        self.seconds, self.programs, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in _COMPILE_ALL:
            self.seconds += secs
        if event == _COMPILE:
            self.programs += 1

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self.hits += 1


def make_clock(block: int):
    """The harness's sink: stamps the end of every block (its last record
    arrives after the block's host pull), and opens and closes the window
    at the rounds it is armed with."""
    from repro.obs.sinks import Sink

    class BlockClock(Sink):
        def __init__(self):
            self.stamps: List[tuple] = []
            self.open_at = self.close_at = None
            self.on_open = self.on_close = None
            self.opened = self.closed = None

        def emit(self, record):
            if (record.round + 1) % block:
                return
            now = time.perf_counter()
            self.stamps.append((record.round + 1, now))
            if record.round + 1 == self.open_at:
                self.opened = now
                if self.on_open:
                    self.on_open()
            elif record.round + 1 == self.close_at:
                self.closed = now
                if self.on_close:
                    self.on_close()

    return BlockClock()


def require_chips(jax, chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"the default device is {devices[0].platform!r}, "
                     "not a TPU; the benchmark does not run elsewhere")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


def host_params(params) -> Dict[str, np.ndarray]:
    import jax
    return {k: np.asarray(v, np.float32)
            for k, v in jax.device_get(params).items()}


def build(cell: Cell, seed: int, sink):
    """The federation, the model and the server, as a user builds them."""
    from repro.core import (CommConfig, ComputeConfig, FedSAEServer,
                            HeterogeneitySim, ServerConfig)
    from repro.data import federated
    from repro.models import fl_models

    gen = cell.config["generator"]
    # one federation for every seed, its clients in the seed's order: the
    # packed shapes (and so the compiled programs) stay the same, and a
    # seed changes which data each client slot holds, not how much work
    # there is
    fed = getattr(federated, gen["fn"])(**gen["args"])
    order = np.random.default_rng(seed).permutation(fed.n_clients)
    ds = federated.FederatedDataset(
        fed.name, [fed.clients_x[i] for i in order],
        [fed.clients_y[i] for i in order], fed.test_x, fed.test_y,
        fed.n_classes, fed.task)
    pm = cell.config["program_model"]
    model = getattr(fl_models, pm["fn"])(**pm["args"])
    t = cell.traffic
    cfg = ServerConfig(seed=seed, compute=ComputeConfig(**t["compute"]),
                       comm=CommConfig(**t.get("comm", {})), **t["server"])
    srv = FedSAEServer(ds, model, cfg,
                       het=HeterogeneitySim(ds.n_clients, seed=seed),
                       sink=sink, telemetry=False)
    return ds, srv


def run_checked_blocks(cell: Cell, srv, ckpt: str):
    """Calls A and B: the checked blocks, through ``run`` and the
    program's checkpoints.  Returns the params after A and after B, and
    the time B started."""
    block, check = cell.block, cell.check_blocks * cell.block
    srv.run(rounds=block, checkpoint_dir=ckpt)                        # A
    snaps = {block: host_params(srv.params)}
    t_b = time.perf_counter()
    srv.run(rounds=check, checkpoint_dir=ckpt, resume=True)           # B
    snaps[check] = host_params(srv.params)
    return snaps, t_b


def program_outputs(hist: Dict, cohorts, snaps: Dict, check: int) -> Dict:
    """What the check compares, from the program's records of the
    checked rounds and the params after each checked call."""
    prog = {k: np.asarray(hist[k][:check])
            for k in ("dropped", "assigned", "uploaded", "train_loss")}
    prog["ids"] = np.asarray(cohorts)[:check]
    prog["params"] = snaps
    return prog


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, log=print) -> Dict:
    """One run of ``cell``; returns the result line's object."""
    import jax

    t_import = time.perf_counter() - t_start
    devices = require_chips(jax, cell.chips)
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    # every program, however quick to compile, goes to the cache: a warm
    # run then compiles nothing and its set-up is steady
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    meter = CompileMeter(jax)
    block, check = cell.block, cell.check_blocks * cell.block

    t_devices = time.perf_counter() - t_start
    clock = make_clock(block)
    ds, srv = build(cell, seed, clock)
    p0 = host_params(srv.params)
    log(f"[setup] import_s={t_import:.3f} devices_s={t_devices:.3f} "
        f"data+server_s={time.perf_counter() - t_start:.3f} "
        f"clients={ds.n_clients} samples={int(ds.sizes.sum())} "
        f"max_n={srv.max_n} max_iters={srv.max_iters} compile_cache={cache}")

    ckpt = tempfile.mkdtemp(prefix="fedsae-bench-")
    prof_dir = tempfile.mkdtemp(prefix="fedsae-trace-") if trace else None
    try:
        snaps, t_b = run_checked_blocks(cell, srv, ckpt)
        t_checked = time.perf_counter() - t_start
        # B's last block alone, between two stamps of one run() call; B's
        # mean, which also holds run()'s start, if that block stalled
        block_s = min(clock.stamps[-1][1] - clock.stamps[-2][1],
                      (clock.stamps[-1][1] - t_b) / (cell.check_blocks - 1))
        want = seconds
        if trace:
            want = min(seconds, float(cell.traffic.get("trace_seconds", 2)))
        n_win = max(1, int(round(want / block_s)))
        clock.open_at = check + block
        clock.close_at = check + block * (1 + n_win)
        marks = {}

        def on_open():
            marks["compiles"] = meter.programs + meter.hits
            if trace:
                jax.profiler.start_trace(prof_dir)
            marks["traced_from"] = time.perf_counter()

        def on_close():
            marks["compiles_in_window"] = (meter.programs + meter.hits
                                           - marks["compiles"])
            if trace:
                jax.profiler.stop_trace()

        clock.on_open, clock.on_close = on_open, on_close
        srv.run(rounds=clock.close_at, checkpoint_dir=ckpt, resume=True)  # C
        stats = devices[0].memory_stats() or {}
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices[:cell.chips])
        # the traced run's window starts once the profiler is on
        window_s = clock.closed - (marks["traced_from"] if trace
                                   else clock.opened)
        setup_s = clock.opened - t_start
        hist = srv.history
        cohorts = np.stack(srv.cohorts)
        log(f"[setup] setup_s={setup_s:.3f} checked_s={t_checked:.3f} "
            f"compile_s={meter.seconds:.3f} "
            f"programs_compiled={meter.programs} cache_hits={meter.hits} "
            f"block_s_estimate={block_s:.4f} window_blocks={n_win} "
            f"compiles_in_window={marks['compiles_in_window']} "
            f"peak_bytes_in_use={peak} "
            f"bytes_in_use={stats.get('bytes_in_use')} "
            f"bytes_limit={stats.get('bytes_limit')}")
        ends = [t for r, t in clock.stamps if clock.open_at <= r
                <= clock.close_at]
        blocks = np.diff(ends)
        log(f"[window] blocks={len(blocks)} block_s_min={blocks.min():.4f} "
            f"median={np.median(blocks):.4f} max={blocks.max():.4f}")
        if marks["compiles_in_window"]:
            raise RuntimeError(f"{marks['compiles_in_window']} programs "
                               "compiled inside the timed window")
        lo, hi = clock.open_at, clock.close_at
        window = {
            "rounds": hi - lo, "window_s": window_s,
            "uploaded": np.asarray(hist["uploaded"][lo:hi]),
            "dropout": np.asarray(hist["dropout"][lo:hi]),
            "train_loss": np.asarray(hist["train_loss"][lo:hi]),
            "ids": cohorts[lo:hi], "sizes": np.asarray(ds.sizes),
            "max_n": srv.max_n, "max_iters": srv.max_iters,
            "n_params": sum(int(v.size) for v in p0.values()),
        }
        prog = program_outputs(hist, cohorts, snaps, check)
        del srv, clock
        gc.collect()

        result = {"correct": False, "attempted": window["rounds"],
                  "failed": int(np.sum((window["dropout"] < 1)
                                       & ~np.isfinite(window["train_loss"]))),
                  "metrics": {}, "device": {
                      "platform": devices[0].platform,
                      "kind": devices[0].device_kind,
                      "count": cell.chips, "memory_peak_bytes": peak}}
        if trace:
            import reduce_trace
            red = reduce_trace.reduce(prof_dir, window_s)
            result["device"]["busy_s"] = red.busy_s
            result["device"]["window_s"] = red.window_s
            result["breakdown"] = red.breakdown()
            ctx = MetricContext(cell, red, window, devices[0].device_kind)
            for m in cell.per_layer():
                mod = importlib.import_module(f"metrics.{m['name']}")
                value = mod.read(ctx)
                if value is not None:
                    result["metrics"][m["name"]] = {"value": value,
                                                    "unit": m["unit"]}
        else:
            values = {"rounds_per_s": window["rounds"] / window_s,
                      "setup_s": setup_s}
            for m in cell.end_to_end():
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
        if prof_dir:
            shutil.rmtree(prof_dir, ignore_errors=True)

    import check as check_mod
    from reference.fedsae import Reference
    t_ref = time.perf_counter()
    ref = Reference(ds.clients_x, ds.clients_y, cell.config,
                    cell.flat_traffic(), seed).run(check, (0, block, check))
    numbers = check_mod.compare(prog, ref, block, check)
    correct, table = check_mod.verdict(numbers, cell.limits)
    log(f"[check] reference_s={time.perf_counter() - t_ref:.3f} "
        f"rounds_checked={check}")
    result["correct"] = correct
    result["checks"] = table
    return result


class MetricContext:
    """What a per-layer metric's reader may look at: the reduced trace,
    the window's per-round stats, the cell's files, the chip's peaks and
    the FLOP and byte counts of ``flops/``."""

    def __init__(self, cell: Cell, trace, window: Dict, device_kind: str):
        self.cell = cell
        self.trace = trace
        self.window = window
        peaks = load_json("peaks.json")["devices"]
        if device_kind not in peaks:
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           "peaks.json")
        self.peaks = peaks[device_kind]

    def flops(self, name: str):
        return importlib.import_module(f"flops.{name}")

    def samples_per_round(self) -> float:
        """Local-SGD samples trained per round, estimated from the stats
        the block pull carries (cohort means only): B x K x mean uploaded
        epochs x mean over the cohort of ceil(n_k / B).  The workload draw
        is independent of client size, so the estimate is unbiased."""
        w = self.window
        B = int(self.cell.traffic["server"]["batch_size"])
        n = np.minimum(w["sizes"][w["ids"]], w["max_n"])
        tau = np.ceil(n / B).mean(axis=1)                  # [rounds]
        K = w["ids"].shape[1]
        return float(np.mean(B * K * w["uploaded"] * tau))


def result_line(result: Dict) -> str:
    """The last stdout line; the compared numbers go last."""
    checks = result.pop("checks", {})
    out = dict(result)
    out["checks"] = {k: [v["value"], v["limit"]] for k, v in checks.items()}
    return json.dumps(out)


def print_checks(result: Dict, stream=sys.stderr):
    for k, v in result.get("checks", {}).items():
        ok = v["value"] <= v["limit"]
        print(f"check {k} {v['value']!r} limit {v['limit']!r} "
              f"{'ok' if ok else 'FAIL'}", file=stream)
    print(f"correct {str(result['correct']).lower()}", file=stream,
          flush=True)
