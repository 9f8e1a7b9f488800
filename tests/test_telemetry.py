"""Federation telemetry subsystem (ISSUE 7, ``repro.obs``).

Four layers of proof:

  * schema: RoundRecord JSONL round-trips NaN-safely (null <-> NaN through
    the typed field table), rejects malformed lines with line numbers, and
    the numpy histogram twin bins identically to the device formula;
  * inertness: enabling telemetry changes NOTHING about training — final
    params and the history view are bitwise identical to a telemetry-off
    run on both drivers and both backends (the telemetry-off program in
    turn is the unchanged pre-ISSUE-7 one: the stats extras are gated out
    of the traced function entirely);
  * cost: the scan driver still performs exactly ONE ``jax.device_get``
    per block with telemetry on — the extras ride the existing stats pull;
  * end-to-end: host- and scan-driver telemetry extras agree, the JSONL
    sink's file validates with the right row count, the silo path emits
    through the same sink, and the health report renders from a real run
    (sharded lane-occupancy extras are covered at S=1 always and S=8 under
    the CI multi-device job).
"""
import contextlib
import json
import math
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FedSAEServer, HeterogeneitySim, ServerConfig
from repro.core import engine
from repro.core.engine import _device_hist
from repro.data.federated import make_femnist_like
from repro.models.fl_models import make_mclr
from repro.obs import (HISTORY_KEYS, LOSS_HIST_BINS, LOSS_HIST_MAX,
                       JsonlSink, NullSink, RingBufferSink, RoundRecord,
                       SchemaError, histogram_counts, read_jsonl,
                       record_from_row, render_report)
from repro.obs import profiling

N_CLIENTS = 24
DIM = 16
ROUNDS = 8
BLOCK = 4
N_DEVICES = len(jax.devices())

needs_devices = lambda n: pytest.mark.skipif(  # noqa: E731
    N_DEVICES < n, reason=f"needs {n} (simulated) devices, have {N_DEVICES};"
    " set REPRO_FORCE_HOST_DEVICES / XLA_FLAGS before jax initializes")


@pytest.fixture(scope="module")
def fed():
    ds = make_femnist_like(n_clients=N_CLIENTS, total=1400, dim=DIM,
                           max_size=60)
    return ds, make_mclr(DIM, ds.n_classes)


def _server(fed, driver, backend="xla", shards=0, sink=None, telemetry=None):
    ds, model = fed
    cfg = ServerConfig(algo="ira", n_selected=8, rounds=ROUNDS, h_cap=4.0,
                       fixed_epochs=4.0, sampling="iid", driver=driver,
                       block_size=BLOCK, backend=backend,
                       mesh_shards=shards,
                       rng_impl="device" if driver == "host" else "")
    return FedSAEServer(ds, model, cfg,
                        het=HeterogeneitySim(ds.n_clients, seed=0),
                        sink=sink, telemetry=telemetry)


_RUNS = {}


@contextlib.contextmanager
def _no_scope(name):
    yield


@contextlib.contextmanager
def _no_span(name, log, block):
    yield


def _run(fed, driver, backend="xla", shards=0, telemetry=False,
         scoped=True):
    """Completed run, memoized per configuration (params, history, server).
    ``scoped=False`` builds and runs the server with the ``fed.*`` stage
    scopes and the host loop's spans stripped out."""
    key = (driver, backend, shards, telemetry, scoped)
    if key not in _RUNS:
        with contextlib.ExitStack() as stack:
            if not scoped:
                stack.enter_context(mock.patch.object(engine, "stage",
                                                      _no_scope))
                stack.enter_context(mock.patch.object(
                    profiling, "host_span", _no_span))
            srv = _server(fed, driver, backend, shards, telemetry=telemetry)
            srv.run()
        _RUNS[key] = srv
    return _RUNS[key]


# ---------------------------------------------------------------------------
# schema: NaN-safe JSONL round-trip + validation
# ---------------------------------------------------------------------------


def test_roundrecord_roundtrip_nan_safe():
    rec = RoundRecord(round=3, acc=0.5, test_loss=float("nan"),
                      train_loss=1.25, dropout=0.125, assigned=2.0,
                      uploaded=1.5, true_workload=1.75, overflowed=0.0,
                      dropped=1.0, wall_time_s=0.01,
                      ids=[4, 9, 11], client_uploaded=[1, 0, 1],
                      upload_bytes=1024.0, dense_upload_bytes=4096.0,
                      loss_hist=[0.0, 2.0, 1.0], workload_hist=[3.0],
                      lane_occupancy=[0.5, 1.0])
    line = rec.to_json()
    # strict JSON: the NaN field must be encoded as null, never "NaN"
    assert "NaN" not in line
    assert json.loads(line)["test_loss"] is None
    back = RoundRecord.from_json(line)
    assert math.isnan(back.test_loss)
    assert back == rec                  # NaN-aware equality
    # and a second trip is stable
    assert RoundRecord.from_json(back.to_json()) == rec


def test_roundrecord_all_nan_roundtrip():
    rec = record_from_row(0, {})        # every scalar NaN, extras absent
    back = RoundRecord.from_json(rec.to_json())
    assert back == rec
    assert back.ids is None and back.loss_hist is None


@pytest.mark.parametrize("line", [
    "not json",
    "[1, 2]",                                   # not an object
    '{"acc": 0.5}',                             # missing round
    '{"round": true}',                          # bool is not an int
    '{"round": 1, "acc": "high"}',              # non-numeric scalar
    '{"round": 1, "ids": [1, "a"]}',            # non-numeric list entry
    '{"round": 1, "nonsense": 3}',              # unknown field
])
def test_roundrecord_rejects(line):
    with pytest.raises(SchemaError):
        RoundRecord.from_json(line)


def test_read_jsonl_meta_and_line_numbers(tmp_path):
    p = tmp_path / "t.jsonl"
    p.write_text('{"_meta": {"algo": "ira"}}\n'
                 + RoundRecord(round=0, acc=0.1).to_json() + "\n"
                 + '{"round": 1, "bogus": 9}\n')
    with pytest.raises(SchemaError, match=r"t\.jsonl:3"):
        read_jsonl(str(p))
    p.write_text('{"_meta": {"algo": "ira"}}\n'
                 + RoundRecord(round=0, acc=0.1).to_json() + "\n")
    meta, recs = read_jsonl(str(p))
    assert meta == {"algo": "ira"} and len(recs) == 1


def test_histogram_twins_agree():
    rng = np.random.default_rng(0)
    x = rng.uniform(-1.0, 10.0, 64).astype(np.float32)  # incl. out-of-range
    w = (rng.uniform(size=64) > 0.3).astype(np.float32)
    host = histogram_counts(x, w, 0.0, LOSS_HIST_MAX, LOSS_HIST_BINS)
    dev = np.asarray(_device_hist(jnp.asarray(x), jnp.asarray(w), 0.0,
                                  LOSS_HIST_MAX, LOSS_HIST_BINS))
    np.testing.assert_array_equal(host, dev)
    assert host.sum() == w.sum()        # clipping loses no mass


# ---------------------------------------------------------------------------
# inertness: telemetry on == telemetry off, bitwise, drivers x backends
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("driver", ["host", "scan"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_telemetry_is_numerically_inert(fed, driver, backend):
    """Metric accumulation must not perturb training: final params and the
    history view are BITWISE identical with telemetry on vs off (and the
    off program is the unchanged untelemetered one — the extras are gated
    out of the traced stats entirely).  The same holds for the ``fed.*``
    stage scopes and the host loop's ``fed.host.*`` spans: a run with
    them stripped out is bitwise the instrumented one."""
    off = _run(fed, driver, backend, telemetry=False)
    on = _run(fed, driver, backend, telemetry=True)
    bare = _run(fed, driver, backend, telemetry=False, scoped=False)
    for other in (on, bare):
        for a, b in zip(jax.tree.leaves(off.params),
                        jax.tree.leaves(other.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        ha, hb = off.history, other.history
        assert list(ha) == list(hb) == list(HISTORY_KEYS)
        for k in ha:
            np.testing.assert_array_equal(np.asarray(ha[k]),
                                          np.asarray(hb[k]))
    assert all(s > 0 for s in off.history["local_steps"])
    assert not bare.host_spans
    if driver == "scan":
        assert len(off.host_spans) == 5 * ROUNDS // BLOCK
    # ...and the on-run actually recorded the extras
    for rec in on._records.records:
        assert rec.client_uploaded is not None
        assert rec.loss_hist is not None and rec.workload_hist is not None


def test_host_scan_telemetry_extras_agree(fed):
    """The host driver's numpy extras match the scan driver's
    device-accumulated ones round for round (same binning, same ledger)."""
    host = _run(fed, "host", telemetry=True)
    scan = _run(fed, "scan", telemetry=True)
    hr, sr = host._records.records, scan._records.records
    assert len(hr) == len(sr) == ROUNDS
    for a, b in zip(hr, sr):
        assert a.ids == b.ids
        assert a.client_uploaded == b.client_uploaded
        assert a.upload_bytes == b.upload_bytes
        assert a.dense_upload_bytes == b.dense_upload_bytes
        assert a.workload_hist == b.workload_hist
        np.testing.assert_allclose(a.loss_hist, b.loss_hist, atol=1e-6)


# ---------------------------------------------------------------------------
# cost: one host pull per block, telemetry on
# ---------------------------------------------------------------------------


def test_scan_driver_one_device_get_per_block(fed, monkeypatch):
    """The regression the ISSUE hard-requires: with telemetry ON the scan
    driver still issues exactly ONE jax.device_get per block — the extras
    ride the existing stats pull instead of adding transfers."""
    srv = _server(fed, "scan", telemetry=True)
    calls = {"n": 0}
    real = jax.device_get

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    srv.run()
    n_blocks = ROUNDS // BLOCK
    assert calls["n"] == n_blocks
    # host_syncs bookkeeping: one stats pull per block + one eval readback
    # per due block (eval_every=1 -> every block)
    assert srv.host_syncs == 2 * n_blocks


# ---------------------------------------------------------------------------
# end-to-end: sinks, sharded lane occupancy, silo path, report
# ---------------------------------------------------------------------------


def test_jsonl_sink_end_to_end(fed, tmp_path):
    path = str(tmp_path / "run.jsonl")
    sink = JsonlSink(path, meta={"algo": "ira", "rounds": ROUNDS})
    srv = _server(fed, "scan", sink=sink)
    assert srv.telemetry        # a sink switches accumulation on by default
    srv.run()
    sink.close()
    meta, recs = read_jsonl(path)
    assert meta == {"algo": "ira", "rounds": ROUNDS}
    assert len(recs) == ROUNDS
    assert [r.round for r in recs] == list(range(ROUNDS))
    # the file IS the ring buffer (same records through the same path)
    assert recs == srv._records.records
    # eval cadence survives the round-trip: non-block-end rounds carry a
    # NaN test_loss, block ends a real one
    assert math.isnan(recs[0].test_loss)
    assert math.isfinite(recs[BLOCK - 1].test_loss)
    report = render_report(meta, recs)
    for section in ("Round summary", "Stragglers", "Per-client reliability",
                    "Upload ledger", "Throughput"):
        assert section in report
    assert "_No per-client telemetry" not in report
    assert "compression saved" in report or "shipped" in report


def test_history_view_backcompat(fed):
    """``history`` is a property now, but every pre-ISSUE-7 consumer must
    see the same dict-of-lists: key order, lengths and NaN-fill."""
    srv = _run(fed, "host")
    hist = srv.history
    assert list(hist) == list(HISTORY_KEYS)
    assert all(len(v) == ROUNDS for v in hist.values())
    assert all(isinstance(x, float) for v in hist.values() for x in v)


@pytest.mark.parametrize("shards", [
    1, pytest.param(8, marks=needs_devices(8))])
def test_sharded_telemetry_lane_occupancy(fed, shards):
    srv = _run(fed, "scan", shards=shards, telemetry=True)
    for rec in srv._records.records:
        occ = rec.lane_occupancy
        assert occ is not None and len(occ) == shards
        assert all(0.0 <= o <= 1.0 for o in occ)
    # K=8 cohort slots spread over the shards: occupancies must add up
    occ0 = np.asarray(srv._records.records[0].lane_occupancy)
    assert occ0.sum() > 0


def test_silo_path_emits_records():
    from repro.configs import get_config
    from repro.core.silo import SiloFedSAE
    from repro.models.api import build_model
    cfg = get_config("llama3.2-3b", smoke=True)
    model = build_model(cfg)
    ring = RingBufferSink()
    fed_ = SiloFedSAE(model, n_silos=2, lr=5e-3, max_steps=4, sink=ring)
    ri = np.random.default_rng(0)
    toks = np.stack([ri.integers(0, cfg.vocab_size, (4, 2, 32))
                     for _ in range(2)])
    batches = {"tokens": jnp.asarray(toks, jnp.int32),
               "labels": jnp.asarray(toks, jnp.int32)}
    for _ in range(3):
        fed_.run_round(batches, np.array([100, 500]))
    assert len(ring) == 3
    assert [r.round for r in ring.records] == [0, 1, 2]
    rec = ring.last
    assert rec.train_loss == fed_.stats["loss"][-1]
    assert rec.client_uploaded is not None and len(rec.ids) == 2
    assert math.isfinite(rec.wall_time_s)
    # silo records serialize through the same schema
    assert RoundRecord.from_json(rec.to_json()) == rec


def test_null_sink_default_off(fed):
    srv = _server(fed, "host")
    assert isinstance(srv.sink, NullSink) and not srv.telemetry
    srv.run(rounds=2)
    assert srv._records.records[0].client_uploaded is None
