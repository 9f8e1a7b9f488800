"""Stage attribution and host-loop spans (``repro.obs.profiling``).

  * ``stage_map`` reads each compiled instruction's innermost ``fed.*``
    scope back from HLO text, on a synthetic module and on a CPU compile of
    the scan segment, where every named stage of the round owns ops;
  * the scan loop logs its five ``fed.host.*`` phases once per block, in
    order, into a bounded log;
  * ``local_steps`` counts the minibatch steps the cohort trained: it
    equals an independent sum of the budget formula, and the host and scan
    drivers agree on it.
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CommConfig, ComputeConfig, FedSAEServer,
                        HeterogeneitySim, ServerConfig)
from repro.core import server as server_mod
from repro.data.federated import make_femnist_like
from repro.models.fl_models import make_mclr
from repro.obs import HOST_PHASES, profiling, stage, stage_map

N_CLIENTS = 24
DIM = 16
BLOCK = 4


@pytest.fixture(scope="module")
def fed():
    ds = make_femnist_like(n_clients=N_CLIENTS, total=1400, dim=DIM,
                           max_size=60)
    return ds, make_mclr(DIM, ds.n_classes)


def _server(fed, driver, algo="ira", telemetry=None, **server):
    ds, model = fed
    cfg = ServerConfig(
        algo=algo, n_selected=8, rounds=8, h_cap=4.0, fixed_epochs=2.0,
        sampling="iid", **server,
        compute=ComputeConfig(
            driver=driver, block_size=BLOCK,
            rng_impl="device" if driver == "host" else ""))
    return FedSAEServer(ds, model, cfg,
                        het=HeterogeneitySim(ds.n_clients, seed=0),
                        telemetry=telemetry)


# ---------------------------------------------------------------------------
# stage_map
# ---------------------------------------------------------------------------

_HLO = """\
HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/fed.select/mul"}
}

ENTRY %main.9 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0)
  %add.2 = f32[4]{0} add(%x.1, %x.1), metadata={op_name="jit(step)/while/body/fed.predict/fed.aggregate/add" source_file="a.py" source_line=3}
  %fusion.3 = f32[4]{0} fusion(%add.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/fed.select/mul"}
  %fed.local_sgd.4 = f32[4]{0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/fed.local_sgd/pallas_call"}
  %neg.5 = f32[4]{0} negate(%fed.local_sgd.4), metadata={op_name="jit(step)/transpose(jvp(fed.gather))/neg"}
  %copy.6 = f32[4]{0} copy(%neg.5), metadata={op_name="jit(step)/fed.local_sgd.pallas/copy"}
  ROOT %sub.7 = f32[4]{0} subtract(%copy.6, %x.1), metadata={op_name="jit(step)/sub"}
}
"""


def test_stage_map_innermost_scope_wins():
    module, stages = stage_map(_HLO)
    assert module == "jit_step"
    assert stages["add.2"] == "fed.aggregate"        # innermost wins
    assert stages["fusion.3"] == "fed.select"
    assert stages["mul.1"] == "fed.select"           # inside the fusion
    assert stages["fed.local_sgd.4"] == "fed.local_sgd"
    assert stages["neg.5"] == "fed.gather"           # inside a transform
    assert stages["copy.6"] is None                  # not a whole component
    assert stages["sub.7"] is None and stages["x.1"] is None


def test_stage_map_of_nested_scopes_in_a_compiled_program():
    def f(x):
        with stage("fed.select"):
            y = jnp.sin(x)
            with stage("fed.aggregate"):
                y = y * 3.0 + 1.0
        return y

    x = jnp.arange(8.0)
    module, stages = stage_map(jax.jit(f).lower(x).compile().as_text())
    assert module.startswith("jit_f")
    assert "fed.aggregate" in stages.values()
    assert set(stages.values()) <= {None, "fed.select", "fed.aggregate"}


@pytest.mark.parametrize("backend,compress", [("xla", "none"),
                                              ("pallas", "topk_q8")])
def test_segment_stage_map_covers_every_round_stage(fed, backend, compress):
    """A CPU compile of the scan segment: each stage of the round owns at
    least one instruction, and the kernels' stages hold their ops."""
    ds, model = fed
    cfg = ServerConfig(
        algo="fassa", n_selected=8, rounds=8, h_cap=4.0, sampling="iid",
        compute=ComputeConfig(driver="scan", block_size=BLOCK,
                              backend=backend),
        comm=CommConfig(upload_compress=compress, topk_frac=0.1))
    srv = FedSAEServer(ds, model, cfg,
                       het=HeterogeneitySim(ds.n_clients, seed=0))
    module, stages = srv.segment_stage_map()
    assert module == "jit_segment"
    found = collections.Counter(s for s in stages.values() if s)
    want = {"fed.gather", "fed.local_sgd", "fed.aggregate", "fed.select",
            "fed.predict"}
    if compress != "none":
        want.add("fed.upload_transform")
    assert want <= set(found), found
    assert set(found) <= want


def test_segment_stage_map_needs_the_scan_driver(fed):
    with pytest.raises(ValueError, match="scan"):
        _server(fed, "host").segment_stage_map()


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------


def test_host_spans_five_phases_per_block_in_order(fed, tmp_path):
    srv = _server(fed, "scan")
    assert srv.host_spans.maxlen == server_mod.HOST_SPAN_LOG
    srv.run(rounds=3 * BLOCK, checkpoint_dir=str(tmp_path),
            checkpoint_every=BLOCK)
    spans = list(srv.host_spans)
    assert [s[0] for s in spans] == list(HOST_PHASES) * 3
    assert [s[1] for s in spans] == [b for b in range(3)
                                     for _ in HOST_PHASES]
    # back to back on one clock: each phase ends before the next starts
    assert all(t0 <= t1 for _, _, t0, t1 in spans)
    assert all(a[3] <= b[2] for a, b in zip(spans, spans[1:]))
    # a resumed run numbers its blocks on from the checkpoint
    srv2 = _server(fed, "scan")
    srv2.run(rounds=4 * BLOCK, checkpoint_dir=str(tmp_path), resume=True)
    assert [s[1] for s in srv2.host_spans] == [3] * len(HOST_PHASES)


def test_host_span_log_stays_bounded(fed):
    srv = _server(fed, "scan")
    srv.host_spans = collections.deque(maxlen=7)
    srv.run(rounds=3 * BLOCK)
    spans = list(srv.host_spans)
    assert len(spans) == 7
    assert [s[:2] for s in spans[-2:]] == [(profiling.HOST_RECORDS, 2),
                                           (profiling.HOST_CHECKPOINT, 2)]


def test_host_span_logs_name_block_and_times():
    log = []
    with profiling.host_span("fed.host.test", log, 5):
        pass
    ((name, block, t0, t1),) = log
    assert (name, block) == ("fed.host.test", 5) and t0 <= t1


# ---------------------------------------------------------------------------
# local_steps
# ---------------------------------------------------------------------------


def test_local_steps_is_the_budget_sum(fed):
    """FedAvg trains ``fixed_epochs`` on every client that can afford them
    and nothing on the rest, so a round's steps are the budget formula
    min(round(E * ceil(n_k / B)), max_iters) summed over its uploaders."""
    ds, _ = fed
    runs = {}
    for driver in ("host", "scan"):
        srv = _server(fed, driver, algo="fedavg", telemetry=True)
        srv.run()
        runs[driver] = srv
        E, B = srv.cfg.fixed_epochs, srv.cfg.batch_size
        for rec in srv._records.records:
            n = np.minimum(ds.sizes[np.asarray(rec.ids)], srv.max_n)
            steps = np.minimum(np.round(E * np.ceil(n / B)), srv.max_iters)
            want = float(np.sum(steps * np.asarray(rec.client_uploaded)))
            assert rec.local_steps == want
    # some cohort slots dropped, so the upload mask mattered
    assert sum(runs["scan"].history["dropped"]) > 0
    assert sum(runs["scan"].history["local_steps"]) > 0
    assert runs["host"].history["local_steps"] == \
        runs["scan"].history["local_steps"]


@pytest.mark.parametrize("algo", ["ira", "fassa"])
def test_local_steps_host_and_scan_agree(fed, algo):
    host, scan = _server(fed, "host", algo), _server(fed, "scan", algo)
    host.run()
    scan.run()
    steps = scan.history["local_steps"]
    assert len(steps) == 8 and all(s > 0 for s in steps)
    assert host.history["local_steps"] == steps
