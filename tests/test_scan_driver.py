"""Fused multi-round scan driver (ISSUE 3): host-vs-scan parity, float32
state pinning (with and without jax_enable_x64), device selection, the
crash-heavy degenerate round, and the ValueTracker empty-update guard."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FedSAEServer, HeterogeneitySim, ServerConfig
from repro.core.engine import budget_iters
from repro.core.selection import (ValueTracker, select_cohort_device,
                                  value_update_device)
from repro.data.federated import make_femnist_like
from repro.models.fl_models import make_mclr

N_CLIENTS = 24
DIM = 16


@pytest.fixture(scope="module")
def small_fed():
    ds = make_femnist_like(n_clients=N_CLIENTS, total=1400, dim=DIM,
                           max_size=60)
    return ds, make_mclr(DIM, ds.n_classes)


def _server(ds, model, driver, algo="ira", het=None, sampling="iid", **over):
    cfg = ServerConfig(algo=algo, n_selected=8, rounds=8, h_cap=4.0,
                       fixed_epochs=4.0, sampling=sampling, driver=driver,
                       block_size=4,
                       rng_impl="device" if driver == "host" else "",
                       **over)
    return FedSAEServer(ds, model, cfg,
                        het=het or HeterogeneitySim(ds.n_clients, seed=0))


def _assert_params_close(a, b, atol=1e-5):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol)


# ---------------------------------------------------------------------------
# driver parity: scan == host with the device rng streams
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("algo", ["ira", "fassa"])
def test_scan_matches_host_driver(small_fed, algo):
    """driver="scan" reproduces driver="host" (device rng): identical
    cohort sequences, final params within 1e-5, identical history arrays."""
    ds, model = small_fed
    host = _server(ds, model, "host", algo)
    scan = _server(ds, model, "scan", algo)
    host.run()
    scan.run()

    assert len(host.cohorts) == len(scan.cohorts) == 8
    for a, b in zip(host.cohorts, scan.cohorts):
        np.testing.assert_array_equal(a, b)
    _assert_params_close(host.params, scan.params)
    np.testing.assert_allclose(host.L, scan.L, atol=1e-5)
    np.testing.assert_allclose(host.H, scan.H, atol=1e-5)
    np.testing.assert_allclose(host.theta, scan.theta, atol=1e-5)
    np.testing.assert_allclose(host.values.v, scan.values.v, rtol=1e-5)
    for k in ("dropout", "assigned", "uploaded", "true_workload"):
        np.testing.assert_allclose(host.history[k], scan.history[k],
                                   rtol=1e-5, atol=1e-6)


def test_scan_matches_host_driver_shuffle_sampling(small_fed):
    """The seed-exact shuffle minibatch rule also composes under the scan
    (gather-based round body)."""
    ds, model = small_fed
    host = _server(ds, model, "host", sampling="shuffle")
    scan = _server(ds, model, "scan", sampling="shuffle")
    host.run(rounds=4)
    scan.run(rounds=4)
    for a, b in zip(host.cohorts, scan.cohorts):
        np.testing.assert_array_equal(a, b)
    _assert_params_close(host.params, scan.params)


def test_scan_partial_final_block(small_fed):
    """T not divisible by block_size: the tail block is shorter, history
    still has one row per round."""
    ds, model = small_fed
    scan = _server(ds, model, "scan")
    scan.run(rounds=6)   # block_size=4 -> blocks of 4 and 2
    assert len(scan.history["dropout"]) == 6
    assert len(scan.cohorts) == 6
    assert np.isfinite(scan.history["acc"][-1])


def test_scan_host_sync_budget(small_fed):
    """The scan driver pulls from device once per block (plus the block
    eval), not once per round."""
    ds, model = small_fed
    host = _server(ds, model, "host")
    scan = _server(ds, model, "scan")
    host.run()
    scan.run()
    assert host.host_syncs >= 8          # >= one per round
    assert scan.host_syncs == 2 * 2      # 2 blocks x (stats pull + eval)


def test_scan_respects_eval_every(small_fed):
    """Blocks with no eval-due round skip the test-set eval entirely and
    carry the previous accuracy forward."""
    ds, model = small_fed
    scan = _server(ds, model, "scan", eval_every=100)
    scan.run(rounds=12)   # blocks of 4: 0-3 (t=0 due), 4-7 (skip), 8-11 (final)
    assert scan.host_syncs == 3 + 2        # 3 stats pulls + 2 evals
    assert len(scan.history["acc"]) == 12
    assert scan.history["acc"][7] == scan.history["acc"][3]
    assert np.isnan(scan.history["test_loss"][7])
    assert np.isfinite(scan.history["test_loss"][11])


def test_scan_crash_heavy_round(small_fed):
    """A heterogeneity regime where every client always crashes (E ~ 0):
    nobody uploads, params stay at init, the value tracker is untouched,
    and neither driver divides by zero."""
    ds, model = small_fed
    crash = dict(mu_range=(0.0, 1e-3), sigma_frac=(0.0, 1e-3))
    host = _server(ds, model, "host",
                   het=HeterogeneitySim(ds.n_clients, seed=0, **crash))
    scan = _server(ds, model, "scan",
                   het=HeterogeneitySim(ds.n_clients, seed=0, **crash))
    p0 = jax.tree.map(np.asarray, scan.params)
    v0 = scan.values.v.copy()
    host.run()
    scan.run()
    assert np.allclose(host.history["dropout"], 1.0)
    assert np.allclose(scan.history["dropout"], 1.0)
    for x, y in zip(jax.tree.leaves(p0), jax.tree.leaves(scan.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # untouched up to the float32 round-trip the device carry imposes
    np.testing.assert_array_equal(v0.astype(np.float32),
                                  scan.values.v.astype(np.float32))
    np.testing.assert_array_equal(v0.astype(np.float32),
                                  host.values.v.astype(np.float32))
    assert all(np.isnan(host.history["train_loss"]))
    assert all(np.isnan(scan.history["train_loss"]))


# ---------------------------------------------------------------------------
# float32 state pinning — with and without jax_enable_x64
# ---------------------------------------------------------------------------


def _state_dtypes(srv):
    st = srv.device_state()
    return {k: st[k].dtype for k in ("L", "H", "theta", "values")}


def test_scan_state_is_float32(small_fed):
    ds, model = small_fed
    scan = _server(ds, model, "scan")
    assert all(dt == jnp.float32 for dt in _state_dtypes(scan).values())
    scan.run(rounds=4)
    # ...and stays float32 after blocks have been absorbed back
    assert all(dt == jnp.float32 for dt in _state_dtypes(scan).values())


def test_scan_driver_runs_under_x64(small_fed):
    """jax_enable_x64 must not widen the scan carry: L/H/theta/values stay
    pinned float32 and the driver still runs end to end."""
    ds, model = small_fed
    with jax.enable_x64(True):
        scan = _server(ds, model, "scan")
        assert all(dt == jnp.float32
                   for dt in _state_dtypes(scan).values())
        hist = scan.run(rounds=4)
        assert np.isfinite(hist["acc"][-1])
        assert all(dt == jnp.float32
                   for dt in _state_dtypes(scan).values())


def test_prediction_device_parity_under_x64():
    """The float32 twins agree with the float64 numpy originals to 1e-6
    regardless of the x64 flag (satellite: explicit scan-state dtypes)."""
    from repro.core import prediction as pred
    rng = np.random.default_rng(3)
    L = rng.uniform(0.5, 10.0, 64).astype(np.float32)
    H = (L + rng.uniform(0.1, 10.0, 64)).astype(np.float32)
    E = rng.uniform(0.0, 25.0, 64).astype(np.float32)
    th = rng.uniform(0.0, 20.0, 64).astype(np.float32)

    def check():
        L2, H2, out = pred.ira_predict(L, H, E, U=10.0, h_cap=24.0)
        L2d, H2d, outd = pred.ira_predict_device(L, H, E, U=10.0, h_cap=24.0)
        np.testing.assert_allclose(np.asarray(L2d), L2, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(H2d), H2, rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(outd), out)
        assert np.asarray(L2d).dtype == np.float32

    check()
    with jax.enable_x64(True):
        check()


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------


def test_unknown_driver_rejected(small_fed):
    ds, model = small_fed
    with pytest.raises(ValueError, match="unknown driver"):
        FedSAEServer(ds, model, ServerConfig(driver="async"))


def test_scan_driver_requires_device_rng(small_fed):
    ds, model = small_fed
    with pytest.raises(ValueError, match="device rng"):
        FedSAEServer(ds, model,
                     ServerConfig(driver="scan", rng_impl="numpy"))


# ---------------------------------------------------------------------------
# device selection + value update primitives
# ---------------------------------------------------------------------------


def test_select_cohort_device_distinct_and_in_range():
    key = jax.random.PRNGKey(0)
    for strategy in ("random", "active", "loss_proportional"):
        ids = np.asarray(select_cohort_device(
            key, jnp.ones(50), 10, strategy, 0.01))
        assert len(set(ids.tolist())) == 10
        assert (ids >= 0).all() and (ids < 50).all()
    with pytest.raises(ValueError, match="unknown selection"):
        select_cohort_device(key, jnp.ones(50), 10, "round_robin", 0.01)


def test_select_cohort_device_active_prefers_high_values():
    v = np.zeros(100, np.float32)
    v[:10] = 500.0
    counts = np.zeros(100)
    for r in range(200):
        ids = np.asarray(select_cohort_device(
            jax.random.PRNGKey(r), jnp.asarray(v), 10, "active", 0.05))
        counts[ids] += 1
    assert counts[:10].mean() > 5 * counts[10:].mean()


def test_select_cohort_device_al_flag_overrides_strategy():
    """use_al=True must reproduce the active strategy bit for bit, whatever
    the configured strategy is (the in-block al_rounds boundary)."""
    v = jnp.asarray(np.random.default_rng(0).uniform(0, 100, 40), jnp.float32)
    key = jax.random.PRNGKey(7)
    active = np.asarray(select_cohort_device(key, v, 8, "active", 0.05))
    forced = np.asarray(select_cohort_device(key, v, 8, "random", 0.05,
                                             use_al=True))
    np.testing.assert_array_equal(active, forced)


def test_value_update_device_matches_tracker_and_skips_non_uploaders():
    sizes = np.array([4.0, 9.0, 16.0, 25.0, 36.0])
    tracker = ValueTracker(5, sizes)
    v0 = jnp.asarray(tracker.v, jnp.float32)
    ids = jnp.array([1, 3], jnp.int32)
    losses = jnp.array([10.0, 20.0], jnp.float32)
    out = np.asarray(value_update_device(
        v0, jnp.asarray(sizes), ids, losses, jnp.array([True, False])))
    tracker.update([1], [10.0])
    np.testing.assert_allclose(out, tracker.v, rtol=1e-6)   # id 3 untouched


def test_value_tracker_empty_update_is_noop():
    """Regression (ISSUE 3 satellite): a round where every selected client
    crashes passes an empty id list — the tracker must return unchanged
    instead of indexing/averaging an empty slice."""
    t = ValueTracker(4, np.array([1.0, 4.0, 9.0, 16.0]))
    before = t.v.copy()
    t.update([], [])
    np.testing.assert_array_equal(t.v, before)
    t.update(np.array([], np.int64), np.array([]))
    np.testing.assert_array_equal(t.v, before)


def test_budget_iters_matches_host_formula():
    rng = np.random.default_rng(1)
    e_eff = rng.uniform(0, 6, 32).astype(np.float32)
    n = rng.integers(1, 60, 32)
    got = np.asarray(budget_iters(e_eff, n, 10, 24))
    tau = np.ceil(n / 10).astype(np.float32)
    want = np.minimum(np.round(e_eff * tau), 24).astype(np.int32)
    np.testing.assert_array_equal(got, want)
