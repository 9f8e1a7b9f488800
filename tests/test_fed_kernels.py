"""Federated Pallas kernels (ISSUE 2): interpret-mode parity between the
``backend="pallas"`` round path and the XLA engine path.

Parity tiers, and why:

  * ``sampling="shuffle"`` rounds must be BIT-IDENTICAL across backends —
    only the gather is fused there, and its padding rows (DMA window tail
    vs XLA clamp-gather neighbours) contribute exactly 0.0 to every masked
    statistic, so not a single bit may move.
  * ``sampling="iid"`` MCLR rounds run the fused local-SGD kernel, which
    sees bit-identical minibatches (same randint draw) but evaluates the
    closed-form softmax-xent gradient with different reduction orders than
    XLA autodiff (one-hot-matmul gather, fused matmul accumulations).  Each
    step's divergence is O(ulp); over ``max_iters`` steps and aggregation we
    allow rtol/atol 2e-5 — observed deltas are ~1e-9 at these scales.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import FedAvg
from repro.core.engine import RoundEngine
from repro.data.federated import make_femnist_like
from repro.kernels import ops, ref
from repro.models.fl_models import make_lstm, make_mclr

RTOL, ATOL = 2e-5, 2e-6


@pytest.fixture(scope="module")
def fed_case():
    ds = make_femnist_like(n_clients=14, total=800, dim=16, max_size=50)
    model = make_mclr(16, ds.n_classes)
    params = model.init(jax.random.PRNGKey(7))
    max_n = int(ds.sizes.max())
    packed = ds.packed(max_n)
    ids = np.array([0, 2, 4, 5, 9, 13])
    n_iters = np.array([0, 1, 3, 6, 2, 4], np.int32)
    rng = jax.random.PRNGKey(3)
    return ds, model, params, packed, ids, max_n, n_iters, rng


def _tree_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _tree_close(a, b, rtol=RTOL, atol=ATOL):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# fused cohort gather
# ---------------------------------------------------------------------------


def test_gather_matches_ref_including_ragged_edges():
    """Window + mask parity with the jnp oracle, covering length == 0,
    length == max_n and interior clients (starts 8-row aligned, as the
    packed layout places them)."""
    rng = np.random.default_rng(0)
    max_n, d = 8, 5
    flat = jnp.asarray(rng.normal(size=(32 + max_n, d)), jnp.float32)
    flat_y = jnp.asarray(rng.integers(0, 4, 32 + max_n), jnp.int32)
    starts = jnp.asarray([0, 8, 16, 24, 32], jnp.int32)
    ns = jnp.asarray([4, 8, 0, 6, 0], jnp.int32)   # max_n, zero-length edges
    x, y, mask = ops.fed_cohort_gather(flat, flat_y, starts, ns, max_n)
    xr, yr, mr = ref.fed_cohort_gather(flat, flat_y, starts, ns, max_n=max_n)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(xr))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(mr))
    assert np.asarray(mask)[1].sum() == max_n    # full client
    assert np.asarray(mask)[2].sum() == 0        # empty client


def test_gather_real_rows_match_xla_clamp_gather(fed_case):
    """Where the mask is 1 (real samples), the kernel must agree with the
    XLA clamp-gather bit for bit; padding rows differ by design and are
    compared only through the mask."""
    ds, model, params, packed, ids, max_n, n_iters, rng = fed_case
    idj = jnp.asarray(ids, jnp.int32)
    starts = packed.offsets[idj]
    n = jnp.minimum(packed.lengths[idj], max_n)
    x, y, mask = ops.fed_cohort_gather(packed.x, packed.y, starts, n, max_n)

    pos = jnp.arange(max_n)
    idx = jnp.minimum(starts[:, None] + pos[None, :], packed.x.shape[0] - 1)
    x_xla, y_xla = packed.x[idx], packed.y[idx]
    mask_xla = (pos[None, :] < n[:, None]).astype(jnp.float32)

    np.testing.assert_array_equal(np.asarray(mask), np.asarray(mask_xla))
    m = np.asarray(mask).astype(bool)
    np.testing.assert_array_equal(np.asarray(x)[m], np.asarray(x_xla)[m])
    np.testing.assert_array_equal(np.asarray(y)[m], np.asarray(y_xla)[m])


def test_gather_handles_higher_rank_features():
    """Sequence-shaped clients (e.g. sent140 tokens) flatten through the
    kernel and come back in their original feature shape."""
    rng = np.random.default_rng(1)
    max_n = 4
    flat = jnp.asarray(rng.integers(0, 99, (16, 3, 2)), jnp.int32)
    flat_y = jnp.asarray(rng.integers(0, 2, 16), jnp.int32)
    starts = jnp.asarray([0, 8], jnp.int32)
    ns = jnp.asarray([4, 3], jnp.int32)
    x, y, mask = ops.fed_cohort_gather(flat, flat_y, starts, ns, max_n)
    assert x.shape == (2, max_n, 3, 2)
    np.testing.assert_array_equal(np.asarray(x)[0], np.asarray(flat)[0:4])
    np.testing.assert_array_equal(np.asarray(x)[1], np.asarray(flat)[8:12])


# ---------------------------------------------------------------------------
# fused masked local SGD
# ---------------------------------------------------------------------------


def test_local_sgd_kernel_matches_ref_oracle():
    rng = np.random.default_rng(2)
    K, max_n, d, C, max_iters, B = 3, 12, 6, 4, 5, 4
    x = jnp.asarray(rng.normal(size=(K, max_n, d)), jnp.float32)
    y = jnp.asarray(rng.integers(0, C, (K, max_n)), jnp.int32)
    ns = jnp.asarray([12, 7, 0], jnp.int32)       # full / ragged / empty
    n_iters = jnp.asarray([5, 3, 0], jnp.int32)   # full / partial / zero
    idx = jnp.asarray(rng.integers(0, 7, (K, max_iters, B)), jnp.int32)
    w0 = jnp.asarray(rng.normal(size=(d, C)) * 0.1, jnp.float32)
    b0 = jnp.zeros(C, jnp.float32)
    for prox_mu in (0.0, 0.2):
        w_k, b_k, losses = ops.fed_local_sgd_mclr(
            x, y, idx, w0, b0, ns, n_iters, lr=0.1, prox_mu=prox_mu)
        wr, br, lr_ = ref.fed_local_sgd_mclr(
            x, y, idx, w0, b0, ns, n_iters, lr=0.1, prox_mu=prox_mu)
        np.testing.assert_allclose(w_k, wr, rtol=RTOL, atol=ATOL)
        np.testing.assert_allclose(b_k, br, rtol=RTOL, atol=ATOL)
        np.testing.assert_allclose(losses, lr_, rtol=RTOL, atol=ATOL)


def test_local_sgd_zero_budget_returns_globals_and_zero_loss():
    """n_iters_k == 0: the kernel must hand back the untouched global params
    (no masked-slot leakage) and a 0.0 loss."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 6, 4)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 3, (2, 6)), jnp.int32)
    idx = jnp.asarray(rng.integers(0, 6, (2, 4, 3)), jnp.int32)
    w0 = jnp.asarray(rng.normal(size=(4, 3)), jnp.float32)
    b0 = jnp.asarray(rng.normal(size=3), jnp.float32)
    w_k, b_k, losses = ops.fed_local_sgd_mclr(
        x, y, idx, w0, b0, jnp.asarray([6, 6], jnp.int32),
        jnp.zeros(2, jnp.int32), lr=0.5)
    for k in range(2):
        np.testing.assert_array_equal(np.asarray(w_k[k]), np.asarray(w0))
        np.testing.assert_array_equal(np.asarray(b_k[k]), np.asarray(b0))
    np.testing.assert_array_equal(np.asarray(losses), np.zeros(2))


# ---------------------------------------------------------------------------
# round-level backend parity
# ---------------------------------------------------------------------------


def _round_args(packed, ids, n_iters, rng):
    return (packed.x, packed.y, packed.offsets, packed.lengths,
            jnp.asarray(ids, jnp.int32), jnp.asarray(n_iters), rng)


def test_packed_round_pallas_shuffle_is_bitwise(fed_case):
    ds, model, params, packed, ids, max_n, n_iters, rng = fed_case
    eng = RoundEngine(lr=0.05, aggregator=FedAvg(), donate=False)
    fx = eng.make_packed_round(model, 10, 6, max_n, sampling="shuffle")
    fp = eng.make_packed_round(model, 10, 6, max_n, sampling="shuffle",
                               backend="pallas")
    p_a, l_a, u_a = fx(params, *_round_args(packed, ids, n_iters, rng))
    p_b, l_b, u_b = fp(params, *_round_args(packed, ids, n_iters, rng))
    _tree_equal(p_a, p_b)
    np.testing.assert_array_equal(np.asarray(l_a), np.asarray(l_b))
    assert bool(u_a) == bool(u_b)


def test_packed_round_pallas_iid_matches_xla_within_tolerance(fed_case):
    ds, model, params, packed, ids, max_n, n_iters, rng = fed_case
    eng = RoundEngine(lr=0.05, aggregator=FedAvg(), donate=False)
    fx = eng.make_packed_round(model, 10, 6, max_n, sampling="iid")
    fp = eng.make_packed_round(model, 10, 6, max_n, sampling="iid",
                               backend="pallas")
    p_a, l_a, _ = fx(params, *_round_args(packed, ids, n_iters, rng))
    p_b, l_b, _ = fp(params, *_round_args(packed, ids, n_iters, rng))
    _tree_close(p_a, p_b)
    np.testing.assert_allclose(np.asarray(l_a), np.asarray(l_b),
                               rtol=RTOL, atol=ATOL)


def test_padded_round_pallas_iid_matches_xla_within_tolerance(fed_case):
    ds, model, params, packed, ids, max_n, n_iters, rng = fed_case
    x, y, mask, n = ds.stacked(ids, max_n)
    args = (jnp.asarray(x), jnp.asarray(y), jnp.asarray(mask),
            jnp.asarray(n, jnp.int32), jnp.asarray(n_iters), rng)
    eng = RoundEngine(lr=0.05, aggregator=FedAvg(), donate=False)
    p_a, l_a, _ = eng.make_padded_round(model, 10, 6, sampling="iid")(
        params, *args)
    p_b, l_b, _ = eng.make_padded_round(model, 10, 6, sampling="iid",
                                        backend="pallas")(params, *args)
    _tree_close(p_a, p_b)
    np.testing.assert_allclose(np.asarray(l_a), np.asarray(l_b),
                               rtol=RTOL, atol=ATOL)


def test_pallas_iid_round_with_prox_matches_xla(fed_case):
    """FedProx local objectives run through the fused kernel's analytic
    proximal gradient."""
    ds, model, params, packed, ids, max_n, n_iters, rng = fed_case
    eng = RoundEngine(lr=0.05, aggregator=FedAvg(), prox_mu=0.3,
                      donate=False)
    fx = eng.make_packed_round(model, 10, 6, max_n, sampling="iid")
    fp = eng.make_packed_round(model, 10, 6, max_n, sampling="iid",
                               backend="pallas")
    p_a, l_a, _ = fx(params, *_round_args(packed, ids, n_iters, rng))
    p_b, l_b, _ = fp(params, *_round_args(packed, ids, n_iters, rng))
    _tree_close(p_a, p_b)
    np.testing.assert_allclose(np.asarray(l_a), np.asarray(l_b),
                               rtol=RTOL, atol=ATOL)


def test_pallas_backend_falls_back_for_non_mclr_model():
    """An LSTM cohort (no fused SGD kernel) still accepts backend="pallas":
    the gather kernel runs, the scan path handles SGD, and the result is
    bit-identical to XLA."""
    rng = np.random.default_rng(4)
    n_clients, max_n, seq = 6, 10, 5
    sizes = rng.integers(3, max_n + 1, n_clients)
    xs = [rng.integers(0, 50, (s, seq)).astype(np.int32) for s in sizes]
    ys = [rng.integers(0, 2, s).astype(np.int32) for s in sizes]
    from repro.data.federated import FederatedDataset
    ds = FederatedDataset("toy", xs, ys, xs[0], ys[0], 2, task="text")
    model = make_lstm(vocab=50)
    params = model.init(jax.random.PRNGKey(0))
    packed = ds.packed(max_n)
    ids = np.arange(4)
    n_iters = np.array([2, 0, 1, 2], np.int32)
    key = jax.random.PRNGKey(9)

    eng = RoundEngine(lr=0.1, aggregator=FedAvg(), donate=False)
    fx = eng.make_packed_round(model, 4, 2, max_n)
    fp = eng.make_packed_round(model, 4, 2, max_n, backend="pallas")
    p_a, l_a, _ = fx(params, *_round_args(packed, ids, n_iters, key))
    p_b, l_b, _ = fp(params, *_round_args(packed, ids, n_iters, key))
    _tree_equal(p_a, p_b)
    np.testing.assert_array_equal(np.asarray(l_a), np.asarray(l_b))


def test_pallas_round_zero_upload_keeps_globals(fed_case):
    ds, model, params, packed, ids, max_n, _, rng = fed_case
    eng = RoundEngine(lr=0.05, aggregator=FedAvg(), donate=False)
    fp = eng.make_packed_round(model, 10, 6, max_n, sampling="iid",
                               backend="pallas")
    zeros = np.zeros(len(ids), np.int32)
    p, _, any_up = fp(params, *_round_args(packed, ids, zeros, rng))
    assert not bool(any_up)
    _tree_equal(params, p)


def test_server_pallas_backend_matches_xla_end_to_end():
    """FedSAEServer with cfg.backend="pallas" (shuffle sampling) reproduces
    the XLA server bit for bit over multiple rounds."""
    from repro.core import FedSAEServer, HeterogeneitySim, ServerConfig
    ds = make_femnist_like(n_clients=16, total=900, dim=16, max_size=50)
    model = make_mclr(16, ds.n_classes)
    servers = []
    for backend in ("xla", "pallas"):
        cfg = ServerConfig(algo="ira", n_selected=6, rounds=2, h_cap=4.0,
                           backend=backend)
        srv = FedSAEServer(ds, model, cfg,
                           het=HeterogeneitySim(ds.n_clients, seed=0))
        for t in range(cfg.rounds):
            srv.run_round(t)
        servers.append(srv)
    _tree_equal(servers[0].params, servers[1].params)


def test_unknown_backend_rejected(fed_case):
    ds, model, params, packed, ids, max_n, n_iters, rng = fed_case
    with pytest.raises(ValueError, match="unknown backend"):
        RoundEngine(lr=0.1, backend="cuda")
    eng = RoundEngine(lr=0.1)
    with pytest.raises(ValueError, match="unknown backend"):
        eng.make_packed_round(model, 10, 6, max_n, backend="tpu")
    with pytest.raises(ValueError, match="unknown backend"):
        eng.make_stream_round(lambda p, b: 0.0, 4, backend="triton")


# ---------------------------------------------------------------------------
# fused upload compression (ISSUE 6)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [0, 1, 7, 20, 21])
def test_compress_kernel_matches_ref_bitwise(k):
    """fed_compress parity with the jnp oracle across the k edges (empty
    mask, single coordinate, interior, P-1, full row) — BITWISE: int8
    codes, scales and the implied transmitted values must all agree."""
    rng = np.random.default_rng(5)
    K, P = 6, 21
    ef = rng.normal(size=(K, P)).astype(np.float32)
    ef[1] = 0.0                              # zero row: scale == 0 branch
    ef[2, :10] = ef[2, 10]                   # heavy magnitude ties
    ef = jnp.asarray(ef)
    q, s = ops.fed_compress_topk_q8(ef, k)
    qr, sr = ref.fed_compress_topk_q8(ef, k=k)
    assert q.dtype == jnp.int8 and qr.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))
    nz = (np.asarray(q) != 0).sum(axis=1)
    assert (nz <= max(k, 0)).all()           # never more than k coords
    assert np.asarray(s)[1] == 0.0 and (np.asarray(q)[1] == 0).all()


def test_compress_kernel_matches_ref_under_jit():
    """The parity must survive jit on both sides — a constant-divisor
    scale would be rewritten to a reciprocal-multiply under jit but not
    eagerly, so this guards the explicit-multiply formulation."""
    ef = jnp.asarray(np.random.default_rng(9).normal(size=(4, 33)),
                     jnp.float32)
    for k in (0, 5, 33):
        q, s = jax.jit(ops.fed_compress_topk_q8,
                       static_argnums=1)(ef, k)
        qr, sr = jax.jit(lambda e: ref.fed_compress_topk_q8(e, k=k))(ef)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))
