"""jit'd public wrappers for the Pallas kernels.

Each model op is a custom_vjp: the forward runs the Pallas kernel, the
backward recomputes through the jnp oracle (flash-style recompute — the
standard memory/compute trade on TPU).  The federated ops at the bottom are
forward-only (round functions are not differentiated through).

The platform decides how a kernel runs: on a TPU every ``pallas_call`` is
compiled by Mosaic, on any other backend it runs in the Pallas
interpreter.  The choice is made when a wrapper is traced, never at import
(``_interpret``); the ``*_fwd`` functions keep an explicit ``interpret=``
argument so tests can force either mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.fed_compress import fed_compress_topk_q8_fwd
from repro.kernels.fed_gather import fed_cohort_gather_fwd
from repro.kernels.fed_local_sgd import fed_local_sgd_mclr_fwd
from repro.kernels.fed_local_sgd_dense import fed_local_sgd_dense_fwd
from repro.kernels.flash_attention import (flash_attention_bwd,
                                           flash_attention_fwd)
from repro.kernels.fused_xent import fused_softmax_xent_fwd
from repro.kernels.selective_scan import selective_scan_fwd


def _interpret() -> bool:
    """Interpret unless the default backend is a TPU.  Called at trace time:
    reading the backend at import would initialise it before callers (and
    the test harness) have chosen their devices."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, window: int = 0):
    out, _ = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                 interpret=_interpret())
    return out


def _fa_fwd(q, k, v, causal, window):
    out, lse = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   interpret=_interpret())
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, window, res, g):
    q, k, v, out, lse = res
    return flash_attention_bwd(q, k, v, out, lse, g, causal=causal,
                               window=window, interpret=_interpret())


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# selective scan
# ---------------------------------------------------------------------------


@jax.custom_vjp
def selective_scan(dt, A, Bmat, Cmat, x, h0):
    return selective_scan_fwd(dt, A, Bmat, Cmat, x, h0,
                              interpret=_interpret())


def _ss_fwd(dt, A, Bmat, Cmat, x, h0):
    return selective_scan(dt, A, Bmat, Cmat, x, h0), (dt, A, Bmat, Cmat, x, h0)


def _ss_bwd(res, g):
    _, vjp = jax.vjp(ref.selective_scan, *res)
    return vjp(g)


selective_scan.defvjp(_ss_fwd, _ss_bwd)


# ---------------------------------------------------------------------------
# fused softmax cross-entropy
# ---------------------------------------------------------------------------


@jax.custom_vjp
def fused_softmax_xent(h, W, labels):
    return fused_softmax_xent_fwd(h, W, labels, interpret=_interpret())


def _fx_fwd(h, W, labels):
    return fused_softmax_xent(h, W, labels), (h, W, labels)


def _fx_bwd(res, g):
    h, W, labels = res
    _, vjp = jax.vjp(lambda h_, W_: ref.softmax_xent(h_, W_, labels), h, W)
    dh, dW = vjp(g)
    return dh, dW, None


fused_softmax_xent.defvjp(_fx_fwd, _fx_bwd)


# ---------------------------------------------------------------------------
# federated kernels (RoundEngine backend="pallas")
#
# Forward-only by design: federated round functions are never differentiated
# through — the gather is a data movement, and the local-SGD kernel computes
# its softmax-xent gradients in closed form inside the kernel — so neither op
# carries a custom_vjp.
#
# Both ops size their grid from the leading cohort-block axis of the inputs:
# K lanes for a full cohort, or the shard's [capacity] compacted lane block
# under capacity-compacted sharded execution (ISSUE 5) — no capacity-
# specific kernel variants exist or are needed.
# ---------------------------------------------------------------------------


def fed_cohort_gather(flat_x, flat_y, starts, ns, max_n: int):
    """Fused gather+mask over the packed federation (see fed_gather.py).

    Every start must be 8-row aligned with an aligned ``max_n`` window in
    bounds (FederatedDataset.packed lays clients out so at upload)."""
    return fed_cohort_gather_fwd(flat_x, flat_y, starts, ns, max_n=max_n,
                                 interpret=_interpret())


def fed_local_sgd_mclr(x, y, idx, w0, b0, ns, n_iters, lr: float,
                       prox_mu: float = 0.0):
    """Fused masked budgeted MCLR local SGD (see fed_local_sgd.py).

    Returns (w_k [K, d, C], b_k [K, C], losses [K])."""
    return fed_local_sgd_mclr_fwd(x, y, idx, w0, b0, ns, n_iters, lr=lr,
                                  prox_mu=prox_mu,
                                  interpret=_interpret())


def fed_local_sgd_dense(x, y, idx, w1, b1, w2, b2, ns, n_iters, lr: float,
                        prox_mu: float = 0.0):
    """Fused masked budgeted dense-MLP local SGD (see fed_local_sgd_dense.py).

    Returns (w1_k [K, d, H], b1_k [K, H], w2_k [K, H, C], b2_k [K, C],
    losses [K])."""
    return fed_local_sgd_dense_fwd(x, y, idx, w1, b1, w2, b2, ns, n_iters,
                                   lr=lr, prox_mu=prox_mu,
                                   interpret=_interpret())


# the step families a fused local-SGD kernel exists for, by LocalStep.kind
FUSED_SGD_KINDS = ("mclr", "mlp")


def fused_sgd_eligible(step, sampling: str) -> bool:
    """Kernel-eligibility dispatch for the LocalStep seam.

    Fused pallas local-SGD kernels exist for the step families in
    ``FUSED_SGD_KINDS`` — masked budgeted MCLR (closed-form softmax-xent
    gradients, ``fed_local_sgd``) and the dense two-layer tanh MLP
    (hand-written backprop, ``fed_local_sgd_dense``) — always with the iid
    minibatch rule (indices drawn outside the kernel, bit-identical to the
    XLA path's draws).  Any other ``LocalStep`` (lstm, the ``from_model``
    architectures) or any other sampling takes the engine's generic XLA
    autodiff path automatically; backend="pallas" then still fuses the
    cohort gather and the upload compressor, which are model-agnostic.
    """
    return (sampling == "iid"
            and getattr(step, "kind", None) in FUSED_SGD_KINDS)


def fed_compress_topk_q8(ef, k: int):
    """Fused top-k + int8 upload compression over per-client error-feedback
    delta rows (see fed_compress.py).  Bitwise-identical to the ref twin.

    Returns (q [K, P] int8, scale [K] f32); transmitted value = q * scale."""
    return fed_compress_topk_q8_fwd(ef, k=k, interpret=_interpret())
