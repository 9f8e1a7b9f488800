"""Pallas fused masked local-SGD kernel for the MCLR federated round.

The XLA engine runs each client's budgeted SGD as a ``lax.scan`` whose carry
(the full parameter pytree) round-trips through HBM every iteration, vmapped
over the cohort.  This kernel runs the whole ``max_iters`` budget for one
client per grid step inside a single ``pallas_call``: the client's padded
shard and the global MCLR params are staged into VMEM once, the parameters
live in VMEM scratch across the ``fori_loop`` (no per-iteration carry
round-trip), and FedSAE's heterogeneous budgets stay uniform control flow —
every client executes ``max_iters`` slots, updates masked by
``i < n_iters_k`` exactly like the scan path.

The grid is the leading cohort-block axis of the inputs: the full cohort
``K``, or — under capacity-compacted sharded execution (ISSUE 5) — the
shard's dense ``[capacity]`` lane block, so the kernel sweeps only the
lanes the shard actually owns with no capacity-specific variant.

Specialised to the paper's convex model (multinomial logistic regression,
params ``{"w": [d, C], "b": [C]}``) and the ``sampling="iid"`` minibatch
rule: batch indices are drawn OUTSIDE the kernel with the same
``jax.random.randint`` call as the XLA path (bit-identical batches), and the
closed-form softmax-xent gradient replaces autodiff.  The minibatch gather
is a one-hot matmul (``sel @ x``) — exact in fp (each row has a single 1.0),
MXU-shaped on TPU.  Remaining divergence from the XLA path is reduction
order inside matmuls/reductions, so parity holds to fp tolerance (see
tests/test_fed_kernels.py), not bitwise.

The platform decides how it runs: compiled by Mosaic on a TPU, interpreted
elsewhere (``repro.kernels.ops``); the ``interpret`` argument forces
either.  Validated against kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sgd_kernel(ns_ref, iters_ref, x_ref, y_ref, idx_ref, w0_ref, b0_ref,
                w_ref, b_ref, loss_ref, w_s, b_s, *,
                max_n: int, B: int, C: int, max_iters: int,
                lr: float, prox_mu: float):
    k = pl.program_id(0)
    nk_safe = jnp.maximum(ns_ref[k], 1)
    iters = iters_ref[k]

    w_s[...] = w0_ref[...].astype(jnp.float32)
    b_s[...] = b0_ref[...].astype(jnp.float32)
    x = x_ref[0].astype(jnp.float32)                       # [max_n, d]
    # one-hot labels for the whole shard (batch rows pick from it exactly)
    oy = (y_ref[0].reshape(max_n, 1)
          == jax.lax.broadcasted_iota(jnp.int32, (max_n, C), 1)
          ).astype(jnp.float32)                            # [max_n, C]
    npos = jax.lax.broadcasted_iota(jnp.int32, (B, max_n), 1)
    # iid semantics: batch slots past the client's size are masked out
    bmask = (jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)
             < nk_safe).astype(jnp.float32)                # [B, 1]
    bsum = jnp.maximum(bmask.sum(), 1.0)

    def body(i, carry):
        loss_sum, cnt = carry
        idx_row = idx_ref[0, pl.ds(i, 1), :].reshape(B, 1)     # [B, 1]
        sel = ((npos == idx_row).astype(jnp.float32)) * bmask  # [B, max_n]
        xb = jnp.dot(sel, x, preferred_element_type=jnp.float32)   # [B, d]
        oyb = jnp.dot(sel, oy, preferred_element_type=jnp.float32)  # [B, C]
        w = w_s[...]
        b = b_s[...]
        logits = jnp.dot(xb, w, preferred_element_type=jnp.float32) + b
        z = logits - jnp.max(logits, axis=-1, keepdims=True)
        logp = z - jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))
        nll = -jnp.sum(logp * oyb, axis=-1, keepdims=True)         # [B, 1]
        loss = jnp.sum(nll * bmask) / bsum
        # closed-form d(masked mean xent)/d logits = (softmax - onehot)/bsum
        err = (jnp.exp(logp) - oyb) * bmask / bsum                 # [B, C]
        gw = jnp.dot(xb.T, err, preferred_element_type=jnp.float32)
        gb = jnp.sum(err, axis=0, keepdims=True)
        if prox_mu:
            dw = w - w0_ref[...].astype(jnp.float32)
            db = b - b0_ref[...].astype(jnp.float32)
            loss = loss + 0.5 * prox_mu * (jnp.sum(dw * dw)
                                           + jnp.sum(db * db))
            gw = gw + prox_mu * dw
            gb = gb + prox_mu * db
        active = (i < iters).astype(jnp.float32)
        w_s[...] = w - lr * active * gw
        b_s[...] = b - lr * active * gb
        return loss_sum + loss * active, cnt + active

    loss_sum, cnt = jax.lax.fori_loop(
        0, max_iters, body, (jnp.float32(0.0), jnp.float32(0.0)))
    w_ref[0] = w_s[...].astype(w_ref.dtype)
    b_ref[0] = b_s[...].astype(b_ref.dtype)
    # iid loss semantics: mean minibatch loss over executed iterations
    loss_ref[...] = jnp.full(loss_ref.shape, loss_sum / jnp.maximum(cnt, 1.0))


def fed_local_sgd_mclr_fwd(x, y, idx, w0, b0, ns, n_iters, *, lr: float,
                           prox_mu: float = 0.0, interpret: bool = True):
    """x: [K, max_n, d] f32; y: [K, max_n] int32; idx: [K, max_iters, B]
    int32 minibatch indices; w0: [d, C]; b0: [C]; ns/n_iters: [K] int32 ->
    (w_k [K, d, C], b_k [K, C], losses [K] f32)."""
    K, max_n, d = x.shape
    max_iters, B = idx.shape[1], idx.shape[2]
    C = w0.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(K,),
        in_specs=[
            pl.BlockSpec((1, max_n, d), lambda k, *_: (k, 0, 0)),
            pl.BlockSpec((1, 1, max_n), lambda k, *_: (k, 0, 0)),
            pl.BlockSpec((1, max_iters, B), lambda k, *_: (k, 0, 0)),
            pl.BlockSpec((d, C), lambda k, *_: (0, 0)),
            pl.BlockSpec((1, C), lambda k, *_: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, d, C), lambda k, *_: (k, 0, 0)),
            pl.BlockSpec((1, 1, C), lambda k, *_: (k, 0, 0)),
            pl.BlockSpec((1, 1, 128), lambda k, *_: (k, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((d, C), jnp.float32),
                        pltpu.VMEM((1, C), jnp.float32)],
    )
    w_k, b_k, losses = pl.pallas_call(
        functools.partial(_sgd_kernel, max_n=max_n, B=B, C=C,
                          max_iters=max_iters, lr=lr, prox_mu=prox_mu),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((K, d, C), w0.dtype),
            jax.ShapeDtypeStruct((K, 1, C), b0.dtype),
            jax.ShapeDtypeStruct((K, 1, 128), jnp.float32),
        ],
        interpret=interpret,
    )(ns, n_iters, x, y.reshape(K, 1, max_n), idx, w0, b0.reshape(1, C))
    return w_k, b_k.reshape(K, C), losses[:, 0, 0]
