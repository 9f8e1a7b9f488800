"""Pallas fused masked local-SGD kernel for the dense two-layer (MLP) step.

Same execution shape as ``fed_local_sgd.py`` — one client per grid step, the
whole ``max_iters`` budget in a single ``fori_loop``, parameters resident in
VMEM scratch across iterations, heterogeneous budgets as uniform control
flow masked by ``i < n_iters_k`` — but specialised to the dense family

    h      = tanh(x @ w1 + b1)
    logits = h @ w2 + b2

(``repro.models.fl_models.make_mlp``, params ``{w1, b1, w2, b2}``).  The
backward pass is hand-written two-layer backprop instead of autodiff:

    err  = (softmax(logits) - onehot) * bmask / bsum        # [B, C]
    gw2  = h.T @ err          gb2 = err.sum(0)
    dh   = err @ w2.T
    dpre = dh * (1 - h^2)                                   # tanh'
    gw1  = xb.T @ dpre        gb1 = dpre.sum(0)

plus the FedProx proximal term on every leaf, mirroring the MCLR kernel.

Batch indices are drawn OUTSIDE the kernel with the exact ``randint`` call
the XLA iid path uses (bit-identical batches); the minibatch gather is the
same one-hot matmul (``sel @ x``).  Divergence from the XLA autodiff path is
reduction order inside matmuls plus the algebraic form of the tanh/softmax
gradients, so engine-level parity is to fp tolerance; kernel/ref parity
against ``ref.fed_local_sgd_dense`` is the pinned contract
(tests/test_fused_generic.py).

The platform decides how it runs: compiled by Mosaic on a TPU, interpreted
elsewhere (``repro.kernels.ops``); the ``interpret`` argument forces
either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dense_sgd_kernel(ns_ref, iters_ref, x_ref, y_ref, idx_ref,
                      w10_ref, b10_ref, w20_ref, b20_ref,
                      w1_ref, b1_ref, w2_ref, b2_ref, loss_ref,
                      w1_s, b1_s, w2_s, b2_s, *,
                      max_n: int, B: int, H: int, C: int, max_iters: int,
                      lr: float, prox_mu: float):
    k = pl.program_id(0)
    nk_safe = jnp.maximum(ns_ref[k], 1)
    iters = iters_ref[k]

    w1_s[...] = w10_ref[...].astype(jnp.float32)
    b1_s[...] = b10_ref[...].astype(jnp.float32)
    w2_s[...] = w20_ref[...].astype(jnp.float32)
    b2_s[...] = b20_ref[...].astype(jnp.float32)
    x = x_ref[0].astype(jnp.float32)                       # [max_n, d]
    oy = (y_ref[0].reshape(max_n, 1)
          == jax.lax.broadcasted_iota(jnp.int32, (max_n, C), 1)
          ).astype(jnp.float32)                            # [max_n, C]
    npos = jax.lax.broadcasted_iota(jnp.int32, (B, max_n), 1)
    bmask = (jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)
             < nk_safe).astype(jnp.float32)                # [B, 1]
    bsum = jnp.maximum(bmask.sum(), 1.0)

    def body(i, carry):
        loss_sum, cnt = carry
        idx_row = idx_ref[0, pl.ds(i, 1), :].reshape(B, 1)     # [B, 1]
        sel = ((npos == idx_row).astype(jnp.float32)) * bmask  # [B, max_n]
        xb = jnp.dot(sel, x, preferred_element_type=jnp.float32)   # [B, d]
        oyb = jnp.dot(sel, oy, preferred_element_type=jnp.float32)  # [B, C]
        w1 = w1_s[...]
        b1 = b1_s[...]
        w2 = w2_s[...]
        b2 = b2_s[...]
        h = jnp.tanh(jnp.dot(xb, w1,
                             preferred_element_type=jnp.float32) + b1)
        logits = jnp.dot(h, w2, preferred_element_type=jnp.float32) + b2
        z = logits - jnp.max(logits, axis=-1, keepdims=True)
        logp = z - jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))
        nll = -jnp.sum(logp * oyb, axis=-1, keepdims=True)         # [B, 1]
        loss = jnp.sum(nll * bmask) / bsum
        err = (jnp.exp(logp) - oyb) * bmask / bsum                 # [B, C]
        gw2 = jnp.dot(h.T, err, preferred_element_type=jnp.float32)
        gb2 = jnp.sum(err, axis=0, keepdims=True)
        dh = jnp.dot(err, w2.T, preferred_element_type=jnp.float32)
        dpre = dh * (1.0 - h * h)                                  # [B, H]
        gw1 = jnp.dot(xb.T, dpre, preferred_element_type=jnp.float32)
        gb1 = jnp.sum(dpre, axis=0, keepdims=True)
        if prox_mu:
            dw1 = w1 - w10_ref[...].astype(jnp.float32)
            db1 = b1 - b10_ref[...].astype(jnp.float32)
            dw2 = w2 - w20_ref[...].astype(jnp.float32)
            db2 = b2 - b20_ref[...].astype(jnp.float32)
            loss = loss + 0.5 * prox_mu * (
                jnp.sum(dw1 * dw1) + jnp.sum(db1 * db1)
                + jnp.sum(dw2 * dw2) + jnp.sum(db2 * db2))
            gw1 = gw1 + prox_mu * dw1
            gb1 = gb1 + prox_mu * db1
            gw2 = gw2 + prox_mu * dw2
            gb2 = gb2 + prox_mu * db2
        active = (i < iters).astype(jnp.float32)
        w1_s[...] = w1 - lr * active * gw1
        b1_s[...] = b1 - lr * active * gb1
        w2_s[...] = w2 - lr * active * gw2
        b2_s[...] = b2 - lr * active * gb2
        return loss_sum + loss * active, cnt + active

    loss_sum, cnt = jax.lax.fori_loop(
        0, max_iters, body, (jnp.float32(0.0), jnp.float32(0.0)))
    w1_ref[0] = w1_s[...].astype(w1_ref.dtype)
    b1_ref[0] = b1_s[...].astype(b1_ref.dtype)
    w2_ref[0] = w2_s[...].astype(w2_ref.dtype)
    b2_ref[0] = b2_s[...].astype(b2_ref.dtype)
    # iid loss semantics: mean minibatch loss over executed iterations
    loss_ref[...] = jnp.full(loss_ref.shape, loss_sum / jnp.maximum(cnt, 1.0))


def fed_local_sgd_dense_fwd(x, y, idx, w1, b1, w2, b2, ns, n_iters, *,
                            lr: float, prox_mu: float = 0.0,
                            interpret: bool = True):
    """x: [K, max_n, d] f32; y: [K, max_n] int32; idx: [K, max_iters, B]
    int32 minibatch indices; w1: [d, H]; b1: [H]; w2: [H, C]; b2: [C];
    ns/n_iters: [K] int32 -> (w1_k [K, d, H], b1_k [K, H], w2_k [K, H, C],
    b2_k [K, C], losses [K] f32)."""
    K, max_n, d = x.shape
    max_iters, B = idx.shape[1], idx.shape[2]
    H, C = w2.shape

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(K,),
        in_specs=[
            pl.BlockSpec((1, max_n, d), lambda k, *_: (k, 0, 0)),
            pl.BlockSpec((1, 1, max_n), lambda k, *_: (k, 0, 0)),
            pl.BlockSpec((1, max_iters, B), lambda k, *_: (k, 0, 0)),
            pl.BlockSpec((d, H), lambda k, *_: (0, 0)),
            pl.BlockSpec((1, H), lambda k, *_: (0, 0)),
            pl.BlockSpec((H, C), lambda k, *_: (0, 0)),
            pl.BlockSpec((1, C), lambda k, *_: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, d, H), lambda k, *_: (k, 0, 0)),
            pl.BlockSpec((1, 1, H), lambda k, *_: (k, 0, 0)),
            pl.BlockSpec((1, H, C), lambda k, *_: (k, 0, 0)),
            pl.BlockSpec((1, 1, C), lambda k, *_: (k, 0, 0)),
            pl.BlockSpec((1, 1, 128), lambda k, *_: (k, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((d, H), jnp.float32),
                        pltpu.VMEM((1, H), jnp.float32),
                        pltpu.VMEM((H, C), jnp.float32),
                        pltpu.VMEM((1, C), jnp.float32)],
    )
    w1_k, b1_k, w2_k, b2_k, losses = pl.pallas_call(
        functools.partial(_dense_sgd_kernel, max_n=max_n, B=B, H=H, C=C,
                          max_iters=max_iters, lr=lr, prox_mu=prox_mu),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((K, d, H), w1.dtype),
            jax.ShapeDtypeStruct((K, 1, H), b1.dtype),
            jax.ShapeDtypeStruct((K, H, C), w2.dtype),
            jax.ShapeDtypeStruct((K, 1, C), b2.dtype),
            jax.ShapeDtypeStruct((K, 1, 128), jnp.float32),
        ],
        interpret=interpret,
    )(ns, n_iters, x, y.reshape(K, 1, max_n), idx, w1, b1.reshape(1, H),
      w2, b2.reshape(1, C))
    return (w1_k, b1_k.reshape(K, H), w2_k, b2_k.reshape(K, C),
            losses[:, 0, 0])
