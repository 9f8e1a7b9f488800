"""Pallas fused upload-compression kernel for the federated round engine.

The upload-transform stage (ISSUE 6) turns each client's error-feedback
delta row into a top-k-sparsified, int8-quantized upload.  The XLA twin
(``kernels/ref.py``) evaluates the same formulation as four separate [K, P]
passes (|.|, sort, select, quantize), each round-tripping an O(K * P)
intermediate through HBM; this kernel fuses the whole per-client transform
— magnitude scan, k-th-largest threshold, deterministic tie-break,
scale derivation and int8 quantization — into ONE VMEM pass over the
client's [P] delta row.  The grid is the cohort BLOCK axis exactly like
``fed_gather``/``fed_local_sgd``: the full cohort ``K``, or the shard's
capacity-compacted lane block (ISSUE 5) — the grid size is simply the
leading axis of the input, so no capacity-specific variant exists.

Formulation (shared VERBATIM with the ref twin so the two backends agree
bit for bit — every op below is rowwise/elementwise with a fixed reduction
order):

    a     = |ef|                       per-coordinate magnitude
    scale = max(a) * (1 / 127)         per-client symmetric int8 scale
                                       (explicit fp32 multiply — XLA rewrites
                                       a constant DIVISOR to an inexact
                                       reciprocal-multiply under jit but not
                                       eagerly, which would break bitwise
                                       parity across calling contexts)
    thr   = sort(a)[P - k]             k-th largest magnitude (k static)
    mask  = (a > thr) | earliest (a == thr) ties up to exactly k coords

(the kernel finds ``thr`` and the tie cut-off by counting binary searches,
since Mosaic lowers neither ``sort`` nor ``cumsum``; the result is the
same bits).
    q     = clip(round(ef / scale), -127, 127) on the mask, else 0

``k == 0`` transmits nothing (empty mask); ``k == P`` keeps every
coordinate (no sort).  A zero row (scale == 0) quantizes to all-zero.  The
transmitted value is ``q * scale`` and the caller carries ``ef - q *
scale`` as the next round's error-feedback residual; that telescoping
identity is EXACT in float32 (Sterbenz: each selected coordinate and its
dequantized value are within a factor of two, so the subtraction is exact
— tests/test_compression.py proves it property-based).

The platform decides how it runs: compiled by Mosaic on a TPU, interpreted
elsewhere (``repro.kernels.ops``); the ``interpret`` argument forces
either.  Validated bitwise against kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _count(pred) -> jax.Array:
    return jnp.sum(pred.astype(jnp.int32))


def _compress_kernel(ef_ref, q_ref, scale_ref, *, k: int):
    e = ef_ref[0].astype(jnp.float32)              # [1, P]
    P = e.shape[1]
    a = jnp.abs(e)
    amax = jnp.max(a)
    scale = amax * jnp.float32(1.0 / 127.0)
    safe = jnp.where(scale > 0, scale, jnp.float32(1.0))
    if k <= 0:
        mask = jnp.zeros(e.shape, bool)
    elif k >= P:
        mask = jnp.ones(e.shape, bool)
    else:
        # k-th largest |e| without a sort: the int32 bit pattern of a
        # non-negative float orders like the float, so build the largest
        # pattern t with count(bits >= t) >= k one bit at a time (31 passes)
        bits = jax.lax.bitcast_convert_type(a, jnp.int32)
        t = jnp.int32(0)
        for bit in range(30, -1, -1):
            cand = t | jnp.int32(1 << bit)
            t = jnp.where(_count(bits >= cand) >= k, cand, t)
        # compared as patterns (equal to the float compares; a row holding
        # a NaN has a NaN scale and quantizes to zero on either path)
        gt = bits > t
        eq = bits == t
        # exactly k coordinates: all strictly-above plus the EARLIEST ties.
        # Without a cumsum: c = the largest position with fewer than
        # ``need`` ties before it (binary search over positions); the ties
        # at positions <= c are the first ``need`` ones.
        need = k - _count(gt)
        pos = jax.lax.broadcasted_iota(jnp.int32, e.shape, 1)
        c = jnp.int32(0)
        for bit in range(P.bit_length() - 1, -1, -1):
            cand = c + jnp.int32(1 << bit)
            c = jnp.where((cand <= P) & (_count(eq & (pos < cand)) < need),
                          cand, c)
        mask = gt | (eq & (pos <= c))
    q = jnp.where(mask & (scale > 0),
                  jnp.clip(jnp.round(e / safe), -127.0, 127.0),
                  jnp.float32(0.0)).astype(jnp.int8)
    q_ref[0] = q
    scale_ref[...] = jnp.full(scale_ref.shape, scale)


def fed_compress_topk_q8_fwd(ef, *, k: int, interpret: bool = True):
    """ef: [K, P] f32 error-feedback delta rows; ``k`` static kept-coord
    count -> (q [K, P] int8 — zero off the per-row top-k mask, scale [K]
    f32).  K is the cohort block being executed — the full cohort or a
    capacity-compacted shard lane block; the grid is sized from the input."""
    K, P = ef.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(K,),
        in_specs=[pl.BlockSpec((1, 1, P), lambda i: (i, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, 1, P), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, 128), lambda i: (i, 0, 0)),
        ],
    )
    q, scale = pl.pallas_call(
        functools.partial(_compress_kernel, k=int(k)),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((K, 1, P), jnp.int8),
            jax.ShapeDtypeStruct((K, 1, 128), jnp.float32),
        ],
        interpret=interpret,
    )(ef.reshape(K, 1, P))
    return q.reshape(K, P), scale[:, 0, 0]
