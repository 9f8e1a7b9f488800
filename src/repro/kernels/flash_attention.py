"""Pallas TPU flash attention (forward) with causal + sliding-window masks
and native GQA (kv-head index mapping — no K/V head replication in HBM).

Grid: (batch, q_heads, q_blocks, kv_blocks); the kv axis is innermost and
sequential on TPU, so the online-softmax state (m, l, acc) lives in VMEM
scratch that persists across kv steps.  Block shapes are MXU-aligned
(multiples of 128 where the problem allows).

The platform decides how it runs: compiled by Mosaic on a TPU, interpreted
elsewhere (``repro.kernels.ops``).  Validated against kernels/ref.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                causal: bool, window: int, bq: int, bk: int, n_kv: int,
                scale: float):
    i = pl.program_id(2)   # q block
    j = pl.program_id(3)   # kv block (sequential)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window:
        mask = mask & (k_pos > q_pos - window)

    # skip fully-masked blocks (no FLOPs, state unchanged)
    run = jnp.any(mask) if (causal or window) else True

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)              # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)              # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)              # [bk, hd]
        s = (q @ k.T) * scale                            # [bq, bk]
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
        m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))


def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = True):
    """q: [B, S, Hq, hd]; k/v: [B, T, Hkv, hd] ->
    (out [B, S, Hq, hd], lse [B, Hq, S])."""
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    bq, bk = min(block_q, S), min(block_k, T)
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)
    n_q, n_kv = S // bq, T // bk
    scale = hd ** -0.5

    qt = q.transpose(0, 2, 1, 3)   # [B, Hq, S, hd]
    kt = k.transpose(0, 2, 1, 3)   # [B, Hkv, T, hd]
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_fwd_kernel, causal=causal, window=window,
                               bq=bq, bk=bk, n_kv=n_kv, scale=scale)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, Hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, h, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, S, hd), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # m: running row max
            pltpu.VMEM((bq,), jnp.float32),       # l: running row sum
            pltpu.VMEM((bq, hd), jnp.float32),    # acc: output accumulator
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention-2 style: recompute P from saved lse)
# ---------------------------------------------------------------------------


def _mask(i, j, bq, bk, causal, window):
    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window:
        mask = mask & (k_pos > q_pos - window)
    return mask


def _bwd_kv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dk_acc, dv_acc, *,
                   causal, window, bq, bk, n_q, scale):
    j = pl.program_id(2)   # kv block
    i = pl.program_id(3)   # q block (sequential)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    mask = _mask(i, j, bq, bk, causal, window)
    run = jnp.any(mask) if (causal or window) else True

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)          # [bq, hd]
        lse = lse_ref[0, 0]                            # [bq]
        delta = delta_ref[0, 0]                        # [bq] rowsum(dO*O)
        s = (q @ k.T) * scale
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)   # [bq, bk]
        dv_acc[...] += p.T @ do                        # [bk, hd]
        dp = do @ v.T                                  # [bq, bk]
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[...] += ds.T @ q                        # [bk, hd]

    @pl.when(i == n_q - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_q_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                  dq_ref, dq_acc, *, causal, window, bq, bk, n_kv, scale):
    i = pl.program_id(2)   # q block
    j = pl.program_id(3)   # kv block (sequential)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    mask = _mask(i, j, bq, bk, causal, window)
    run = jnp.any(mask) if (causal or window) else True

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = (q @ k.T) * scale
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = do @ v.T
        ds = p * (dp - delta[:, None]) * scale
        dq_acc[...] += ds @ k

    @pl.when(j == n_kv - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, do, *, causal: bool = True,
                        window: int = 0, block_q: int = 128,
                        block_k: int = 128, interpret: bool = True):
    """FlashAttention-2 backward.  GQA is handled by expanding K/V to Hq
    heads for the kernels and group-summing dK/dV afterwards.

    q/out/do: [B, S, Hq, hd]; k/v: [B, T, Hkv, hd]; lse: [B, Hq, S].
    Returns (dq, dk, dv) with the input shapes."""
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    bq, bk = min(block_q, S), min(block_k, T)
    n_q, n_kv = S // bq, T // bk
    scale = hd ** -0.5

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    # delta_i = rowsum(dO_i * O_i)  (precomputed; tiny)
    delta = jnp.einsum("bshd,bshd->bhs", do.astype(jnp.float32),
                       out.astype(jnp.float32))

    kv_spec = pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, i, G=G: (b, h // G, j, 0))
    q_spec_kv = pl.BlockSpec((1, 1, bq, hd), lambda b, h, j, i: (b, h, i, 0))
    row_spec_kv = pl.BlockSpec((1, 1, bq), lambda b, h, j, i: (b, h, i))

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_kv_kernel, causal=causal, window=window,
                          bq=bq, bk=bk, n_q=n_q, scale=scale),
        grid=(B, Hq, n_kv, n_q),
        in_specs=[q_spec_kv, kv_spec, kv_spec, q_spec_kv, row_spec_kv,
                  row_spec_kv],
        out_specs=[
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, T, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, T, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)

    q_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0))
    kv_spec_q = pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, G=G: (b, h // G, j, 0))
    row_spec = pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, h, i))
    dq = pl.pallas_call(
        functools.partial(_bwd_q_kernel, causal=causal, window=window,
                          bq=bq, bk=bk, n_kv=n_kv, scale=scale),
        grid=(B, Hq, n_q, n_kv),
        in_specs=[q_spec, kv_spec_q, kv_spec_q, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)

    dq = dq.transpose(0, 2, 1, 3).astype(q.dtype)
    # group-sum dK/dV back to Hkv heads (GQA)
    dk = dk.reshape(B, Hkv, G, T, hd).sum(2).transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv.reshape(B, Hkv, G, T, hd).sum(2).transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dv
