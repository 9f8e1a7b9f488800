"""Pallas TPU selective-scan (Mamba-1 SSM recurrence).

TPU adaptation of the CUDA selective-scan: grid (batch, d_blocks, seq_chunks)
with the chunk axis innermost/sequential; the hidden state h [d_blk, N] lives
in VMEM scratch and persists across chunks, so the [B, S, d, N] state tensor
never exists in HBM.  dA = exp(dt*A) and dB*x are computed in-register per
timestep from the compact (dt, A, B, x) inputs.

The platform decides how it runs: compiled by Mosaic on a TPU, interpreted
elsewhere (``repro.kernels.ops``).  Validated against kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(dt_ref, a_ref, b_ref, c_ref, x_ref, h0_ref,
                 y_ref, hT_ref, h_ref, *, chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    A = a_ref[...].astype(jnp.float32)                  # [d_blk, N]

    def step(t, h):
        dt_t = dt_ref[0, t].astype(jnp.float32)          # [d_blk]
        x_t = x_ref[0, t].astype(jnp.float32)            # [d_blk]
        b_t = b_ref[0, t].astype(jnp.float32)            # [N]
        c_t = c_ref[0, t].astype(jnp.float32)            # [N]
        dA = jnp.exp(dt_t[:, None] * A)                  # [d_blk, N]
        h = dA * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_ref[0, t] = (h @ c_t).astype(y_ref.dtype)      # [d_blk]
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_ref[...])
    h_ref[...] = h

    @pl.when(ci == n_chunks - 1)
    def _final():
        hT_ref[0] = h.astype(hT_ref.dtype)


def selective_scan_fwd(dt, A, Bmat, Cmat, x, h0, *, d_block: int = 128,
                       chunk: int = 256, interpret: bool = True):
    """dt/x: [B, S, d]; A: [d, N]; Bmat/Cmat: [B, S, N]; h0: [B, d, N].

    Returns (y [B, S, d] f32, hT [B, d, N] f32).
    """
    B, S, d = dt.shape
    N = A.shape[1]
    db = min(d_block, d)
    ck = min(chunk, S)
    assert d % db == 0 and S % ck == 0, (d, db, S, ck)
    n_d, n_chunks = d // db, S // ck

    kernel = functools.partial(_scan_kernel, chunk=ck, n_chunks=n_chunks)
    y, hT = pl.pallas_call(
        kernel,
        grid=(B, n_d, n_chunks),
        in_specs=[
            pl.BlockSpec((1, ck, db), lambda b, di, ci: (b, ci, di)),   # dt
            pl.BlockSpec((db, N), lambda b, di, ci: (di, 0)),           # A
            pl.BlockSpec((1, ck, N), lambda b, di, ci: (b, ci, 0)),     # B
            pl.BlockSpec((1, ck, N), lambda b, di, ci: (b, ci, 0)),     # C
            pl.BlockSpec((1, ck, db), lambda b, di, ci: (b, ci, di)),   # x
            pl.BlockSpec((1, db, N), lambda b, di, ci: (b, di, 0)),     # h0
        ],
        out_specs=[
            pl.BlockSpec((1, ck, db), lambda b, di, ci: (b, ci, di)),   # y
            pl.BlockSpec((1, db, N), lambda b, di, ci: (b, di, 0)),     # hT
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, d), jnp.float32),
            jax.ShapeDtypeStruct((B, d, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((db, N), jnp.float32)],
        interpret=interpret,
    )(dt, A, Bmat, Cmat, x, h0)
    return y, hT
