"""Pallas fused cohort gather for the federated round engine.

The XLA packed-round gather (``flat_x[min(offsets[ids,None]+arange(max_n),
total-1)]``) materialises a ``[K, max_n]`` index intermediate and pads the
cohort with clamp-gathered neighbour rows that the mask then has to cancel.
This kernel fuses the window copy and the padding mask into one
``pallas_call``: the grid is the cohort BLOCK axis (the full cohort ``K``,
or the shard's capacity-compacted lane block — the grid size is simply
``starts.shape[0]``, so compacted [capacity]-sized inputs get
capacity-sized grids with no kernel variant), per-client start/length
arrive via scalar prefetch, and each grid step's input block is the
client's ``[max_n, feat]`` row window of the packed federation
(element-indexed, so the pipeline DMAs it straight from HBM) while the VPU
writes the validity mask.  Padding rows simply carry whatever the window
tail holds and the emitted mask zeroes them out of every downstream
statistic.

Contract: every start is a multiple of ``ROW_ALIGN`` (8, the f32 sublane
tiling: a TPU DMA window must start on a tile row) and satisfies
``start + roundup(max_n, 8) <= flat rows``.
``repro.data.federated.FederatedDataset.packed`` guarantees both by
aligning each client's offset and appending aligned tail slack at upload
time (the ops wrapper additionally clamps, so an unpadded caller is
memory-safe — but its windows would be misplaced; pad at upload).

Labels are gathered by XLA in the wrapper: a 1-D int32 array is tiled in
1024-element chunks in HBM, so a per-client label window cannot be a DMA
source, and the ``[K, max_n]`` label gather is 1/feat of the bytes.

The platform decides how it runs: compiled by Mosaic on a TPU, interpreted
elsewhere (``repro.kernels.ops``); the ``interpret`` argument forces
either.  Validated against kernels/ref.py.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.data.federated import ROW_ALIGN


def _gather_kernel(starts_ref, ns_ref, window_ref, x_ref, mask_ref):
    x_ref[0] = window_ref[...]
    pos = jax.lax.broadcasted_iota(jnp.int32, mask_ref.shape, 2)
    mask_ref[...] = (pos < ns_ref[pl.program_id(0)]).astype(jnp.float32)


def fed_cohort_gather_fwd(flat_x, flat_y, starts, ns, *, max_n: int,
                          interpret: bool = True):
    """flat_x: [total(+pad), ...feat]; flat_y: [total(+pad)] int32;
    starts/ns: [K] int32 (cohort offsets / clipped lengths) ->
    (x [K, max_n, ...feat], y [K, max_n], mask [K, max_n] f32).

    K here is the cohort block being executed — the full cohort or a
    capacity-compacted shard block; the grid is sized from the input."""
    K = starts.shape[0]
    feat_shape = flat_x.shape[1:]
    feat = math.prod(feat_shape) if feat_shape else 1
    fx = flat_x.reshape(flat_x.shape[0], feat)
    win = -(-max_n // ROW_ALIGN) * ROW_ALIGN
    # memory-safety clamp; a no-op for padded uploads (see module docstring)
    last = (fx.shape[0] - win) // ROW_ALIGN * ROW_ALIGN
    starts = jnp.minimum(starts, last)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(K,),
        in_specs=[pl.BlockSpec(
            (pl.Element(win), pl.Element(feat)),
            lambda k, s, _: (pl.multiple_of(s[k], ROW_ALIGN), 0))],
        out_specs=[
            pl.BlockSpec((1, win, feat), lambda k, *_: (k, 0, 0)),
            pl.BlockSpec((1, 1, win), lambda k, *_: (k, 0, 0)),
        ],
    )
    x, mask = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((K, win, feat), flat_x.dtype),
            jax.ShapeDtypeStruct((K, 1, win), jnp.float32),
        ],
        interpret=interpret,
    )(starts, ns, fx)
    y = flat_y[starts[:, None] + jnp.arange(max_n)[None, :]]
    return (x[:, :max_n].reshape((K, max_n) + feat_shape), y,
            mask[:, 0, :max_n])
