"""The four federated Pallas kernels compile for a TPU v5e at paper shapes.

Interpret mode cannot see what Mosaic refuses (block tiling, unaligned DMA
windows, scalar VMEM stores, primitives with no TPU lowering), so each
kernel is compiled with ``interpret=False`` for a described ``v5e:2x2``
chip that is not attached.  Nothing runs: the tests pass on any host whose
JAX can describe the topology.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and under several test workers a
description at collection time would give workers different tests.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import draw_minibatch_idx
from repro.data.federated import ROW_ALIGN
from repro.kernels.fed_compress import fed_compress_topk_q8_fwd
from repro.kernels.fed_gather import fed_cohort_gather_fwd
from repro.kernels.fed_local_sgd import fed_local_sgd_mclr_fwd
from repro.kernels.fed_local_sgd_dense import fed_local_sgd_dense_fwd

# paper deployment (examples/paper_scale_fl.py): MNIST-like federation of
# 1,000 clients / 69,035 samples, d=784, 10 classes, max_size 400; K=30
# clients a round, batch 10, h_cap 24 -> 960 local-SGD slots; MCLR has
# 7,850 params; make_mlp's hidden width is 64
N_CLIENTS, SAMPLES, K, MAX_N, D, C = 1000, 69035, 30, 400, 784, 10
B, MAX_ITERS, P, H = 10, 960, D * C + C, 64
# upper bound of the packed row count: every client padded to ROW_ALIGN,
# plus the aligned tail slack
ROWS = SAMPLES + N_CLIENTS * (ROW_ALIGN - 1) + MAX_N


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compilation cache off while
    the file's tests run: a compile for a chip that is not attached is
    written to the cache but cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                desc = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:  # no TPU compiler on this host
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


F32, I32 = jnp.float32, jnp.int32
KERNELS = {
    "fed_gather": (
        functools.partial(fed_cohort_gather_fwd, max_n=MAX_N,
                          interpret=False),
        [((ROWS, D), F32), ((ROWS,), I32), ((K,), I32), ((K,), I32)]),
    "fed_local_sgd": (
        functools.partial(fed_local_sgd_mclr_fwd, lr=0.03, interpret=False),
        [((K, MAX_N, D), F32), ((K, MAX_N), I32), ((K, MAX_ITERS, B), I32),
         ((D, C), F32), ((C,), F32), ((K,), I32), ((K,), I32)]),
    "fed_local_sgd_dense": (
        functools.partial(fed_local_sgd_dense_fwd, lr=0.03,
                          interpret=False),
        [((K, MAX_N, D), F32), ((K, MAX_N), I32), ((K, MAX_ITERS, B), I32),
         ((D, H), F32), ((H,), F32), ((H, C), F32), ((C,), F32),
         ((K,), I32), ((K,), I32)]),
    "fed_compress": (
        functools.partial(fed_compress_topk_q8_fwd, k=-(-P // 10),
                          interpret=False),
        [((K, P), F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e_at_paper_shapes(one_chip, name):
    fn, specs = KERNELS[name]
    compiled = jax.jit(fn).lower(*_shapes(one_chip, *specs)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_local_sgd_compiles_with_budgets_from_zero_to_max_iters(one_chip):
    """Each lane's loop runs to its own budget, read at run time from the
    scalar-prefetched n_iters: Mosaic lowers the loop with a dynamic trip
    count, here for a budget vector that holds 0 and MAX_ITERS."""
    fn, specs = KERNELS["fed_local_sgd"]
    budgets = np.linspace(0, MAX_ITERS, K).round().astype(np.int32)
    assert budgets[0] == 0 and budgets[-1] == MAX_ITERS

    def with_budgets(*args):
        return fn(*args, jnp.asarray(budgets))

    compiled = jax.jit(with_budgets).lower(
        *_shapes(one_chip, *specs[:-1])).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _draw_into_local_sgd(draw):
    """``draw(keys, ns)``'s minibatch indices feeding fed_local_sgd, the
    way the engine's fused round calls them."""
    def round_fn(rng, x, y, w0, b0, ns, n_iters):
        idx = draw(jax.random.split(rng, K), ns)
        return fed_local_sgd_mclr_fwd(x, y, idx, w0, b0, ns, n_iters,
                                      lr=0.03, interpret=False)
    return round_fn


def test_minibatch_draw_compiles_lane_dense(one_chip):
    """The engine draws the kernel's [K, max_iters, B] indices flat, so the
    random bits and remainders run on full 128-lane rows; a draw shaped
    [..., B] with B = 10 is padded to 128 lanes.  No fusion may produce the
    padded index array (a reshape or copy into it is the kernel's relayout);
    the shaped draw, compiled the same way, does."""
    padded = re.compile(
        r"= s32\[%d,%d,%d\]\{[^}]*\} fusion\(" % (K, MAX_ITERS, B))
    specs = [((2,), jnp.uint32)] + KERNELS["fed_local_sgd"][1][:2] + \
        KERNELS["fed_local_sgd"][1][3:]
    shaped = jax.vmap(lambda key, nk: jax.random.randint(
        key, (MAX_ITERS, B), 0, jnp.maximum(nk, 1)))
    texts = {}
    for name, draw in (("flat", lambda keys, ns: draw_minibatch_idx(
            keys, ns, MAX_ITERS, B)), ("shaped", shaped)):
        texts[name] = jax.jit(_draw_into_local_sgd(draw)).lower(
            *_shapes(one_chip, *specs)).compile().as_text()
        assert "tpu_custom_call" in texts[name]
    assert padded.search(texts["shaped"])
    assert not padded.search(texts["flat"])
