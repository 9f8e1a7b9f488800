"""The per-layer readers on a synthetic reduced trace whose numbers are
known."""
import importlib

import numpy as np
import pytest

import harness
import reduce_trace
from flops import fed_compress
from test_harness_cpu import DATA, SPEC

MS = 1_000_000
V5E = "TPU v5 lite"


def context(ops, window_s=0.010, rounds=4):
    cell = harness.Cell(SPEC, "tiny-mclr.tiny-fedavg-topk", base=DATA)
    red = reduce_trace.Reduction(ops, [], 1, window_s)
    window = {"rounds": rounds, "n_params": 170,
              "sizes": np.array([25, 35, 45, 55]), "max_n": 50,
              "ids": np.array([[0, 1, 2, 3]] * rounds),
              "uploaded": np.full(rounds, 2.0)}
    return harness.MetricContext(cell, red, window, V5E)


def read(name, ctx):
    return importlib.import_module(f"metrics.{name}").read(ctx)


OPS = [
    ("%fed.local_sgd.4 = f32[2] custom-call(f32[2] %y)", 0, 4 * MS),
    ("%fed.upload_transform.9 = s8[2] custom-call(f32[2] %e)", 4 * MS,
     5 * MS),
    ("%fed.upload_transform.9 = s8[2] custom-call(f32[2] %e)", 6 * MS,
     7 * MS),
]


def test_fed_compress_roofline_is_the_bandwidth_bound():
    _, nbytes = fed_compress.cost(8, 170)
    want = 100 * 2 * nbytes / 819e9 / 0.002
    assert read("fed_compress_roofline", context(OPS)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["fed_compress_roofline",
                                  "fed_local_sgd_ms_per_round"])
def test_kernel_readers_are_silent_without_their_kernel(name):
    assert read(name, context([("%fusion.1 = f32[2] fusion()", 0, MS)])) \
        is None


def test_local_sgd_ms_per_round_and_idle_share():
    ctx = context(OPS)
    assert read("fed_local_sgd_ms_per_round", ctx) == pytest.approx(1.0)
    # busy 6 ms of a 10 ms window
    assert read("device_idle_pct", ctx) == pytest.approx(40.0)


def test_mfu_from_the_estimated_samples():
    ctx = context(OPS)
    # B x K x mean uploaded x mean ceil(min(n, max_n) / B):
    # 10 x 4 x 2 x (3 + 4 + 5 + 5) / 4
    assert ctx.samples_per_round() == pytest.approx(340.0)
    fwd = 2 * 16 * 10 + 10
    want = 100 * 3 * fwd * 340 * 4 / (0.010 * 197e12)
    assert read("mfu", ctx) == pytest.approx(want)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.MetricContext(
            harness.Cell(SPEC, "tiny-mclr.tiny-fassa", base=DATA),
            reduce_trace.Reduction([], [], 1, 1.0), {}, "TPU v9 imagined")
