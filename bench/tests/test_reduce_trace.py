"""The trace reduction, on a recorded chip trace and on a synthetic
profile whose numbers are known."""
import os

import jax

import reduce_trace

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "mnist-mclr.fassa-k30.xplane.pb")


def test_recorded_trace_reduces_to_fixed_numbers():
    """One steady 16-round block of ``mnist-mclr.fassa-k30`` traced on a
    TPU v5 lite (JAX 0.9.0, 0.4442 s of host time)."""
    red = reduce_trace.from_profile(
        jax.profiler.ProfileData.from_file(FIXTURE), 0.4442436409999999)
    assert red.n_devices == 1
    assert len(red.ops) == 1223
    assert red.busy_s == 0.43420334600000005
    # one gather and one local-SGD kernel call a round
    assert red.kernel("fed.gather") == (0.0009304690000000001, 16)
    assert red.kernel("fed.local_sgd") == (0.418846516, 16)
    assert red.kernel("fed.upload_transform") == (0, 0)
    ops = red.breakdown()["device_ops"]
    assert ops[0] == ["fed.local_sgd.10", 0.4188465160000001]
    assert not any(name.startswith("while") for name, _ in ops)


class Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = []


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Profile:
    def __init__(self, planes):
        self.planes = planes


def test_busy_union_kernels_and_gaps():
    ms = 1_000_000
    ops = [
        Ev("%while.1 = (s32[]) while(s32[] %a)", 0, 10 * ms),
        Ev("%fed.gather.3 = f32[2] custom-call(f32[2] %x)", 0, 2 * ms),
        Ev("%fed.local_sgd.4 = f32[2] custom-call(f32[2] %y)", 2 * ms,
           6 * ms),
        Ev("%fusion.7 = f32[2] fusion(f32[2] %z)", 8 * ms, 1 * ms),
        # after a 4 ms gap: the eval program
        Ev("%dot.2 = f32[2] dot(f32[2] %e)", 14 * ms, 2 * ms),
    ]
    host = [Ev("device_get", 10 * ms, 3 * ms), Ev("run", 0, 20 * ms)]
    red = reduce_trace.from_profile(Profile([
        Plane("/host:CPU", [Line("python", host)]),
        Plane("/device:TPU:0", [Line("XLA Modules", []),
                                Line("XLA Ops", ops)]),
    ]), window_s=0.020)
    assert red.busy_s == 0.012
    assert red.kernel("fed.local_sgd") == (0.006, 1)
    assert red.kernel("fusion") == (0, 0)          # not a custom call
    bd = red.breakdown()
    assert [n for n, _ in bd["device_ops"]] == [
        "fed.local_sgd.4", "fed.gather.3", "dot.2", "fusion.7"]
    # the 4 ms gap goes to the innermost host event covering half of it
    assert bd["idle_gaps"] == [["device_get", 0.004]]
