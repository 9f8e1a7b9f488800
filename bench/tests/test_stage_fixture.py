"""The program's stage map against a recorded chip trace: every device op
of the scan segment is an instruction the map knows, and the per-stage
device times, ops in no ``fed.*`` scope included, add up to the segment's
busy time.

The fixture is one steady 16-round block of
``mnist-mclr.fedavg-e1-k100-topk`` (rounds 1408-1423) traced on a TPU v5
lite with JAX 0.9.0, and the map ``FedSAEServer.segment_stage_map()``
returned in the same process."""
import collections
import json
import os

import jax
import pytest

import reduce_trace

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")
CELL = "mnist-mclr.fedavg-e1-k100-topk"
ROUNDS = 16


@pytest.fixture(scope="module")
def recorded():
    pd = jax.profiler.ProfileData.from_file(
        os.path.join(TESTDATA, f"{CELL}.xplane.pb"))
    with open(os.path.join(TESTDATA, f"{CELL}.stage_map.json")) as f:
        stage_map = json.load(f)
    (device,) = [p for p in pd.planes if p.name == "/device:TPU:0"]
    lines = {line.name: [(e.name, int(e.start_ns),
                          int(e.start_ns) + int(e.duration_ns))
                         for e in line.events] for line in device.lines}
    return lines["XLA Modules"], lines["XLA Ops"], stage_map


def segment_ops(modules, ops, module):
    """The non-container ops inside the ``module(...)`` events."""
    spans = [(s, e) for name, s, e in modules
             if name.split("(")[0] == module]
    return [(text, s, e) for text, s, e in ops
            if any(ms <= s and e <= me for ms, me in spans)
            and not reduce_trace._CONTAINER.search(text)]


def test_every_segment_op_is_in_the_map_and_stages_sum_to_busy(recorded):
    modules, ops, stage_map = recorded
    stages = stage_map["stages"]
    assert stage_map["module"] == "jit_segment"
    assert [m[0].split("(")[0] for m in modules].count("jit_segment") == 1
    seg = segment_ops(modules, ops, stage_map["module"])
    assert len(seg) == 1423
    seconds = collections.Counter()
    for text, s, e in seg:
        name = reduce_trace.short_name(text)
        assert name in stages, name
        seconds[stages[name]] += (e - s) * 1e-9
    busy = sum(e - s for s, e in reduce_trace._union(
        [(s, e) for _, s, e in seg])) * 1e-9
    assert busy == pytest.approx(0.089959184, rel=1e-9)
    # one op at a time on the chip: the stage times tile the busy time
    assert sum(seconds.values()) == pytest.approx(busy, rel=1e-9)
    ms_per_round = {k: 1e3 * v / ROUNDS for k, v in seconds.items()}
    assert set(ms_per_round) == {"fed.local_sgd", "fed.upload_transform",
                                 "fed.gather", "fed.select",
                                 "fed.aggregate", None}
    assert ms_per_round["fed.local_sgd"] == pytest.approx(3.838, abs=1e-3)
    assert ms_per_round["fed.upload_transform"] == pytest.approx(
        0.9888, abs=1e-4)
    assert ms_per_round["fed.gather"] == pytest.approx(0.7042, abs=1e-4)
    # ops in no scope: under 2% of the segment's busy time
    assert seconds[None] < 0.02 * busy


def test_kernels_keep_their_scope_names(recorded):
    """A Pallas kernel's custom call is named after its scope, and the map
    puts it in that scope (XLA's own ``AllocateBuffer`` calls are not
    kernels)."""
    modules, ops, stage_map = recorded
    calls = collections.Counter()
    for text, _, _ in segment_ops(modules, ops, stage_map["module"]):
        if "custom-call(" in text and "AllocateBuffer" not in text:
            name = reduce_trace.short_name(text)
            stage = stage_map["stages"][name]
            assert name.rsplit(".", 1)[0] == stage
            calls[stage] += 1
    assert calls == {"fed.gather": ROUNDS, "fed.local_sgd": ROUNDS,
                     "fed.upload_transform": ROUNDS}
