"""``host_ms_per_block``, the host loop's time between stats pulls: on a
synthetic reduced trace whose numbers are known, and on a traced tiny run
of the program on the CPU, with and without the program's spans."""
import contextlib
import importlib
import time
from unittest import mock

import pytest

import harness
import reduce_trace
from test_harness_cpu import DATA, SPEC
from test_metrics import V5E

MS = 1_000_000


def read(host):
    cell = harness.Cell(SPEC, "tiny-mclr.tiny-fassa", base=DATA)
    red = reduce_trace.Reduction([], host, 1, 0.1)
    return importlib.import_module("metrics.host_ms_per_block").read(
        harness.MetricContext(cell, red, {"rounds": 8}, V5E))


def test_mean_per_block_of_each_phase_but_the_pull():
    host = [
        ("fed.host.dispatch", 0, 1 * MS),
        ("fed.host.pull", 1 * MS, 11 * MS),
        ("fed.host.eval", 11 * MS, 15 * MS),
        ("fed.host.records", 15 * MS, 17 * MS),
        ("fed.host.checkpoint", 17 * MS, 17 * MS + 500_000),
        ("fed.block", 0, 18 * MS),
        ("_array.py:297 __float__", 11 * MS, 14 * MS),
        ("fed.host.dispatch", 18 * MS, 21 * MS),
        ("fed.host.pull", 21 * MS, 31 * MS),
        # the second block's eval, records and checkpoint fell after the
        # trace stopped: each phase is averaged over the spans it has
    ]
    # dispatch (1 + 3) / 2, eval 4, records 2, checkpoint 0.5
    assert read(host) == pytest.approx(2 + 4 + 2 + 0.5)


def test_silent_without_the_spans():
    assert read([("fed.host.pull", 0, MS), ("run", 0, 2 * MS)]) is None
    assert read([]) is None


@contextlib.contextmanager
def _no_span(name, log, block):
    yield


def traced_tiny_run():
    spec = dict(SPEC, per_layer=[{"name": "host_ms_per_block",
                                  "unit": "ms"}])
    cell = harness.Cell(spec, "tiny-mclr.tiny-fassa", base=DATA)
    return harness.run_cell(cell, 7, 0.5, True, time.perf_counter(),
                            log=lambda s: None)


@pytest.mark.usefixtures("no_chip_check")
def test_traced_tiny_run_reads_the_program_spans(monkeypatch):
    load_json = harness.load_json

    def with_cpu_peaks(*parts, **kw):
        out = load_json(*parts, **kw)
        if parts == ("peaks.json",):
            out["devices"]["cpu"] = out["devices"][V5E]
        return out

    monkeypatch.setattr(harness, "load_json", with_cpu_peaks)
    r = traced_tiny_run()
    assert r["correct"] is True
    assert r["metrics"]["host_ms_per_block"]["value"] > 0
    # a program without the spans: the metric is left out, nothing raises
    from repro.obs import profiling
    with mock.patch.object(profiling, "host_span", _no_span):
        r = traced_tiny_run()
    assert r["correct"] is True
    assert "host_ms_per_block" not in r["metrics"]
