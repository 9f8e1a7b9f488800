"""The harness end to end at a tiny size on the CPU, without the timing
path's look for a chip; and its refusal to run anywhere but a TPU."""
import json
import os
import subprocess
import sys
import time

import jax
import pytest

import harness

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SPEC = {
    "workloads": [
        {"name": "tiny-mclr.tiny-fassa", "config": "tiny-mclr",
         "traffic": "tiny-fassa", "chips": 1},
        {"name": "tiny-mclr.tiny-fedavg-topk", "config": "tiny-mclr",
         "traffic": "tiny-fedavg-topk", "chips": 1}],
    "end_to_end": [{"name": "rounds_per_s", "unit": "rounds/s"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}
SEED = 2 ** 31 + 17       # seeds above 32 signed bits must work


def run_tiny(name, seed=SEED):
    cell = harness.Cell(SPEC, name, base=DATA)
    return harness.run_cell(cell, seed, 0.5, False, time.perf_counter(),
                            log=lambda s: None)


@pytest.mark.usefixtures("no_chip_check")
@pytest.mark.parametrize("name", ["tiny-mclr.tiny-fassa",
                                  "tiny-mclr.tiny-fedavg-topk"])
def test_tiny_cell_runs_and_is_correct(name):
    r = run_tiny(name)
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"rounds_per_s", "setup_s"}
    assert r["metrics"]["rounds_per_s"]["value"] > 0
    assert r["device"]["platform"] == "cpu"
    line = json.loads(harness.result_line(dict(r)))
    assert list(line)[-1] == "checks"
    for value, limit in line["checks"].values():
        assert value <= limit


def test_refuses_a_cpu_device():
    root = os.path.dirname(harness.BENCH)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "run.py"),
         "--workload", "mnist-mclr.fassa-k30", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 1
    assert "not a TPU" in p.stderr
    assert not p.stdout.strip()


def test_require_chips_counts_devices():
    with pytest.raises(harness.NoChip):
        harness.require_chips(jax, 1)
