"""A run whose timed path is broken underneath comes out not correct:
once for each fault a one-chip training cell can have.  The program runs
at a tiny size on the CPU; the fault is planted in it."""
import time

import jax
import jax.numpy as jnp
import pytest

import harness
from repro.core.engine import RoundEngine
from test_harness_cpu import DATA, SPEC, SEED

CELL = "tiny-mclr.tiny-fassa"
pytestmark = pytest.mark.usefixtures("no_chip_check")


def run(cell=CELL):
    c = harness.Cell(SPEC, cell, base=DATA)
    return harness.run_cell(c, SEED, 0.3, False, time.perf_counter(),
                            log=lambda s: None)


def wrap_segment(monkeypatch, alter):
    """Make every scan segment the server builds pass its outputs through
    ``alter(state_in_params, state_out, stats)``."""
    orig = RoundEngine.make_segment_fn

    def make(self, *a, **kw):
        seg = orig(self, *a, **kw)

        def segment(state, *args):
            before = jax.tree.map(jnp.copy, state["params"])
            out = seg(state, *args)
            return alter(before, *out)

        return segment

    monkeypatch.setattr(RoundEngine, "make_segment_fn", make)


def test_state_left_unchanged(monkeypatch):
    def alter(before, state, stats):
        return dict(state, params=before), stats

    wrap_segment(monkeypatch, alter)
    r = run()
    assert r["correct"] is False
    assert r["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_left_out(monkeypatch):
    orig = RoundEngine._iid_batch_views

    def views(self, batch_size, max_iters):
        prep = orig(self, batch_size, max_iters)

        def half(fetch, nk, key):
            xb, yb, bmask = prep(fetch, nk, key)
            keep = jnp.arange(batch_size) < batch_size // 2
            return xb, yb, bmask * keep

        return half

    monkeypatch.setattr(RoundEngine, "_iid_batch_views", views)
    r = run()
    assert r["correct"] is False
    assert r["checks"]["loss_gap"]["value"] > r["checks"]["loss_gap"]["limit"]


def test_answer_altered(monkeypatch):
    def alter(before, state, stats):
        ids = stats["ids"]
        stats = dict(stats, ids=ids.at[0, 0].set((ids[0, 0] + 1) % 40))
        return state, stats

    wrap_segment(monkeypatch, alter)
    r = run()
    assert r["correct"] is False
    assert r["checks"]["cohort_mismatch"]["value"] >= 1


def test_control_is_not_correct():
    """The reference in bfloat16, put in the program's place, fails the
    check against the float32 reference."""
    import check
    from reference.fedsae import Reference

    c = harness.Cell(SPEC, CELL, base=DATA)
    ds, _ = harness.build(c, SEED, harness.make_clock(c.block))
    rounds = c.block * c.check_blocks
    at = (0, c.block, rounds)
    ref = Reference(ds.clients_x, ds.clients_y, c.config, c.flat_traffic(),
                    SEED).run(rounds, at)
    ctl = Reference(ds.clients_x, ds.clients_y, c.config, c.flat_traffic(),
                    SEED, dtype=jnp.bfloat16).run(rounds, at)
    correct, table = check.verdict(check.compare(ctl, ref, c.block, rounds),
                                   c.limits)
    assert correct is False
    assert table["loss_gap"]["value"] > table["loss_gap"]["limit"]
