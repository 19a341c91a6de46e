"""A run with the timed path broken underneath must come out not correct,
for each fault a cell can have (``bench/faults.py``); a sound run at the
same size must come out correct.  The cells run on one chip, so there is
no exchange between chips to leave out."""
import pytest

from bench import faults
from conftest import run_small

CELL = "netcraq.ycsb_b"
# YCSB-B's update share at the test's size drops too few updates for the
# window's checks to bite; an update-heavy mix drops hundreds
WRITE_HEAVY = {"updateproportion": 0.5}


@pytest.mark.parametrize("traffic", [{}, WRITE_HEAVY], ids=["ycsb_b", "write_heavy"])
def test_sound_run_is_correct(traffic):
    out = run_small(CELL, traffic=dict(traffic))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 1000
    assert list(out)[-1] == "checks"
    if traffic:
        assert out["failed"] > 0          # updates dropped at full windows


def test_sound_netchain_run_is_correct():
    out = run_small("netchain.ycsb_b", traffic=dict(WRITE_HEAVY))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0


@pytest.mark.parametrize("fault, number", [
    ("unchanged", "offered_mismatch"),
    ("half_batch", "lost_reads"),
    ("altered_reads", "read_value_mismatch"),
    ("short_window", "lost_writes"),
])
def test_planted_fault_is_not_correct(fault, number):
    with faults.FAULTS[fault]():
        out = run_small(CELL, traffic=dict(WRITE_HEAVY))
    assert not out["correct"]
    assert out["checks"][number]["value"] > 0, out["checks"]
