"""The four-chip cell at a size the CPU holds, on 4 emulated devices: a
sound run is correct, and each fault planted in what the four-chip scan
shares with the one-chip one (the node step, the configuration) makes it
not correct.  ``unchanged`` patches only ``ChainSim.tick``, which this
cell does not run."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

CELL = "netcraq_dist4.ycsb_b"
WRITE_HEAVY = {"updateproportion": 0.5}
CASES = [("none", {}, None), ("none", WRITE_HEAVY, None),
         ("half_batch", WRITE_HEAVY, "lost_reads"),
         ("altered_reads", WRITE_HEAVY, "read_value_mismatch"),
         ("short_window", WRITE_HEAVY, "lost_writes")]


@pytest.fixture(scope="module")
def runs():
    code = f"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{ROOT!r}, {os.path.join(ROOT, "src")!r},
                {os.path.join(ROOT, "bench", "tests")!r}]
from bench import faults
from conftest import run_small
from bench.drivers import dist4
from conftest import small_cell
import jax
found = small_cell({CELL!r})
eng = dist4.Engine(found["config"], found["traffic"], 24.0, 3 << 31, jax.devices())
gen = jax.eval_shape(lambda: eng.gen)
ours = dist4.scan_lowering(eng.dist, gen, eng.seg).as_text()
theirs = type(eng.dist)._dist_openloop_scan.lower(
    eng.dist, eng.state, eng.gen, eng.seg, eng.width, 0).as_text()
print(json.dumps({{"same_lowering": ours == theirs}}))
for fault, traffic in {[(f, t) for f, t, _ in CASES]!r}:
    with faults.FAULTS[fault]():
        out = run_small({CELL!r}, traffic=dict(traffic))
    print(json.dumps({{"fault": fault, "correct": out["correct"],
                      "attempted": out["attempted"], "failed": out["failed"],
                      "count": out["device"]["count"],
                      "checks": {{k: v["value"] for k, v in out["checks"].items()}}}}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert lines[0] == {"same_lowering": True}
    return lines[1:]


@pytest.mark.parametrize("i", [0, 1], ids=["ycsb_b", "write_heavy"])
def test_sound_run_is_correct(runs, i):
    out = runs[i]
    assert out["correct"], out["checks"]
    assert out["count"] == 4
    assert out["attempted"] > 1000
    if i:
        assert out["failed"] > 0          # updates dropped at full windows


@pytest.mark.parametrize("i", [2, 3, 4], ids=[c[0] for c in CASES[2:]])
def test_planted_fault_is_not_correct(runs, i):
    out, number = runs[i], CASES[i][2]
    assert out["fault"] == CASES[i][0]
    assert not out["correct"]
    assert out["checks"][number] > 0, out["checks"]


def test_one_device_is_refused():
    from bench import harness
    from conftest import small_cell

    with pytest.raises(harness.Refused, match="needs 4 chips"):
        harness.run(CELL, 1, 1.0, False, 0.0, found=small_cell(CELL),
                    require_tpu=False)


def _view(stage_map):
    """Two chips, two executions each of the four-chip scan in the window,
    2 ticks each: 8 ticks over 4 executions."""
    ops = [("fusion.1", 10, 40), ("fusion.2", 40, 100)]
    chip = lambda off: {
        "XLA Modules": [("jit__dist_openloop_scan(5)", 10 + off, 110 + off),
                        ("jit__dist_openloop_scan(5)", 200 + off, 300 + off)],
        "XLA Ops": [(n, s + off, e + off) for n, s, e in ops]
        + [(n, s + 190 + off, e + 190 + off) for n, s, e in ops]}
    view = {"planes": {"/host:CPU": {"python": [("bench_window", 0, 1000)]},
                       "/device:TPU:0": chip(0), "/device:TPU:1": chip(5)},
            "summary": {"window": (0, 1000)},
            "rec": {"fn_name": "_dist_openloop_scan", "ticks_per_segment": 2,
                    "window_ticks": (0, 8), "cross_chip_window": 64,
                    "msg_bytes": 60, "chips": 2, "lower_scan": None},
            "peak": {"ici_bits_per_s": 8e9}}
    view["stage_map"] = stage_map
    return view


def _read(name, view):
    from bench import harness

    return harness._module("readers", name).read(view)


def test_dist_readers_on_two_chips():
    view = _view({"fusion.1": "exchange", "fusion.2": "store"})
    # 4 ticks a chip; the scan runs 100 ns an execution, exchange 30 of it
    assert _read("dist_tick_ms", view) == pytest.approx(200e-6 / 4)
    assert _read("exchange_ms", view) == pytest.approx(60e-6 / 4)
    # 64 messages of 60 B over 2 chips and 8 ticks: 240 B a chip and tick,
    # over 15 ns at 1 GB/s
    assert _read("exchange_roofline_pct", view) == pytest.approx(
        100 * 240 / (15e-9 * 1e9))


def test_dist_readers_report_nothing_without_the_four_chip_scan():
    view = _view(None)                          # a program with no scopes
    assert _read("exchange_ms", view) is None
    assert _read("exchange_roofline_pct", view) is None
    view = _view({"fusion.1": "exchange"})
    del view["rec"]["cross_chip_window"]        # a one-chip run
    assert _read("dist_tick_ms", view) is None
    assert _read("exchange_roofline_pct", view) is None
