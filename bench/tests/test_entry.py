"""The entry point's refusals and the data-driven layout of the cells."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT


def test_no_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run_cell.py"),
         "--workload", "netcraq.ycsb_b", "--seed", str((1 << 40) + 1),
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax

    from bench import harness

    class Fake:
        platform, device_kind = "tpu", "TPU v0 unknown"

    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()])
    with pytest.raises(harness.Refused, match="peaks.json"):
        harness.run("netcraq.ycsb_b", 1, 1.0, False, 0.0)


def test_too_few_chips_is_refused(monkeypatch):
    import jax

    from bench import harness

    class Fake:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()])
    found = harness.load_cell("netcraq.ycsb_b")
    found["cell"]["chips"] = 4
    with pytest.raises(harness.Refused, match="needs 4 chips"):
        harness.run("netcraq.ycsb_b", 1, 1.0, False, 0.0, found=found)


def test_every_cell_finds_its_files_by_name():
    from bench import harness

    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for cell in spec["workloads"]:
        found = harness.load_cell(cell["name"])
        assert os.path.exists(os.path.join(
            ROOT, "bench", "drivers", found["config"]["driver"] + ".py"))
        assert found["rate"]["ops_per_tick"] > 0
    for metric in spec["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "readers", metric["name"] + ".py"))
    names = {c["name"] for c in spec["workloads"]}
    for metric in spec["per_layer"]:
        assert set(metric["workloads"]) <= names
