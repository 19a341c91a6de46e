"""chip_smoke.py's phases at a tiny size on CPU, its refusal to run without
a TPU, and the compile-cache placement it relies on."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from helpers import REPO, run_with_devices


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_without_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_phase_openloop_tiny(smoke):
    row = smoke.phase_openloop(64, 2, seed=0, inject_capacity=8, ticks=16,
                               segment_ticks=8)
    assert row["ops_offered"] > 0
    assert row["ops_completed"] == row["ops_offered"] - row["ops_shed"]
    assert row["keys_written"] > 0
    assert row["inflight"] == 0 and row["leaked_locks"] == 0


def test_phase_txn_tiny(smoke):
    row = smoke.phase_txn(64, 2, n_txns=32, seed=0)
    assert row["txns_resolved"] == row["txns_offered"] == 32
    assert 2 * row["txns_committed"] > 32
    assert row["keys_written"] > 0


def test_phase_four_chips_tiny():
    out = run_with_devices(f"""
import importlib.util, json, jax
spec = importlib.util.spec_from_file_location(
    "chip_smoke", {os.path.join(REPO, "chip_smoke.py")!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
row = smoke.phase_four_chips(jax.devices(), num_keys=256, seed=1, steps=12)
print(json.dumps(row))
""", n_devices=4)
    row = json.loads(out.strip().splitlines()[-1])
    assert len(set(row["shard_devices"])) == 4
    assert row["ops_completed"] == row["ops_offered"]
    assert row["replica_reads_checked"] == 4 * row["acked_keys_read_back"] > 0


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_dir(tmp_path, placed):
    """``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache
    sits at the fixed ``<checkout>/.jax_cache``."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = ("import jax\n"
            "from repro.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True).stdout.split()
    want = str(tmp_path) if placed else os.path.join(REPO, ".jax_cache")
    assert out == [want, want]
