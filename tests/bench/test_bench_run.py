"""bench/run.py end to end on the CPU, at tiny sizes."""

import json
import os
import subprocess
import sys

import pytest

from benchtiny import REPO, tiny_checkout

import run


def _cli(cwd, *args, env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "garnet_clean",
         "--seed", "3", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result_line():
    proc = _cli(REPO)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_benchmark_files_alone_exit_nonzero_without_a_result_line(tmp_path):
    """A directory with BENCHMARK.json and the benchmark's paths alone holds
    no system under test."""
    import shutil
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in manifest["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _cli(str(tmp_path), env={"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("cell", ["garnet_clean", "linsys_clean",
                                  "garnet_lossy"])
def test_tiny_run_is_correct_and_reports_the_cell_metrics(tmp_path, cell):
    root = tiny_checkout(tmp_path)
    code, res = run.run_cell(root, cell, 2**31 + 11, 0.2, False,
                             require_chip=False,
                             bench=os.path.join(root, "bench"))
    assert code == 0
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 8
    assert set(res["metrics"]) == {"samples_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    want = {"j_eval", "j_sim", "comm"}
    if cell == "garnet_lossy":
        want |= {"delivered", "delivered_over_sent"}
    assert set(res["checks"]) == want


def test_tiny_sharded_run_on_four_cpu_devices(tmp_path):
    """The four-chip cell ``garnet_clean_x4`` on four CPU devices."""
    root = tiny_checkout(tmp_path)
    code = (
        "import sys, json; sys.path.insert(0, 'bench'); import run; "
        "c, r = run.run_cell('.', 'garnet_clean_x4', 5, 0.2, False, "
        "require_chip=False, bench='bench'); print(json.dumps(r))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    res = json.loads(proc.stdout.splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["checks"]["devices_missing"]["value"] == 0
    assert res["attempted"] % 32 == 0


def test_cell_without_limits_exits_nonzero(tmp_path):
    root = tiny_checkout(tmp_path)
    os.remove(os.path.join(root, "bench", "limits", "garnet_clean.json"))
    code, res = run.run_cell(root, "garnet_clean", 1, 0.2, False,
                             require_chip=False,
                             bench=os.path.join(root, "bench"))
    assert code != 0 and res is None
