"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
phases — driven here at a tiny size, kernels interpreted — run end to end.
The chip run itself is ``python chip_smoke.py`` on a TPU host."""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = dict(num_states=8, num_agents=4, num_samples=8, num_iterations=12)


def test_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--out", str(tmp_path / "out")],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr
    assert not (tmp_path / "out").exists()


@pytest.fixture(scope="module")
def swept():
    lines = []
    results, failures = chip_smoke.phase_sweep(TINY, "cpu", lines.append)
    return results, failures, lines


def test_sweep_phase_runs_every_step_path(swept):
    results, failures, lines = swept
    assert failures == []
    assert set(results) == set(chip_smoke.PATHS)
    for res in results.values():
        assert res.comm_rate.shape == (2, 4, 1, 2)
    assert sum(line.startswith("agreement") for line in lines) == 2


def test_store_phase_answers_from_the_registry(swept, tmp_path):
    results, _, lines = swept
    assert chip_smoke.phase_store(results[chip_smoke.PATHS[0]], TINY,
                                  str(tmp_path), lines.append) == []
    assert os.listdir(tmp_path / "store")


def test_resume_phase_runs_two_segments(swept, tmp_path):
    results, _, lines = swept
    assert chip_smoke.phase_resume(results[chip_smoke.PATHS[0]], TINY,
                                   str(tmp_path), lines.append) == []
    assert "segments=2" in lines[-1]


def test_sharded_phase_on_the_visible_devices():
    lines = []
    assert chip_smoke.phase_sharded(TINY, jax.device_count(), "cpu",
                                    lines.append) == []
    assert len(lines) == 2


def test_agreement_check_rejects_a_moved_comm_rate(swept):
    results, _, _ = swept
    ref = results[chip_smoke.PATHS[0]]
    moved = ref._replace(comm_rate=ref.comm_rate + 2 * chip_smoke.COMM_ATOL)
    assert chip_smoke.agrees(chip_smoke.compare(ref, ref))
    assert not chip_smoke.agrees(chip_smoke.compare(moved, ref))


def test_result_line_is_the_contracted_json():
    info = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4,
            "extra": "dropped"}
    assert json.loads(chip_smoke.result_line(info)) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}}
