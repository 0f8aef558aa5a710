"""Cells, configurations, traffic mixes, limits and metrics found by name."""

import json
import os

import pytest

from benchtiny import BENCH, REPO

import cells


def test_every_cell_resolves_from_the_committed_files():
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for w in manifest["workloads"]:
        res = cells.resolve(REPO, w["name"])
        assert res["config"]["name"] == w["config"]
        assert res["traffic"]["name"] == w["traffic"]
        for path in (res["program_env"], res["reference_env"], res["work"]):
            assert os.path.isfile(path)
        assert {m["name"] for m in res["end_to_end"]} >= {"samples_per_s",
                                                          "setup_s"}
        for m in res["per_layer"]:
            assert os.path.isfile(os.path.join(res["metrics_dir"],
                                               f"{m['name']}.py"))


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        cells.resolve(REPO, "no_such_cell")


def test_new_files_and_entries_add_a_cell_and_a_metric(tmp_path):
    """A configuration, a traffic mix, a cell and a per-layer metric come in
    as new files plus BENCHMARK.json entries; no existing file changes."""
    import shutil
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench)
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cfg = json.load(open(bench / "configs" / "garnet-s128-m1024.json"))
    cfg.update(name="garnet-s32-m128", num_states=32, features=32,
               num_agents=128)
    (bench / "configs" / "garnet-s32-m128.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "lossy10.json").write_text(json.dumps(
        {"name": "lossy10", "channel": {"drop_prob": 0.1, "delay": 0},
         "seeds_per_call": 1, "mesh_devices": 0}))
    (bench / "limits" / "small_lossy.json").write_text(json.dumps(
        {"limits": {"j_eval": 1.0}}))
    (bench / "metrics" / "calls_in_window.py").write_text(
        "def read(ctx):\n    return float(ctx['calls'])\n")
    manifest["workloads"].append(
        {"name": "small_lossy", "config": "garnet-s32-m128",
         "traffic": "lossy10", "chips": 1, "why": "test"})
    manifest["per_layer"].append(
        {"name": "calls_in_window", "unit": "count", "better": "higher",
         "source": "host_clock", "layer": "entry and set-up",
         "moves": "samples_per_s", "workloads": ["small_lossy"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    res = cells.resolve(str(tmp_path), "small_lossy", bench=str(bench))
    assert res["config"]["num_states"] == 32
    assert res["config"]["w0"] == [0.0] * 32
    assert res["traffic"]["channel"]["drop_prob"] == 0.1
    assert res["limits"] == {"j_eval": 1.0}
    names = [m["name"] for m in res["per_layer"]]
    assert "calls_in_window" in names
    assert cells.read_metric(res["metrics_dir"], "calls_in_window",
                             {"calls": 3}) == 3.0
    # the new metric lists its cell, so the old cells do not report it
    old = cells.resolve(str(tmp_path), "garnet_clean", bench=str(bench))
    assert "calls_in_window" not in [m["name"] for m in old["per_layer"]]


def test_peaks_by_device_kind():
    v5e = cells.peaks(BENCH, "TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cells.peaks(BENCH, "TPU v9 imaginary")


def test_metric_readers_return_nothing_without_a_trace():
    ctx = {"trace": None, "peaks": None, "calls": 2, "chips": 1,
           "window_s": 1.0, "num_iterations": 10,
           "work_per_step": {"flops": 1, "bytes": 1},
           "window_bounds": (1.0, 2.0), "setup_bounds": (0.0, 1.0),
           "compile_events": [("/jax/core/compile/backend_compile_duration",
                               0.5, 0.25),
                              ("/jax/core/compile/backend_compile_duration",
                               1.5, 0.01)],
           "backend_compile_event":
               "/jax/core/compile/backend_compile_duration"}
    metrics = os.path.join(BENCH, "metrics")
    assert cells.read_metric(metrics, "device_idle_share", ctx) is None
    assert cells.read_metric(metrics, "step_roofline_share", ctx) is None
    assert cells.read_metric(metrics, "compiles_in_window", ctx) == 1.0
    assert cells.read_metric(metrics, "compile_s", ctx) == 0.25
    ctx["peaks"] = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    ctx["work_per_step"] = {"flops": 100, "bytes": 4}
    # least time max(1 s, 0.4 s) over 0.05 s per step measured -> 2000 %
    assert cells.read_metric(metrics, "step_roofline_share", ctx) == \
        pytest.approx(2000.0)
