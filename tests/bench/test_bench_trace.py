"""bench/trace_reduce.py on small traces: one written by hand, one cut from
a profile recorded on a TPU v5e (``bench/testdata/``)."""

import json
import os

import pytest

from benchtiny import BENCH

import trace_reduce


def _ev(name, start, dur, **stats):
    return {"name": name, "start_ns": start, "dur_ns": dur, "stats": stats}


HAND = {"planes": [
    {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        _ev("bench.window", 1000, 10000),
        _ev("bench.call", 1000, 500),
        _ev("bench.block", 1500, 8000),
        _ev("bench.call", 9500, 300),
        _ev("unrelated", 0, 99999),
    ]}]},
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [_ev("jit_step", 0, 20000)]},
        {"name": "XLA Ops", "events": [
            _ev("fusion.1", 500, 1000, tf_op="jit(f)/sample"),
            _ev("while.1", 1500, 6000),          # holds the next two ops
            _ev("fusion.2", 2000, 2000, tf_op="jit(f)/grad"),
            _ev("fusion.1", 6000, 1000, tf_op="jit(f)/sample"),
            _ev("copy.3", 10500, 2000),
        ]}]},
    {"name": "/device:TPU:0 SparseCore", "lines": [
        {"name": "XLA Ops", "events": [_ev("ignored", 1000, 10000)]}]},
]}


def test_hand_trace():
    r = trace_reduce.reduce(HAND)
    assert r["window_s"] == pytest.approx(10000e-9)
    # busy inside [1000, 11000): 500 + 2000 + 1000 + 500 ns; the while op
    # is left out (it holds other ops)
    assert r["busy_s"] == {"/device:TPU:0": pytest.approx(4000e-9)}
    assert r["idle_share"]["/device:TPU:0"] == pytest.approx(0.6)
    ops = dict((k, v) for k, v in r["device_ops"])
    assert set(ops) == {"fusion.1 jit(f)/sample", "fusion.2 jit(f)/grad",
                        "copy.3"}
    assert ops["fusion.1 jit(f)/sample"] == pytest.approx(1500e-9)
    assert ops["fusion.2 jit(f)/grad"] == pytest.approx(2000e-9)
    assert ops["copy.3"] == pytest.approx(500e-9)
    assert r["device_ops"][0][0] == "fusion.2 jit(f)/grad"
    # gaps [7000, 10500), [4000, 6000), [1500, 2000): the host was inside
    # bench.block at the middle of each
    assert r["idle_gaps"] == [["bench.block", pytest.approx(3500e-9)],
                              ["bench.block", pytest.approx(2000e-9)],
                              ["bench.block", pytest.approx(500e-9)]]


def test_window_must_be_unique():
    bad = {"planes": [{"name": "/host:CPU", "lines": [{"name": "p",
                                                        "events": []}]}]}
    with pytest.raises(ValueError):
        trace_reduce.reduce(bad)


def test_recorded_tpu_trace():
    path = os.path.join(BENCH, "testdata", "trace_v5e_garnet.json")
    with open(path) as f:
        rec = json.load(f)
    r = trace_reduce.reduce(rec["trace"])
    want = rec["expected"]
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert list(r["busy_s"]) == ["/device:TPU:0"]
    assert r["busy_s"]["/device:TPU:0"] == pytest.approx(want["busy_s"])
    assert 0.0 <= r["idle_share"]["/device:TPU:0"] <= 1.0
    assert [k for k, _ in r["device_ops"]] == want["top_ops"]
    assert all(name.startswith("bench.") for name, _ in r["idle_gaps"])
