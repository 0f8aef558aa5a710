"""The algorithm's necessary work per inner step, against hand counts."""

import os

from benchtiny import BENCH

import cells

TABULAR = cells.load_module(os.path.join(BENCH, "work", "tabular.py"),
                            "bench_work_tabular")
POLY2 = cells.load_module(os.path.join(BENCH, "work", "poly2.py"),
                          "bench_work_poly2")


def test_tabular_hand_count():
    # one practical run, m=2 agents, T=3 samples, n=4 states
    # samples: 2*3 * 4 ops, 2*3 * 8 B; agents: 2 * (4 + 8 + 1 + 8) ops,
    # 2 * (16 + 16) B; run: 12 ops, 64 B
    assert TABULAR.per_step(["practical"], 2, 3, 4) == {
        "flops": 24 + 42 + 12, "bytes": 48 + 64 + 64}
    # theoretical: samples 2*3 * 2 ops; agents 2 * (4 scale + 8 g.gradJ
    # + 12 g'Phi g + 1 + 8); run 12 gradJ + 12 update
    assert TABULAR.per_step(["theoretical"], 2, 3, 4) == {
        "flops": 12 + 66 + 24, "bytes": 48 + 64 + 64}


def test_tabular_garnet_cell_bytes():
    # 8 runs of m=1024, T=256, n=128: ~16.8 MB of samples dominate
    work = TABULAR.per_step(["theoretical"] * 4 + ["practical"] * 4,
                            1024, 256, 128)
    assert work["bytes"] == 8 * (1024 * 256 * 8 + 1024 * (512 + 16)
                                 + 16 * 128)


def test_poly2_hand_count():
    # practical, m=1, T=2, n=6: samples 2 * (3 + 24 + 14); agent
    # 6 + 12 + 1 + 12; run 18.  Bytes: 2 * 12, 24 + 16, 144 + 72
    assert POLY2.per_step(["practical"], 1, 2, 6) == {
        "flops": 82 + 31 + 18, "bytes": 24 + 40 + 216}
    # theoretical: samples 2 * (3 + 24); agent 6 + (72 + 24) + 1 + 12;
    # run (72 + 12) + 18
    assert POLY2.per_step(["theoretical"], 1, 2, 6) == {
        "flops": 54 + 115 + 102, "bytes": 24 + 40 + 216}


def test_work_adds_over_runs():
    one = POLY2.per_step(["practical"], 8, 16, 6)
    two = POLY2.per_step(["practical", "practical"], 8, 16, 6)
    assert two == {k: 2 * v for k, v in one.items()}
