"""The control at a size a CPU test run holds: the reference, its
contractions written out one and three bfloat16 passes deep (the chip's
"default" and "high"), put in the program's place, has to read worse than
the program on the number that guards precision; each planted fault has to
read worse on one number.  The CPU computes the program's own float32 alike
at every matmul precision, so its "program:<p>" controls are read on the
chip only."""

import os

from benchtiny import tiny_checkout

import control


def test_control_and_faults_separate_from_the_program(tmp_path):
    root = tiny_checkout(tmp_path)
    out = control.readings(root, "garnet_clean", seeds=[1, 2, 3],
                           control_seeds=[101, 102, 103],
                           faults=control.FAULTS,
                           controls=("reference:high", "reference:default"),
                           bench=os.path.join(root, "bench"),
                           log=lambda *_: None)
    s = control.summary(out)
    lower, upper = s["lower"], s["upper"]
    for c in ("reference:high", "reference:default"):
        assert upper[c]["j_eval"] > 3 * lower["j_eval"], (c, upper[c], lower)
    for fault in control.FAULTS:
        assert any(upper[fault][n] > 3 * lower[n]
                   for n in ("j_sim", "comm")), (fault, upper[fault], lower)
