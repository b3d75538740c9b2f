"""The control has to come out as not correct, and so has each planted
fault: at toy size on the CPU, through the same `compare` and the same
reference code that the chip readings of PERF.md were taken with."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import control  # noqa: E402
import toy  # noqa: E402


@pytest.mark.parametrize("kind,chips", [("lm", 1), ("lm", 4), ("resnet", 1)])
def test_control_and_faults_are_not_correct(kind, chips):
    spec = toy.spec(kind, chips=chips)
    got = control.readings(spec, seed=7)
    assert "control" in got and "half_batch" in got
    assert ("no_exchange" in got) == (chips > 1)
    for name, (correct, numbers, _) in got.items():
        assert not correct, (name, numbers)
        over = [k for k, v in numbers.items()
                if v > spec.cell["limits"][k]]
        assert over, (name, numbers)
