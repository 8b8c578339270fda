"""The control of ``correct``: the reference put in the program's place and
computed in bfloat16, one precision below the configurations' float32,
has to come out not correct under each cell's limits, while the program
comes out correct. ``calibrate.py`` reads the same numbers on the chip at
the cells' own sizes; here they are read at toy widths on the CPU."""
import pytest

from chipbench import calibrate, compare
from chipbench.conftest import MIXES, SEED


@pytest.mark.parametrize("name", MIXES)
def test_control_fails_and_program_passes(toy, name):
    config, workload = toy(name)
    got = calibrate.readings(name, SEED + 1, True, config=config,
                             workload=workload)
    limits = workload["limits"]
    assert compare.judge(got["program"], limits)[0], got["program"]
    for fault in ("control", "half_batch", "unchanged"):
        assert not compare.judge(got[fault], limits)[0], (fault, got[fault])
