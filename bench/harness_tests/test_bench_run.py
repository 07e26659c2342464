"""A whole run at smoke size on the CPU, with the look for a chip
skipped: the clean program is ``correct``, and each fault planted in the
timed path underneath (``faults.py``), or the control put in the
program's place, makes ``correct`` come out false."""
import pytest

import faults
import run
from smoke_cell import smoke_cell


@pytest.mark.parametrize("fault", ("none",) + faults.FAULTS)
def test_correct_sees_each_fault(monkeypatch, fault):
    if fault != "none":
        for owner, attr, value in faults.patches(fault):
            monkeypatch.setattr(owner, attr, value)
    res = run.run("smoke", 2 ** 40 + 3, 2.0, False,
                  cell=smoke_cell("reasoning-batch"), check_device=False)
    assert res["attempted"] > 0 and res["check"]["tokens_compared"][
        "value"] >= 20
    assert res["correct"] is (fault == "none"), res["check"]
    assert list(res)[-1] == "check"


@pytest.mark.parametrize("seed", (17, 2 ** 40 + 3, 2200000101))
def test_control_reads_above_the_program(seed):
    """The fp8 control, put in the program's place and judged by the
    same comparison, comes out not correct."""
    res = run.run("smoke", seed, 2.0, False,
                  cell=smoke_cell("reasoning-batch"), check_device=False,
                  control=True)
    c = res["check"]
    assert c["tokens_compared"]["value"] >= c["tokens_compared"]["limit"]
    assert c["widest_gap"]["value"] > c["widest_gap"]["limit"]
    assert res["correct"] is False
