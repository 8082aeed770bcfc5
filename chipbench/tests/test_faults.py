"""The check catches a broken timed path: each fault a serving cell can
have (``chipbench/faults.py``), planted under a whole CPU run at smoke
size, makes ``correct`` come out false through the widest-gap comparison.
(A one-chip cell has no exchange between chips to leave out.)"""
import pytest

from chipbench import faults
from chipbench.tests.conftest import run_smoke


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("name", ["internlm2-1.8b.steady",
                                  "rwkv6-1.6b.steady"])
def test_fault_fails_the_check(name, fault):
    undo = faults.plant(fault)
    try:
        res = run_smoke(name, seed=7)
    finally:
        undo()
    gap, limit = res["checks"]["widest_gap"]
    assert gap > limit
    assert res["correct"] is False
