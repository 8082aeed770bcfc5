"""The control at smoke size, through the harness's own check: the
reference computed in float8, put in the program's place, comes out not
correct, where the program's own reading on the same run stays below the
limit."""
import time

import jax
import pytest

from chipbench import harness
from chipbench.tests.conftest import smoke_cell


@pytest.mark.parametrize("name", ["internlm2-1.8b.steady",
                                  "rwkv6-1.6b.steady"])
def test_control_fails_where_the_program_passes(name):
    cell = smoke_cell(name)
    res = harness.run(cell, 2 ** 32 + 1, 1.5, False, time.monotonic(),
                      jax.devices(), None, control=True)
    limit = cell.config["correct"]["widest_gap_limit"]
    assert res["failed"] == 0 and res["checks"]["compared_tokens"][0] >= 8
    assert res["readings"]["program_gap"] <= limit
    assert res["checks"]["widest_gap"] == [res["readings"]["control_gap"],
                                           limit]
    assert res["readings"]["control_gap"] > limit
    assert res["correct"] is False
    assert list(res)[-1] == "checks"
