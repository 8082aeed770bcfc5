"""The reduction from trace to metrics, on a small window recorded from a
chip run (``data/trace_paper_decode.json``): busy union, idle gaps and
their labels, kernel time by name."""
import json
from pathlib import Path

import pytest

from chipbench import trace as tr

FIXTURE = Path(__file__).resolve().parents[1] / "data" / "trace_paper_decode.json"


@pytest.fixture(scope="module")
def rec():
    return json.loads(FIXTURE.read_text())


def covered(ops, lo, hi):
    """Busy time the slow way: every elementary interval between event
    boundaries, counted if some event covers its midpoint."""
    cuts = sorted({lo, hi} | {min(max(t, lo), hi) for _, s, d in ops
                              for t in (s, s + d)})
    total = 0.0
    for a, b in zip(cuts, cuts[1:]):
        m = (a + b) / 2
        if any(s <= m < s + d for _, s, d in ops):
            total += b - a
    return total


def test_instruction_names():
    assert tr.op_name("%fusion.65 = s32[4]{0} fusion(s32[64,4] %x)") == \
        "fusion.65"
    assert tr.op_name("%dequant_matmul.3 = bf16[64,768] custom-call()") == \
        "dequant_matmul.3"
    assert tr.base_name("dequant_matmul_t.1.2") == "dequant_matmul_t"
    assert tr.base_name("copy-start.17") == "copy-start"


def test_busy_union_matches_a_brute_force_sweep(rec):
    ops, lo, hi = rec["ops"], rec["lo"], rec["hi"]
    assert tr.busy_ns(ops, lo, hi) == pytest.approx(covered(ops, lo, hi),
                                                    rel=1e-9)
    idle = sum(b - a for a, b in tr.gaps(ops, lo, hi))
    assert idle + tr.busy_ns(ops, lo, hi) == pytest.approx(hi - lo, rel=1e-9)


def test_the_host_gap_between_steps_is_labelled(rec):
    gaps = tr.longest_gaps(rec["ops"], rec["spans"], rec["lo"], rec["hi"])
    label, seconds = gaps[0]
    # the engine's host work after a step: logits to the host, sampling
    assert label == "step_once"
    assert 1e-3 < seconds < (rec["hi"] - rec["lo"]) / 1e9
    assert all(s <= seconds for _, s in gaps)


def test_kernel_time_by_name(rec):
    ops, lo, hi = rec["ops"], rec["lo"], rec["hi"]
    inside = [o for o in ops if o[1] >= lo and o[1] + o[2] <= hi]
    want = sum(d for n, _, d in inside
               if n.split(".")[0] in ("dequant_matmul", "dequant_matmul_t"))
    got = tr.kernel_ns(ops, {"dequant_matmul", "dequant_matmul_t"}, lo, hi)
    assert got == want > 0
    assert tr.kernel_ns(ops, {"no_such_kernel"}, lo, hi) == 0


def test_top_ops_leave_out_control_flow(rec):
    top = tr.top_ops(rec["ops"], rec["lo"], rec["hi"])
    assert len(top) == 10
    assert all(tr.base_name(n) not in tr.CONTAINERS for n, _ in top)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
