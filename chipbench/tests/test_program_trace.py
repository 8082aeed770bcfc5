"""The readers of the program's own spans (``program_trace.py``): idle time
booked to the innermost span, the five ``host_idle_pct`` groups summing to
the idle share (on made-up windows and on a window recorded from a chip
run, ``data/trace_internlm2_steady.json``), the engine's counters that
the ``serve.step`` spans carry, finding the run's own trace, and silence
where the program wrote nothing."""
import json
import os
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import harness, program_trace
from chipbench import trace as tr

GROUPS = ["logits_to_host", "sample", "admit", "dispatch", "other"]
FIXTURE = (Path(__file__).resolve().parents[1] / "data"
           / "trace_internlm2_steady.json")


def span(name, start, end, **attrs):
    return (name, float(start), float(end - start), attrs)


# two steps of a made-up engine over [0, 100): the device runs 10-30 and
# 60-80; the host assembles, dispatches, waits, copies the logits out,
# samples and refills around them, and a harness gap sits between the steps.
# Each step ends with the engine's counters since it was built: 3 untraced
# steps before these, then a T=8 step and a T=1 one over 32 slots
SPANS = [
    span("serve.step", 0, 50, T=8, steps_total=4, tokens_valid=140,
         tokens_computed=800, tokens_emitted=90,
         logits_host_bytes=9_000_000),
    span("serve.admit", 0, 4), span("serve.seat", 1, 3, rid=7, slot=2),
    span("serve.assemble", 4, 8), span("serve.dispatch", 8, 10),
    span("serve.device_wait", 10, 30), span("serve.logits_to_host", 30, 40),
    span("serve.sample", 40, 46), span("serve.refill", 46, 50),
    span("serve.step", 52, 100, T=1, steps_total=5, tokens_valid=172,
         tokens_computed=832, tokens_emitted=122,
         logits_host_bytes=10_000_000),
    span("serve.admit", 52, 54), span("serve.assemble", 54, 58),
    span("serve.dispatch", 58, 60), span("serve.device_wait", 60, 80),
    span("serve.logits_to_host", 80, 90), span("serve.sample", 90, 100),
]
OPS = [("fusion.1", 10.0, 20.0), ("dequant_matmul.2", 60.0, 20.0)]


def window(ops, lo=0.0, hi=100.0):
    busy = tr.busy_ns(ops, lo, hi)
    return SimpleNamespace(
        trace=SimpleNamespace(ops=ops, lo=lo, hi=hi, window_s=(hi - lo) / 1e9,
                              busy_s=busy / 1e9))


@pytest.fixture
def program(monkeypatch):
    """Serve made-up program spans in place of the run's trace file."""
    def use(spans):
        monkeypatch.setattr(program_trace, "of_run", lambda ctx: spans)
    return use


def test_each_piece_goes_to_the_innermost_span():
    pieces = program_trace.innermost(SPANS, 0.0, 100.0)
    at = lambda t: next(n for a, b, n in pieces if a <= t < b)
    assert at(2) == "serve.seat"            # inside admit, inside step
    assert at(0.5) == "serve.admit"
    assert at(20) == "serve.device_wait"
    assert at(51) == ""                      # between the steps
    assert sum(b - a for a, b, _ in pieces) == pytest.approx(100.0)


def test_idle_time_by_phase():
    ns = program_trace.idle_by_group(OPS, SPANS, 0.0, 100.0)
    assert ns == {"logits_to_host": 20.0, "sample": 16.0, "admit": 10.0,
                  "dispatch": 12.0, "other": 2.0}


@pytest.mark.parametrize("lo,hi", [(0.0, 100.0), (5.0, 95.0), (31.0, 61.0)])
def test_the_groups_sum_to_the_idle_share(program, lo, hi):
    program(SPANS)
    ctx = window(OPS, lo, hi)
    got = [harness.reader(f"host_idle_pct.{g}")(ctx) for g in GROUPS]
    assert sum(got) == pytest.approx(harness.reader("device_idle_pct")(ctx))
    assert all(v >= 0 for v in got)


def test_on_a_chip_trace_the_gap_is_the_logits_copy(program):
    rec = json.loads(FIXTURE.read_text())
    ops, lo, hi = rec["ops"], rec["lo"], rec["hi"]
    program([tuple(s) for s in rec["program_spans"]])
    ctx = window(ops, lo, hi)
    got = {g: harness.reader(f"host_idle_pct.{g}")(ctx) for g in GROUPS}
    idle = harness.reader("device_idle_pct")(ctx)
    assert sum(got.values()) == pytest.approx(idle, abs=0.1)
    # between two steps the host mostly copies the step's logits out
    assert max(got, key=got.get) == "logits_to_host"
    assert got["logits_to_host"] > idle / 2
    assert got["dispatch"] > got["sample"] > got["admit"] > 0


def test_step_counts(program):
    """The counters of the last step cover the whole window, not only the
    traced steps, whatever order the spans come in."""
    program(SPANS[::-1])
    ctx = window([])
    assert harness.reader("valid_token_share")(ctx) == pytest.approx(
        100.0 * 172 / 832)
    assert harness.reader("logits_host_mb_per_token")(ctx) == pytest.approx(
        10.0 / 122)


def _traces(monkeypatch, tmp_path, recs):
    """Trace files of runs of one cell, newest last, each read as ``recs``
    gives it (program spans and the harness's window)."""
    monkeypatch.setattr(program_trace, "OUT", tmp_path)
    by_path = {}
    for i, rec in enumerate(recs):
        path = tmp_path / f"c.{i}" / "trace" / "t.xplane.pb"
        path.parent.mkdir(parents=True)
        path.write_bytes(b"")
        os.utime(path, (1000 + i, 1000 + i))
        by_path[str(path)] = rec
    monkeypatch.setattr(program_trace, "load", lambda p: by_path[str(p)])
    return SimpleNamespace(cell=SimpleNamespace(name="c"),
                           trace=SimpleNamespace(lo=0.0, hi=100.0))


@pytest.mark.parametrize("newest_first", [True, False])
def test_the_run_finds_its_own_trace(monkeypatch, tmp_path, newest_first):
    mine = {"spans": SPANS[:1], "window": (0.0, 100.0)}
    other = {"spans": SPANS[9:10], "window": (5.0, 95.0)}
    ctx = _traces(monkeypatch, tmp_path,
                  [other, mine] if newest_first else [mine, other])
    assert program_trace.of_run(ctx) == SPANS[:1]


def test_a_lost_trace_is_an_error_not_silence(monkeypatch, tmp_path):
    """Traces of the cell hold program spans but none has this run's
    window: the program wrote spans, so reporting nothing would hide a
    fault of the benchmark."""
    other = {"spans": SPANS, "window": (5.0, 95.0)}
    ctx = _traces(monkeypatch, tmp_path, [other])
    with pytest.raises(LookupError):
        program_trace.of_run(ctx)
    with pytest.raises(LookupError):
        harness.reader("valid_token_share")(ctx)


def test_a_program_without_spans_reads_nothing(monkeypatch, tmp_path):
    """A program that writes no ``serve.*`` spans, as before the engine
    wrote them: its metrics are left out, whether or not the window is
    found."""
    ctx = _traces(monkeypatch, tmp_path,
                  [{"spans": [], "window": (5.0, 95.0)},
                   {"spans": [], "window": None}])
    assert program_trace.of_run(ctx) is None
    assert harness.reader("logits_host_mb_per_token")(ctx) is None


@pytest.mark.parametrize("name", ["valid_token_share",
                                  "logits_host_mb_per_token"]
                         + [f"host_idle_pct.{g}" for g in GROUPS])
def test_silent_without_program_spans(program, name):
    read = harness.reader(name)
    assert read(SimpleNamespace(trace=None)) is None
    program(None)                            # a program that wrote none
    assert read(window(OPS)) is None


def test_idle_groups_are_silent_without_a_device(program):
    program(SPANS)                           # a CPU trace: no device plane
    assert harness.reader("host_idle_pct.other")(window([])) is None


def test_a_traced_run_reads_the_engine_spans(tmp_path, monkeypatch):
    """A whole traced run at smoke size on the CPU: the readers find the
    run's own trace under the cell's output directory and read the
    engine's step counts; with no device plane the idle groups stay
    silent."""
    from chipbench.tests.conftest import run_smoke
    name = "internlm2-1.8b.steady"
    monkeypatch.setattr(program_trace, "OUT", tmp_path)
    res = run_smoke(name, trace=True, out=tmp_path / f"{name}.1")
    m = res["metrics"]
    assert 0 < m["valid_token_share"]["value"] < 100
    assert m["logits_host_mb_per_token"]["value"] > 0
    assert not [k for k in m if k.startswith("host_idle_pct")]
