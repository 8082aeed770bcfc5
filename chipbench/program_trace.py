"""The program's own marks in the trace a traced run wrote.

:func:`chipbench.trace.load` keeps the device's operations and the
harness's spans. This module reads the same ``.xplane.pb`` for what the
program writes there: the engine's ``serve.*`` spans, one ``serve.step``
per ``ServeEngine.step_once`` around one span per host phase, each step
ending with the engine's counters as attributes. A run of a program
without them finds none, and the readers that use this module then
report nothing."""
from __future__ import annotations

import functools
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from chipbench import trace as tr

SPAN_PREFIX = "serve."
OUT = Path(__file__).resolve().parent / "out"

# host phase of the engine -> group of ``host_idle_pct``; idle time under
# any other span (serve.device_wait, serve.step between its phases, the
# harness's spans) or under none is ``other``
IDLE_GROUP = {"serve.logits_to_host": "logits_to_host",
              "serve.sample": "sample",
              "serve.admit": "admit", "serve.refill": "admit",
              "serve.seat": "admit",
              "serve.assemble": "dispatch", "serve.dispatch": "dispatch"}


@functools.lru_cache(maxsize=4)
def _load(path: str, mtime: float) -> dict:
    from jax.profiler import ProfileData
    spans, harness = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name, e.start_ns, e.duration_ns,
                                  {k: v for k, v in e.stats}))
                elif e.name.startswith(tr.SPAN_PREFIX):
                    harness.append((e.start_ns, e.start_ns + e.duration_ns))
    window = (min(s for s, _ in harness), max(e for _, e in harness)) \
        if harness else None
    return {"spans": sorted(spans, key=lambda s: s[1]), "window": window}


def load(path) -> dict:
    """``{"spans": [(name, start_ns, dur_ns, attributes)], "window": (lo,
    hi)}`` from an ``.xplane.pb`` file: the program's spans in order of
    start, and the window the harness's spans open and close (as
    ``harness.read_trace`` takes it), or None."""
    p = Path(path)
    return _load(str(p), p.stat().st_mtime)


def of_run(ctx) -> Optional[list]:
    """The program's spans in the trace that ``ctx.trace`` was read from:
    the newest trace under the cell's output directories
    (``out/<cell>.<seed>/trace``) whose harness spans open and close the
    same window. None without a trace or without program spans; raises
    if traces there hold program spans but none is this run's."""
    t = getattr(ctx, "trace", None)
    if t is None:
        return None
    found = sorted(OUT.glob(f"{ctx.cell.name}.*/trace/**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime, reverse=True)
    recs = [load(path) for path in found[:4]]
    for rec in recs:
        if rec["window"] == (t.lo, t.hi):
            return rec["spans"] or None
    if any(rec["spans"] for rec in recs):
        raise LookupError(f"chipbench: no trace under {OUT} has this run's "
                          f"window ({t.lo}, {t.hi}); the newest hold "
                          f"program spans of other runs")
    return None


def innermost(spans, lo: float, hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut into pieces, each with the name of the shortest span
    that covers it (``""`` where none does). Spans are (name, start, dur,
    ...) and nest, as the spans of one thread do."""
    cuts = sorted({lo, hi} | {min(max(t, lo), hi) for _, s, d, *_ in spans
                              for t in (s, s + d)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        m = (a + b) / 2
        cover = [(d, n) for n, s, d, *_ in spans if s <= m < s + d]
        out.append((a, b, min(cover)[1] if cover else ""))
    return out


def idle_by_group(ops, spans, lo: float, hi: float) -> Dict[str, float]:
    """The idle time of the device within [lo, hi], in ns, by the group of
    the innermost program span that covers it (:data:`IDLE_GROUP`, else
    ``other``). The groups sum to the window's idle time."""
    out = dict.fromkeys(("logits_to_host", "sample", "admit", "dispatch",
                         "other"), 0.0)
    pieces = innermost(spans, lo, hi)
    j = 0
    for a, b in tr.gaps(ops, lo, hi):
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            s, e, name = pieces[k]
            out[IDLE_GROUP.get(name, "other")] += min(e, b) - max(s, a)
            k += 1
    return out


def idle_pct(ctx, group: str) -> Optional[float]:
    """``host_idle_pct.<group>``: the share of the traced window in which
    the device was idle under that group's program spans. None without
    device operations (a CPU run)."""
    spans = of_run(ctx)
    t = ctx.trace
    if spans is None or not t.ops or t.window_s <= 0:
        return None
    ns = idle_by_group(t.ops, spans, t.lo, t.hi)
    return 100.0 * ns[group] / (t.hi - t.lo)


COUNTERS = ("steps_total", "tokens_valid", "tokens_computed",
            "tokens_emitted", "logits_host_bytes")


def counters(ctx) -> Optional[Dict[str, int]]:
    """The engine's counters (``ServeEngine.counters()``) as the last traced
    ``serve.step`` ends: they count from the engine's construction, and the
    harness builds the engine for the run and warms its steps without
    ``step_once``, so they cover the window's steps up to that one."""
    spans = of_run(ctx)
    if spans is None:
        return None
    steps = [a for n, _, _, a in spans
             if n == "serve.step" and all(k in a for k in COUNTERS)]
    if not steps:
        return None
    last = max(steps, key=lambda a: int(a["steps_total"]))
    return {k: int(last[k]) for k in COUNTERS}
