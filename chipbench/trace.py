"""From a profiler trace to the numbers the per-layer readers use.

A TPU trace holds a plane per device (``/device:TPU:<n>``) whose
``XLA Ops`` line has one event per executed HLO instruction, named by the
instruction's text (``%dequant_matmul.3 = ...``), and a ``/host:CPU``
plane whose thread lines carry the harness's own spans
(``chipbench.step_once``, ``chipbench.submit``), on the
same clock. :func:`load` keeps just those events; everything else here
works on the plain lists it returns, so a small recorded trace checks it
on the CPU."""
from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, List, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."
# control flow: these events enclose the operations they run, so they say
# nothing of their own about where the time goes
CONTAINERS = {"while", "conditional", "call"}

_INSTR = re.compile(r"^%?([\w.\-]+)(?: = |$)")


def op_name(event_name: str) -> str:
    """The instruction name of an ``XLA Ops`` event (``%fusion.3 = ...`` →
    ``fusion.3``)."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0]


def base_name(op: str) -> str:
    """An instruction name less its ``.N`` uniquifiers (``dequant_matmul.3``
    → ``dequant_matmul``)."""
    return re.sub(r"(\.\d+)+$", "", op)


def load(path) -> dict:
    """``{"devices": {plane: [(op, start_ns, dur_ns)]}, "spans": [(name,
    start_ns, dur_ns)]}`` from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices: Dict[str, list] = {}
    spans = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(e.name), e.start_ns, e.duration_ns)
                               for e in line.events)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint ones, in order."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy(ops, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The intervals within [lo, hi] in which some operation ran."""
    ivs = [(max(s, lo), min(s + d, hi)) for _, s, d in ops
           if s + d > lo and s < hi]
    return union([iv for iv in ivs if iv[1] > iv[0]])


def busy_ns(ops, lo: float, hi: float) -> float:
    return sum(e - s for s, e in busy(ops, lo, hi))


def gaps(ops, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The idle intervals within [lo, hi]."""
    out, t = [], lo
    for s, e in busy(ops, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: Tuple[float, float], spans) -> str:
    """The harness span that covers most of an idle gap, or ``other``."""
    best, cover = "other", 0.0
    for name, s, d in spans:
        c = min(gap[1], s + d) - max(gap[0], s)
        if c > cover:
            best, cover = name[len(SPAN_PREFIX):], c
    return best


def longest_gaps(ops, spans, lo, hi, n: int = 10) -> list:
    """The ``n`` longest idle gaps as ``[label, seconds]``."""
    gs = sorted(gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[label(g, spans), (g[1] - g[0]) / 1e9] for g in gs]


def top_ops(ops, lo, hi, n: int = 10) -> list:
    """The ``n`` operations that took most device time, by instruction
    name, leaving out control-flow containers: ``[name, seconds]``."""
    tot: Dict[str, float] = {}
    for name, s, d in ops:
        if s >= lo and s + d <= hi and base_name(name) not in CONTAINERS:
            tot[name] = tot.get(name, 0.0) + d
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def kernel_ns(ops, kernels, lo, hi) -> float:
    """Summed device time of the events of the named kernels."""
    return sum(d for name, s, d in ops
               if base_name(name) in kernels and s >= lo and s + d <= hi)
