"""Reduce a profiler trace to the numbers the per-layer metrics read.

``extract`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
small JSON-able record: per device, the operations that ran on it
(name, start, duration, in nanoseconds on the trace's clock), and the
host spans that the benchmark itself opened (names starting with
``chipbench.``).  Everything else here works on that record, so the
reduction can be checked on a recorded trace without a chip.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."

Interval = Tuple[float, float]


def extract(logdir: str) -> Dict:
    """{"devices": [[[name, start_ns, dur_ns], ...], ...] (one list per
    TPU plane, in plane order), "spans": [[name, start_ns, dur_ns], ...]}
    from the newest trace under ``logdir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(files[-1])
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend([op_name(e.name), float(e.start_ns),
                                float(e.duration_ns)] for e in line.events)
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def op_name(hlo: str) -> str:
    """An operation's name without its HLO text (``%fusion.12 = ...``)."""
    return hlo.split(" = ", 1)[0]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping [start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_intervals(ops: Sequence) -> List[Interval]:
    return [(s, s + d) for _, s, d in ops]


def busy_ns(ops: Sequence, lo: float, hi: float) -> float:
    """Time in [lo, hi) during which some operation ran on the device."""
    return sum(e - s for s, e in union(clip(op_intervals(ops), lo, hi)))


def gaps(ops: Sequence, lo: float, hi: float) -> List[Interval]:
    """Idle intervals of the device within [lo, hi)."""
    out, t = [], lo
    for s, e in union(clip(op_intervals(ops), lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def span_window(rec: Dict, name: str) -> Interval:
    """[start, end) of the first host span called ``name``."""
    for n, s, d in rec["spans"]:
        if n == name:
            return s, s + d
    raise KeyError(f"no host span {name!r} in the trace")


def top_ops(ops: Sequence, lo: float, hi: float, k: int = 10
            ) -> List[List]:
    """The ``k`` operation names with the most device time in [lo, hi),
    as [name, seconds]."""
    tot: Dict[str, float] = {}
    for name, s, d in ops:
        ov = min(s + d, hi) - max(s, lo)
        if ov > 0:
            tot[name] = tot.get(name, 0.0) + ov
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t * 1e-9] for n, t in best]


def labelled_gaps(ops: Sequence, spans: Sequence, lo: float, hi: float,
                  k: int = 10, default: str = "trainer loop") -> List[List]:
    """The ``k`` longest idle gaps in [lo, hi), each labelled by the host
    span (other than the window's own) that covers most of it, as
    [label, seconds]."""
    out = []
    for gs, ge in gaps(ops, lo, hi):
        best, cover = default, 0.0
        for name, s, d in spans:
            if name.endswith(".window"):
                continue
            ov = min(s + d, ge) - max(s, gs)
            if ov > cover:
                best, cover = name[len(SPAN_PREFIX):], ov
        out.append([best, (ge - gs) * 1e-9])
    return sorted(out, key=lambda g: -g[1])[:k]
