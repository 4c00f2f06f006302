"""The reduction from a trace to busy time, idle gaps and per-operation
time, on a hand-made trace with overlapping events and on a small trace
recorded on the chip."""
import json
import os

import numpy as np
import pytest

from chipbench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "steps_trace.json")


def test_overlapping_events_by_hand():
    ops = [["a", 0, 10], ["b", 5, 10], ["a", 30, 10], ["c", 32, 2],
           ["b", 50, 5]]
    assert trace.union(trace.op_intervals(ops)) == [(0, 15), (30, 40),
                                                   (50, 55)]
    assert trace.busy_ns(ops, 0, 60) == 30
    assert trace.busy_ns(ops, 8, 35) == 12  # clipped at both ends
    assert trace.gaps(ops, 0, 60) == [(15, 30), (40, 50), (55, 60)]
    top = trace.top_ops(ops, 0, 60)
    assert [n for n, _ in top] == ["a", "b", "c"]
    np.testing.assert_allclose([t for _, t in top], [20e-9, 15e-9, 2e-9])
    spans = [["chipbench.window", 0, 60], ["chipbench.batch", 14, 10],
             ["chipbench.callback", 41, 3]]
    gaps = trace.labelled_gaps(ops, spans, 0, 60)
    assert [n for n, _ in gaps] == ["batch", "callback", "trainer loop"]
    np.testing.assert_allclose([t for _, t in gaps], [15e-9, 10e-9, 5e-9])
    assert trace.op_name("%fusion.12 = bf16[8] fusion(...)") == "%fusion.12"


def _timeline(ops, lo, hi, step):
    """Busy flags on a grid of ``step`` ns: the brute-force reading."""
    t = np.arange(lo, hi, step) + step / 2
    busy = np.zeros(t.shape, bool)
    for _, s, d in ops:
        busy |= (t >= s) & (t < s + d)
    return busy


def test_recorded_chip_trace_against_a_timeline():
    rec = json.load(open(DATA))
    lo, hi = rec["window_ns"]
    ops = rec["devices"][0]
    step = 1000.0
    busy = _timeline(ops, lo, hi, step)
    assert trace.busy_ns(ops, lo, hi) == pytest.approx(
        busy.sum() * step, abs=2 * step * len(ops))
    gaps = trace.gaps(ops, lo, hi)
    idle = sum(e - s for s, e in gaps)
    assert idle + trace.busy_ns(ops, lo, hi) == pytest.approx(hi - lo)
    # the step boundary: the device waits while the host runs the loop
    longest = trace.labelled_gaps(ops, rec["spans"], lo, hi, k=1)[0]
    assert longest[1] > 1e-4
    top = trace.top_ops(ops, lo, hi, k=3)
    assert len(top) == 3 and top[0][1] >= top[1][1] >= top[2][1]
    assert all(not n.count(" = ") for n, _ in top)
