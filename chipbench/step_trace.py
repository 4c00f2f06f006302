"""Read the program's own scopes, spans and counters out of a profiler
trace: where inside the fused train step the device time goes, and what
the host loop was doing while the device waited.

``extract`` adds two keys to the record that ``trace.extract`` makes of
the same trace: ``program_spans``, the host spans the trainer opens
(names starting with ``trainer.``), and ``op_scopes``, the scope path
(the HLO ``op_name``) of every device operation, per device, parallel to
``devices``.  The rest works on that record, so it can be checked on a
recorded trace without a chip.

Scopes the program opens (``train/state.py``, ``models/transformer.py``,
``optim/bucketing.py``): ``fwd_bwd`` (with ``embed``, ``layers`` and
``ce`` inside, and backward operations under ``transpose(jvp(...))``),
``clip``, ``optimizer`` (with ``polar`` and one ``bucket_<B>x<m>x<n>``
per bucket inside).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace

PROGRAM_PREFIX = "trainer."
STEP_SPAN = "trainer.step"
SYNC_SPAN = "trainer.sync"
STEP_SCOPES = ("fwd_bwd", "clip", "optimizer")
UNSCOPED = "unscoped"

_HLO_LINE = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*op_name="([^"]*)"')
_SEGMENT = re.compile(r"^(?:[\w]+\()*([^()]*)\)*$")


# ------------------------------------------------------------- reading


def extract(logdir: str, hlo_text: str) -> Dict:
    """{"program_spans": [[name, start_ns, dur_ns], ...], "op_scopes":
    [[path, ...], ...]} from the newest trace under ``logdir``, the
    device operations in the order ``trace.extract`` lists them.  A TPU
    trace names each operation by its HLO text without metadata, so its
    path ("" if none) comes from ``hlo_text``, the text of the compiled
    program that ran (``compiled.as_text()``)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    by_name = hlo_scopes(hlo_text)
    pd = ProfileData.from_file(files[-1])
    scopes, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith(trace.DEVICE_PLANE):
            paths = []
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    paths.extend(
                        by_name.get(trace.op_name(e.name).lstrip("%"), "")
                        for e in line.events)
            scopes.append(paths)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                             for e in line.events
                             if e.name.startswith(PROGRAM_PREFIX))
    return {"program_spans": spans, "op_scopes": scopes}


def hlo_scopes(text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` for every instruction of an HLO
    module's text that carries one."""
    out = {}
    for line in text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


# ------------------------------------------------------------- scopes


def scope_names(path: str) -> List[str]:
    """The scopes of an op name, transformations taken off:
    ``jit(f)/fwd_bwd/transpose(jvp(layers))/while`` ->
    ``["f", "fwd_bwd", "layers", "while"]``."""
    out = []
    for seg in path.split("/"):
        m = _SEGMENT.match(seg)
        out.append(m.group(1) if m else seg)
    return out


def label(path: str) -> str:
    """The part of the step an operation belongs to: ``fwd_bwd/ce``,
    ``fwd_bwd/backward``, ``fwd_bwd/forward``, ``clip``,
    ``optimizer/<bucket>`` (a bucket's chain), ``optimizer/polar`` (the
    rest of the polar call), ``optimizer/other``, or ``unscoped``."""
    names = scope_names(path)
    top = next((n for n in names if n in STEP_SCOPES), None)
    if top == "fwd_bwd":
        if "ce" in names:
            return "fwd_bwd/ce"
        # value_and_grad stages the backward pass as transpose(jvp(...))
        return ("fwd_bwd/backward" if "transpose(" in path
                else "fwd_bwd/forward")
    if top == "optimizer":
        bucket = next((n for n in names if n.startswith("bucket_")), None)
        if bucket:
            return "optimizer/" + bucket
        return "optimizer/polar" if "polar" in names else "optimizer/other"
    return top or UNSCOPED


def exclusive_ns(ops: Sequence, labels: Sequence[str], lo: float,
                 hi: float) -> Dict[str, float]:
    """Device time in [lo, hi) by label, each instant given to the
    innermost operation running then (the one that started last: a loop
    contains its body's operations).  The values add up to
    ``trace.busy_ns(ops, lo, hi)``."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    out: Dict[str, float] = {}
    stack: List[Tuple[float, str]] = []  # (end, label), innermost last
    t = lo

    def advance(to: float) -> None:
        nonlocal t
        while t < to:
            while stack and stack[-1][0] <= t:
                stack.pop()
            if not stack:
                t = to
                return
            end = min(stack[-1][0], to)
            a, b = max(t, lo), min(end, hi)
            if b > a:
                out[stack[-1][1]] = out.get(stack[-1][1], 0.0) + (b - a)
            t = end

    for i in order:
        _, s, d = ops[i]
        if s >= hi:
            break
        advance(s)
        t = max(t, s)
        stack.append((s + d, labels[i]))
    advance(hi)
    return out


# ---------------------------------------------------------------- steps


def step_spans(rec: Dict, lo: float, hi: float) -> List[Tuple[float, float]]:
    """[start, end) of every whole ``trainer.step`` span in [lo, hi).  A
    step's device work runs inside its span: it is dispatched there and
    the span waits for its loss."""
    return sorted((s, s + d) for n, s, d in rec.get("program_spans", ())
                  if n == STEP_SPAN and s >= lo and s + d <= hi)


def device_ms_by_label(rec: Dict, lo: float, hi: float, chips: int = 1
                       ) -> Optional[Dict[str, float]]:
    """Device ms per step by ``label``, over the whole steps in [lo, hi),
    averaged over the first ``chips`` devices; None without steps or
    scopes."""
    steps = step_spans(rec, lo, hi)
    devices = rec["devices"][:chips]
    scopes = rec.get("op_scopes", [])[:chips]
    if not steps or len(scopes) < len(devices) or not devices:
        return None
    out: Dict[str, float] = {}
    for ops, paths in zip(devices, scopes):
        labels = [label(p) for p in paths]
        for s, e in steps:
            for k, v in exclusive_ns(ops, labels, s, e).items():
                out[k] = out.get(k, 0.0) + v
    n = len(steps) * len(devices)
    return {k: v * 1e-6 / n for k, v in out.items()}


def scope_ms(rec: Dict, lo: float, hi: float, prefix: str,
             chips: int = 1) -> Optional[float]:
    """Device ms per step of the operations whose label starts with
    ``prefix`` (``fwd_bwd``, ``optimizer``, ...)."""
    by = device_ms_by_label(rec, lo, hi, chips)
    if by is None or not any(k != UNSCOPED for k in by):
        return None
    return sum(v for k, v in by.items() if k.startswith(prefix))


def polar_ms(rec: Dict, lo: float, hi: float, chips: int = 1
             ) -> Optional[float]:
    """Device ms per step under the ``polar`` scope."""
    by = device_ms_by_label(rec, lo, hi, chips)
    if by is None:
        return None
    ms = sum(v for k, v in by.items()
             if k == "optimizer/polar" or k.startswith("optimizer/bucket_"))
    return ms or None


def host_loop_ms(rec: Dict, lo: float, hi: float) -> Optional[float]:
    """Mean over the whole steps in [lo, hi) of the ``trainer.step``
    span's length less its ``trainer.sync`` child's: the loop's serial
    host work, during which the device waits."""
    steps = step_spans(rec, lo, hi)
    if not steps:
        return None
    syncs = [(s, s + d) for n, s, d in rec["program_spans"]
             if n == SYNC_SPAN]
    total = 0.0
    for s, e in steps:
        inner = sum(min(b, e) - max(a, s) for a, b in syncs
                    if a < e and b > s)
        total += (e - s) - inner
    return total * 1e-6 / len(steps)


def idle_ms_by_span(rec: Dict, lo: float, hi: float, device: int = 0
                    ) -> Dict[str, float]:
    """Device idle time in [lo, hi), in ms, by the innermost program span
    it falls in (``step`` when only ``trainer.step`` covers it), the rest
    under "outside"."""
    spans = [[n[len(PROGRAM_PREFIX):], s, d]
             for n, s, d in rec["program_spans"]]
    names = [n for n, _, _ in spans]
    out: Dict[str, float] = {}
    for gs, ge in trace.gaps(rec["devices"][device], lo, hi):
        covered = exclusive_ns(spans, names, gs, ge)
        for k, v in covered.items():
            out[k] = out.get(k, 0.0) + v * 1e-6
        rest = (ge - gs) - sum(covered.values())
        if rest > 0:
            out["outside"] = out.get("outside", 0.0) + rest * 1e-6
    return out


def summary(rec: Dict, lo: float, hi: float, chips: int = 1,
            compiles: int = 0) -> str:
    """One line: over the whole steps in [lo, hi), device ms per step by
    part of the step (the unscoped remainder by op name) and idle ms per
    step by program span; over all of [lo, hi), the share of the idle
    time inside a span below ``trainer.step``; and the compilations in
    the window."""
    steps = step_spans(rec, lo, hi)
    if not steps:
        return "step scopes: no whole trainer.step span in the window"
    n = len(steps)
    by = device_ms_by_label(rec, lo, hi, chips) or {}
    parts = ", ".join(f"{k} {v:.3f}" for k, v in sorted(by.items()))
    rest = ", ".join(f"{k} {v * 1e-6 / n:.3f}"
                     for k, v in unscoped_ops(rec, lo, hi)[:8])
    idle = idle_ms_by_span(rec, steps[0][0], steps[-1][1])
    idle_s = ", ".join(f"{k} {v / n:.3f}" for k, v in
                       sorted(idle.items(), key=lambda kv: -kv[1]))
    window = idle_ms_by_span(rec, lo, hi)
    total = sum(window.values())
    leaf = sum(v for k, v in window.items() if k not in ("step", "outside"))
    share = 100.0 * leaf / total if total else 0.0
    return (f"step scopes: {n} steps; device ms/step: {parts}; unscoped "
            f"by op: {rest or 'none'}; idle ms/step by span: "
            f"{idle_s or 'none'}; idle in window {total:.3f} ms, "
            f"{share:.2f}% inside a leaf span; compiles in window: "
            f"{compiles}")


def unscoped_ops(rec: Dict, lo: float, hi: float, device: int = 0
                 ) -> List[Tuple[str, float]]:
    """Exclusive device ns of each operation outside the step's scopes,
    over the whole steps in [lo, hi), longest first."""
    ops = rec["devices"][device]
    paths = rec["op_scopes"][device]
    out: Dict[str, float] = {}
    names = [op[0] if label(p) == UNSCOPED else "" for op, p in
             zip(ops, paths)]
    for s, e in step_spans(rec, lo, hi):
        for k, v in exclusive_ns(ops, names, s, e).items():
            if k:
                out[k] = out.get(k, 0.0) + v
    return sorted(out.items(), key=lambda kv: -kv[1])
