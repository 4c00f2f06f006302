"""Device time per traced training step of the operations whose scope
path holds the expert layer's ``moe`` scope (``models/moe.py``: routing,
dispatch, the grouped products, combine and the shared experts, in the
forward pass, its recomputation and the backward pass), in the step the
trainer runs: busy time of the device inside each whole ``trainer.step``
span of the traced window, each instant given to the innermost
operation, over the number of those spans.  None where no operation
carries the scope (a program without the expert layer)."""
from chipbench import step_trace


def holds(path: str, scopes) -> bool:
    """Whether the op name ``path`` holds the consecutive scopes
    ``scopes``, transformations taken off."""
    names, n = step_trace.scope_names(path), len(scopes)
    return any(tuple(names[i:i + n]) == tuple(scopes)
               for i in range(len(names) - n + 1))


def scope_path_ms(ctx, scopes):
    rec = ctx["trace"]
    if "op_scopes" not in rec:
        return None
    lo, hi = ctx["window_ns"]
    steps = step_trace.step_spans(rec, lo, hi)
    devices = rec["devices"][:ctx["chips"]]
    paths = rec["op_scopes"][:ctx["chips"]]
    if not steps or not devices or len(paths) < len(devices):
        return None
    ns, found = 0.0, False
    for ops, names in zip(devices, paths):
        labels = ["in" if holds(p, scopes) else "out" for p in names]
        found = found or "in" in labels
        for s, e in steps:
            ns += step_trace.exclusive_ns(ops, labels, s, e).get("in", 0.0)
    if not found:
        return None
    return ns * 1e-6 / (len(steps) * len(devices))


def read(ctx):
    return scope_path_ms(ctx, ("moe",))
