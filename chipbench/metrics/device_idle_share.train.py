"""Share of the traced training steps in which no operation ran on the
device, averaged over the chips the cell uses (trainer host loop)."""
from chipbench import trace


def read(ctx):
    lo, hi = ctx["window_ns"]
    devices = ctx["trace"]["devices"][:ctx["chips"]]
    if not devices or hi <= lo:
        return None
    busy = sum(trace.busy_ns(ops, lo, hi) for ops in devices) / len(devices)
    return 100.0 * (1.0 - busy / (hi - lo))
