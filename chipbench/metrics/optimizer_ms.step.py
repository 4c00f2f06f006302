"""Device time per traced training step of the operations under the
train step's ``optimizer`` scope (``train/state.py``: Muon's update with
its polar chains), read as ``fwd_bwd_ms.step`` reads its scope."""
from chipbench import step_trace


def read(ctx):
    if "op_scopes" not in ctx["trace"]:
        return None
    lo, hi = ctx["window_ns"]
    return step_trace.scope_ms(ctx["trace"], lo, hi, "optimizer",
                               ctx["chips"])
