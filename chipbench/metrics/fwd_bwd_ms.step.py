"""Device time per traced training step of the operations under the
train step's ``fwd_bwd`` scope (``train/state.py``: the casts, the
model's loss and its gradient), in the step the trainer runs: busy time
of the device inside each whole ``trainer.step`` span of the traced
window, each instant given to the innermost operation, over the number
of those spans."""
from chipbench import step_trace


def read(ctx):
    if "op_scopes" not in ctx["trace"]:
        return None
    lo, hi = ctx["window_ns"]
    return step_trace.scope_ms(ctx["trace"], lo, hi, "fwd_bwd",
                               ctx["chips"])
