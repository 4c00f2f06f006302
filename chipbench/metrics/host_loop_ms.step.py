"""The training loop's serial host work per traced step, during which
the device waits: the mean over the whole ``trainer.step`` spans of the
traced window of each span's length less its ``trainer.sync`` child's
(``train/trainer.py::Trainer.run``)."""
from chipbench import step_trace


def read(ctx):
    if "program_spans" not in ctx["trace"]:
        return None
    lo, hi = ctx["window_ns"]
    return step_trace.host_loop_ms(ctx["trace"], lo, hi)
