"""Least time the chip could take for one step's bucketed polar chains
(per bucket, the larger of its FLOPs over the bf16 peak and its bytes
over the HBM bandwidth, counted by ``chipbench/flops.py`` for the
optimizer's matrix views of the trainer's parameter shapes, with its own
iteration count and dtype), over the device time per traced training
step under the ``polar`` scope (``optim/bucketing.py::polar_bucketed``)
of the step the trainer runs."""
import sys

from chipbench import flops, step_trace


def read(ctx):
    if "op_scopes" not in ctx["trace"] or not ctx.get("polar_buckets"):
        return None
    lo, hi = ctx["window_ns"]
    ms = step_trace.polar_ms(ctx["trace"], lo, hi, ctx["chips"])
    if not ms:
        return None
    least, f, b, bound = flops.polar_least_seconds(
        ctx["polar_buckets"], ctx["polar_dtype"], iters=ctx["polar_iters"],
        degree=ctx["polar_degree"],
        peak_flops=ctx["peaks"]["bf16_flops_per_s"],
        peak_bytes=ctx["peaks"]["hbm_bytes_per_s"])
    print(f"matfn_roofline.step: least {least * 1e3:.6f} ms ({bound}-bound)"
          f" over {ms:.6f} ms per step under polar", file=sys.stderr)
    return 100.0 * least / (ms * 1e-3)
