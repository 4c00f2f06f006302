"""Least time the chip could take for the cell's bucketed polar chains
(per bucket, the larger of its FLOPs over the bf16 peak and its bytes
over the HBM bandwidth, counted by ``chipbench/flops.py`` with the
optimizer's own iteration count and dtype), over the device time of one
standalone call of the bucketed polar entry that Muon's update calls."""
import sys

from chipbench import flops


def read(ctx):
    ms = ctx.get("polar_ms")
    if not ms or not ctx.get("polar_buckets"):
        return None
    least, f, b, bound = flops.polar_least_seconds(
        ctx["polar_buckets"], ctx["polar_dtype"], iters=ctx["polar_iters"],
        degree=ctx["polar_degree"],
        peak_flops=ctx["peaks"]["bf16_flops_per_s"],
        peak_bytes=ctx["peaks"]["hbm_bytes_per_s"])
    print(f"matfn_roofline: {f:.6e} FLOP, {b:.6e} bytes, least "
          f"{least * 1e3:.6f} ms ({bound}-bound) over {ms:.6f} ms",
          file=sys.stderr)
    return 100.0 * least / (ms * 1e-3)
