"""Device time per traced training step of the expert layer's grouped
products, the operations under ``moe/experts`` (``models/moe.py``),
read as ``moe_ms.step`` reads ``moe``."""
import os

from chipbench import harness

_MOE = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "moe_ms.step.py"))


def read(ctx):
    return _MOE.scope_path_ms(ctx, ("moe", "experts"))
