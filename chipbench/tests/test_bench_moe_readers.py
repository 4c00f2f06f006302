"""The expert layer's readers (``moe_ms.step``, ``moe_experts_ms.step``)
on a hand-made record, and silent on a recorded step of a model that has
no expert layer."""
import gzip
import json
import os

import pytest

from chipbench import harness, step_trace, trace

HERE = os.path.dirname(os.path.abspath(__file__))
KERNELS = os.path.join(HERE, "data", "step_kernels_trace.json.gz")


def _read(name, ctx):
    return harness.load_module(os.path.join(
        harness.HERE, "metrics", name + ".py")).read(ctx)


def _ctx(rec, lo, hi):
    return {"trace": rec, "window_ns": (lo, hi), "chips": 1}


LAYER = "jit(train_step)/fwd_bwd/jvp(layers)/while/body/closed_call/"
BACK = ("jit(train_step)/fwd_bwd/transpose(jvp(layers))/while/body/"
        "closed_call/checkpoint/rematted_computation/")


def _record(steps):
    """Each 1000 ns step: a routing op, a grouped product with a fusion
    inside it, the backward's grouped product, an attention kernel and
    an optimizer op."""
    ops, paths, spans = [], [], []
    for i in range(steps):
        t = 1000 * i
        spans.append(["trainer.step", t, 1000])
        for name, s, d, p in [
                ("%fusion.1", 10, 50, LAYER + "moe/route/sigmoid"),
                ("%gmm.3", 60, 100, LAYER + "moe/experts/jit(gmm)/pallas_call"),
                ("%fusion.2", 80, 20, LAYER + "moe/experts/convert"),
                ("%tgmm.1", 200, 40, BACK + "moe/experts/jit(tgmm)/pallas"),
                ("%splash.1", 300, 70, LAYER + "attn_kernel/splash"),
                ("%fusion.9", 400, 30,
                 "jit(train_step)/optimizer/polar/bucket_8x64x64/mul")]:
            ops.append([name, t + s, d])
            paths.append(p)
    return {"devices": [ops], "op_scopes": [paths], "program_spans": spans}


def test_moe_readers_on_a_hand_made_record():
    rec = _record(3)
    ctx = _ctx(rec, 0, 3000)
    assert _read("moe_ms.step", ctx) == pytest.approx(190e-6)
    assert _read("moe_experts_ms.step", ctx) == pytest.approx(140e-6)
    # a step the window cuts is left out
    assert _read("moe_ms.step", _ctx(rec, 0, 2500)) == pytest.approx(190e-6)


def test_a_scope_is_matched_whole():
    mod = harness.load_module(os.path.join(harness.HERE, "metrics",
                                           "moe_ms.step.py"))
    assert mod.holds(BACK + "moe/experts/x", ("moe", "experts"))
    assert not mod.holds(LAYER + "moe/route/experts", ("moe", "experts"))
    assert not mod.holds(LAYER + "moex/experts", ("moe",))


@pytest.mark.parametrize("name", ["moe_ms.step", "moe_experts_ms.step"])
def test_silent_without_the_expert_layer(name):
    """gpt2-paper's recorded step holds no ``moe`` scope: no number."""
    with gzip.open(KERNELS, "rt") as f:
        rec = json.load(f)
    scopes = step_trace.hlo_scopes(rec.pop("hlo"))
    rec["op_scopes"] = [[scopes.get(trace.op_name(n).lstrip("%"), "")
                         for n, _, _ in rec["devices"][0]]]
    lo, hi = rec["window_ns"]
    assert _read(name, _ctx(rec, lo, hi)) is None
    assert _read(name, _ctx({"devices": [[]]}, lo, hi)) is None
