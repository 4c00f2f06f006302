"""The reduction of the program's scopes and spans: device time by part
of the train step, the host loop's serial time, and idle time by span,
on a hand-made record with nested operations and spans, on a trace the
CPU records here, and on a small trace recorded on the chip."""
import gzip
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, harness, step_trace, trace

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "step_scopes_trace.json.gz")
NEW = ["fwd_bwd_ms.step", "optimizer_ms.step", "matfn_roofline.step",
       "host_loop_ms.step", "matfn_launches.step"]
J = "jit(train_step)/"
# one step's device operations, [name, start, duration, op name]: two
# loops that contain their bodies, then CE, clipping, one bucket's chain,
# the rest of the optimizer and a copy outside every scope
STEP_OPS = [
    ["while.1", 100, 200, J + "fwd_bwd/jvp(layers)/while"],
    ["fusion.1", 120, 60, J + "fwd_bwd/jvp(layers)/while/body/dot_general"],
    ["while.2", 300, 300, J + "fwd_bwd/transpose(jvp(layers))/while"],
    ["fusion.2", 350, 200,
     J + "fwd_bwd/transpose(jvp(layers))/while/body/transpose"],
    ["fusion.3", 600, 50, J + "fwd_bwd/jvp(ce)/while/body/dot_general"],
    ["fusion.4", 650, 10, J + "clip/reduce_sum"],
    ["prism_warm_tail", 660, 200,
     J + "optimizer/polar/bucket_10x1024x1024/pallas_call"],
    ["fusion.6", 860, 40, J + "optimizer/add"],
    ["copy.7", 900, 10, ""],
]
# the loop body: [name, start, end] within a step of 1000 ns
STEP_SPANS = [["trainer.step", 0, 1000], ["trainer.batch", 0, 50],
              ["trainer.dispatch", 50, 100], ["trainer.sync", 100, 920],
              ["trainer.heartbeat", 920, 940], ["trainer.stats", 940, 960],
              ["trainer.callback", 960, 990]]


def _record(steps=2):
    ops, scopes, spans = [], [], []
    for k in range(steps):
        o = 1000.0 * k
        ops += [[n, o + s, d] for n, s, d, _ in STEP_OPS]
        scopes += [p for *_, p in STEP_OPS]
        spans += [[n, o + s, e - s] for n, s, e in STEP_SPANS]
    return {"devices": [ops], "spans": [], "op_scopes": [scopes],
            "program_spans": spans}


def _ctx(rec, lo, hi, **kw):
    return dict(trace=rec, window_ns=(lo, hi), chips=1,
                peaks=harness.peaks("TPU v5 lite"),
                polar_buckets=[(10, (1024, 1024))], polar_dtype="float32",
                polar_iters=3, polar_degree=2, **kw)


def _read(name, ctx):
    path = os.path.join(harness.HERE, "metrics", name + ".py")
    return harness.load_module(path).read(ctx)


def test_labels_take_the_transformations_off():
    assert step_trace.scope_names(J + "fwd_bwd/transpose(jvp(layers))/while"
                                  ) == ["train_step", "fwd_bwd", "layers",
                                        "while"]
    assert [step_trace.label(p) for *_, p in STEP_OPS] == [
        "fwd_bwd/forward", "fwd_bwd/forward", "fwd_bwd/backward",
        "fwd_bwd/backward", "fwd_bwd/ce", "clip",
        "optimizer/bucket_10x1024x1024", "optimizer/other", "unscoped"]
    assert step_trace.label(J + "optimizer/polar/concatenate") == \
        "optimizer/polar"


def test_innermost_operation_takes_each_instant():
    rec = _record(1)
    ops, paths = rec["devices"][0], rec["op_scopes"][0]
    labels = [step_trace.label(p) for p in paths]
    got = step_trace.exclusive_ns(ops, labels, 0, 1000)
    assert got == {"fwd_bwd/forward": 200, "fwd_bwd/backward": 300,
                   "fwd_bwd/ce": 50, "clip": 10,
                   "optimizer/bucket_10x1024x1024": 200,
                   "optimizer/other": 40, "unscoped": 10}
    assert sum(got.values()) == trace.busy_ns(ops, 0, 1000)
    # a body's own label wins over its loop's, and the window clips
    names = [n for n, _, _ in ops]
    inner = step_trace.exclusive_ns(ops, names, 110, 400)
    assert inner == {"while.1": 130, "fusion.1": 60, "while.2": 50,
                     "fusion.2": 50}


def test_readers_on_a_hand_made_record():
    rec = _record(2)
    ctx = _ctx(rec, 0, 2000, matfn_launches=7)
    # ms per step: 550 ns of loss and gradient, 240 of optimizer
    assert _read("fwd_bwd_ms.step", ctx) == pytest.approx(550e-6)
    assert _read("optimizer_ms.step", ctx) == pytest.approx(240e-6)
    # the step lasts 1000 ns, 820 of them waiting for its loss
    assert _read("host_loop_ms.step", ctx) == pytest.approx(180e-6)
    least = flops.polar_least_seconds(
        [(10, (1024, 1024))], "float32", iters=3, degree=2,
        peak_flops=197e12, peak_bytes=819e9)[0]
    assert _read("matfn_roofline.step", ctx) == pytest.approx(
        100 * least / 200e-9)
    assert _read("matfn_launches.step", ctx) == 7
    # a step that the window cuts is not counted
    assert _read("fwd_bwd_ms.step", _ctx(rec, 0, 1500)) == pytest.approx(
        550e-6)
    idle = step_trace.idle_ms_by_span(rec, 0, 2000)
    assert idle == pytest.approx({"batch": 1e-4, "dispatch": 1e-4,
                                  "sync": 2e-5, "heartbeat": 4e-5,
                                  "stats": 4e-5, "callback": 6e-5,
                                  "step": 2e-5})
    line = step_trace.summary(rec, 0, 2000, compiles=0)
    assert "2 steps" in line and "copy.7 0.000" in line
    assert "94.74% inside a leaf span" in line  # 180 of 190 ns


def test_readers_fall_silent_on_the_benchmark_record():
    """The record and context the benchmark builds today carry no
    program spans, scopes or launch count: every new reader reads
    nothing and raises nothing."""
    rec = _record(2)
    bare = {"devices": rec["devices"], "spans": []}
    for name in NEW:
        assert _read(name, _ctx(bare, 0, 2000)) is None, name


def test_program_spans_stay_out_of_the_benchmarks_spans(tmp_path):
    @jax.jit
    def f(x):
        with jax.named_scope("fwd_bwd"):
            return jnp.tanh(x @ x)

    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        with jax.profiler.StepTraceAnnotation("trainer.step", step_num=0):
            with jax.profiler.TraceAnnotation("trainer.dispatch"):
                y = f(x)
            with jax.profiler.TraceAnnotation("trainer.sync"):
                y.block_until_ready()
    jax.profiler.stop_trace()
    text = f.lower(x).compile().as_text()
    old = trace.extract(str(tmp_path))
    new = step_trace.extract(str(tmp_path), text)
    assert [s[0] for s in old["spans"]] == ["chipbench.window"]
    assert sorted(s[0] for s in new["program_spans"]) == [
        "trainer.dispatch", "trainer.step", "trainer.sync"]
    # the CPU has no device plane: no operations, no scopes
    assert old["devices"] == [] and new["op_scopes"] == []
    # the compiled program's text names each operation's scope
    assert any(p.endswith("/fwd_bwd/dot_general")
               for p in step_trace.hlo_scopes(text).values())


@pytest.mark.parametrize("line,want", [
    ('  %fusion.577 = f32[8]{0} fusion(%p), kind=kLoop, calls=%c, '
     'metadata={op_name="' + J + 'clip/mul" source_line=3}',
     {"fusion.577": J + "clip/mul"}),
    ('  ROOT %pad.10.clone = f32[9]{0} pad(%a, %b), padding=0_1, '
     'metadata={op_name="' + J + 'fwd_bwd/jvp(embed)/pad"}',
     {"pad.10.clone": J + "fwd_bwd/jvp(embed)/pad"}),
    ('  %copy-done.15 = f32[8]{0} copy-done(%copy-start.15)', {})])
def test_hlo_text_gives_each_instruction_its_scope(line, want):
    """Trace events on a TPU carry an operation's HLO text without its
    metadata; the compiled module's text maps the name to the path."""
    assert step_trace.hlo_scopes(line) == want


def _recorded():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


def _timeline(ops, labels, lo, hi, grid):
    """Label of the innermost operation at the middle of each ``grid`` ns
    of [lo, hi): the brute-force reading."""
    got = np.full(int((hi - lo) // grid), "", dtype=object)
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    for i in order:  # an operation started later is inside the others
        _, s, d = ops[i]
        a = max(int(np.ceil((s - lo) / grid - 0.5)), 0)
        b = max(int(np.ceil((s + d - lo) / grid - 0.5)), 0)
        got[a:b] = labels[i]
    return got


def test_recorded_chip_step_against_a_timeline():
    rec = _recorded()
    lo, hi = rec["window_ns"]
    steps = step_trace.step_spans(rec, lo, hi)
    assert len(steps) == 1
    s, e = steps[0]
    ops, paths = rec["devices"][0], rec["op_scopes"][0]
    labels = [step_trace.label(p) for p in paths]
    by = step_trace.device_ms_by_label(rec, lo, hi)
    grid = 250.0
    line = _timeline(ops, labels, s, e, grid)
    for k, ms in by.items():
        assert ms == pytest.approx((line == k).sum() * grid * 1e-6,
                                   rel=1e-3, abs=0.02), k
    busy = trace.busy_ns(ops, s, e) * 1e-6
    assert sum(by.values()) == pytest.approx(busy)
    # the step's three scopes hold nearly all of its device time
    scoped = sum(v for k, v in by.items()
                 if k.split("/")[0] in step_trace.STEP_SCOPES)
    assert scoped >= 0.97 * busy
    # this step reads as the run's three whole steps did: to 1% on the
    # device's clock, to 10% on the host's
    ctx = dict(_ctx(rec, lo, hi, matfn_launches=rec["matfn_launches"]),
               **rec["polar"])
    for name, want in rec["readings"].items():
        rel = 0.1 if name == "host_loop_ms.step" else 0.01
        assert _read(name, ctx) == pytest.approx(want, rel=rel), name
    # and the device's idle time nearly all inside a leaf span
    idle = step_trace.idle_ms_by_span(rec, lo, hi)
    leaf = sum(v for k, v in idle.items() if k not in ("step", "outside"))
    assert leaf >= 0.9 * sum(idle.values())
