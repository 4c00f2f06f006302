"""The trainer's observability: host spans that tile each step of
``Trainer.run``, named scopes in the compiled step that change no
operation, the compile counter, and the bounded straggler window."""
import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.config import OptimizerConfig, PrismConfig, TrainConfig
from repro.configs import get_smoke_config
from repro.data import DataConfig
from repro.kernels import ops
from repro.models import build
from repro.optim import bucketing
from repro.optim.muon import matrix_view_shapes
from repro.train import Trainer
from repro.train import trainer as trainer_mod

OCFG = OptimizerConfig(name="muon", learning_rate=0.02,
                       prism=PrismConfig(degree=2, iterations=3,
                                         warm_alpha_iters=3, sketch_dim=8))
LEAVES = ["trainer.batch", "trainer.dispatch", "trainer.sync",
          "trainer.heartbeat", "trainer.stats", "trainer.callback"]


def _trainer(tmp_path, steps=3, every=0):
    cfg = get_smoke_config("gpt2-paper")
    tcfg = TrainConfig(steps=steps, checkpoint_dir=str(tmp_path),
                       checkpoint_every=every, log_every=100,
                       async_checkpoint=False)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4,
                      markov_rank=8)
    return Trainer(build(cfg), OCFG, tcfg, dcfg)


def _host_spans(logdir):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(glob.glob(f"{logdir}/**/*.xplane.pb",
                                         recursive=True)[-1])
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats) if e.name == "trainer.step" else {})
                           for e in line.events
                           if e.name.startswith(("trainer.", "chipbench.")))
    return sorted(out, key=lambda s: s[1])


def test_each_step_is_one_span_tiled_by_its_children(tmp_path):
    tr = _trainer(tmp_path / "ck", steps=4, every=2)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    tr.run(on_metrics=lambda t, m: None)
    jax.profiler.stop_trace()
    spans = _host_spans(tmp_path / "trace")
    assert not [s for s in spans if s[0].startswith("chipbench.")]
    steps = [s for s in spans if s[0] == "trainer.step"]
    assert [s[3]["step_num"] for s in steps] == [0, 1, 2, 3]
    init = [s for s in spans if s[0] == "trainer.restore_or_init"]
    assert len(init) == 1 and init[0][2] <= steps[0][1]
    for t, (_, lo, hi, _) in enumerate(steps):
        inside = [s for s in spans if s[0].startswith("trainer.")
                  and s[0] != "trainer.step" and lo <= s[1] and s[2] <= hi]
        due = ["trainer.checkpoint"] if (t + 1) % 2 == 0 else []
        assert [s[0] for s in inside] == LEAVES + due
        # the children follow one another, in loop order
        assert all(a[2] <= b[1] for a, b in zip(inside, inside[1:]))


def _compiled_text(tr, args):
    return jax.jit(tr.raw_step_fn, static_argnums=(4,)).lower(
        *args).compile().as_text()


def _strip(text):
    """The module's operations: metadata and the table of source
    locations it points into taken out."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return "\n".join(line for line in text.splitlines()
                     if not re.match(r"^(\d+ |FileNames|FunctionNames|"
                                     r"FileLocations|StackFrames)", line))


def test_scopes_name_the_step_and_change_no_operation(tmp_path,
                                                      monkeypatch):
    tr = _trainer(tmp_path)
    params, opt_state, _ = tr.init_state()
    args = (params, opt_state, tr.batch_fn(jnp.asarray(0)), jnp.int32(0),
            None)
    text = _compiled_text(tr, args)
    names = re.findall(r'op_name="([^"]*)"', text)
    dots = re.findall(r' dot\(.*op_name="([^"]*)"', text)
    assert any("/fwd_bwd/jvp(layers)/" in n for n in dots)
    assert any("/fwd_bwd/transpose(jvp(layers))/" in n for n in dots)
    assert any("/fwd_bwd/jvp(ce)/" in n for n in dots)
    assert any(re.search(r"/optimizer/polar/bucket_\d+x\d+x\d+/", n)
               for n in dots)
    assert any("/fwd_bwd/jvp(embed)/" in n for n in names)
    assert any("/clip/" in n for n in names)
    # the same step built with every scope a no-op compiles to the same
    # operations: only the op names differ
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _compiled_text(_trainer(tmp_path), args)
    assert not re.search(r'op_name="[^"]*/fwd_bwd/', bare)
    assert _strip(bare) == _strip(text)


def test_compiles_are_counted_by_step(tmp_path):
    tr = _trainer(tmp_path, steps=6)
    wide = jnp.zeros((4, 32), jnp.int32)
    narrow = jnp.zeros((2, 32), jnp.int32)
    tr.batch_fn = lambda t: {"tokens": wide if int(t) < 3 else narrow}
    tr.run()
    counts = tr.counters["compiles_by_step"]
    # step 0 compiles the step; steps 1-2 and 4-5 run it as compiled; the
    # new batch shape at step 3 compiles it once more
    assert set(counts) == {0, 3}
    assert counts[0] >= 1 and counts[3] == 1


def test_attention_path_is_counted_when_the_step_traces(tmp_path):
    tr = _trainer(tmp_path, steps=3)
    assert "attention_path" not in tr.counters
    tr.run()
    # one trace of the step: the CPU takes the jnp chunk loop in every
    # layer scan it stages (forward, remat and backward share one trace)
    paths = tr.counters["attention_path"]
    assert set(paths) == {"chunked"} and paths["chunked"] >= 1
    params, opt_state, _ = tr.init_state()
    text = _compiled_text(tr, (params, opt_state,
                               tr.batch_fn(jnp.asarray(0)), jnp.int32(0),
                               None))
    dots = re.findall(r' dot\(.*op_name="([^"]*)"', text)
    assert any("/jvp(layers)/" in n and "/attn_chunked/" in n for n in dots)
    assert not any("/attn_kernel/" in n for n in dots)


def test_polar_apply_is_counted_when_the_step_traces(tmp_path,
                                                    monkeypatch):
    # a zero VMEM budget runs every Muon bucket unfused: three warm
    # updates a bucket, each on the Gram side (degree 2)
    monkeypatch.setattr(ops, "VMEM_BUDGET", 0)
    tr = _trainer(tmp_path, steps=2)
    assert "polar_apply" not in tr.counters
    tr.run()
    shapes = matrix_view_shapes(tr.model.param_shapes(),
                                tr.model.logical_axes())
    buckets = bucketing.plan_buckets(shapes, pad=OCFG.bucket_pad,
                                     pad_slack=OCFG.bucket_pad_slack)
    assert tr.counters["polar_apply"] == {"gram_side": 3 * len(buckets)}


def _sleeping_step(slow_at, base=0.02, slow=0.4):
    import time

    def step(params, opt_state, batch, t, refresh):
        time.sleep(slow if int(t) == slow_at else base)
        return params, opt_state, {"loss": jnp.float32(1.0),
                                   "grad_norm": jnp.float32(0.0)}
    return step


def test_straggler_median_reads_a_bounded_window(tmp_path, monkeypatch,
                                                 capsys):
    monkeypatch.setattr(trainer_mod, "STRAGGLER_WINDOW", 8)
    tr = _trainer(tmp_path, steps=24)
    tr.init_state = lambda seed=0: ({}, {}, 0)
    tr.batch_fn = lambda t: {}
    tr.step_fn = _sleeping_step(slow_at=16)
    tr.run()
    # only the last 8 steps' times are kept, whatever the run's length
    assert len(tr.step_times) == 8
    assert tr.straggler_events >= 1
    assert "straggler: step 16 " in capsys.readouterr().out


@pytest.mark.parametrize("n", [3, 300])
def test_step_times_never_outgrow_the_window(tmp_path, n):
    tr = _trainer(tmp_path, steps=n)
    tr.init_state = lambda seed=0: ({}, {}, 0)
    tr.batch_fn = lambda t: {}
    tr.step_fn = _sleeping_step(slow_at=-1, base=0.0)
    tr.run()
    assert len(tr.step_times) == min(n - 1, trainer_mod.STRAGGLER_WINDOW)
