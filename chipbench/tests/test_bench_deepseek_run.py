"""``correct`` for the Moonlight cell at a tiny size on the CPU: a whole
run through ``drivers/train.py`` (the program's trainer on the expert
family, the family's reference, the chain check on expert stacks) passes
the cell's limits; a fault planted in the train step and each precision
control fail them."""
import os
import tempfile
import time

import jax
import pytest

from chipbench import harness, traffic_gen
from chipbench.tests import test_bench_correct as dense
from chipbench.tests import tiny
from chipbench.tests.test_bench_deepseek import tiny_config
from repro.train import trainer as trainer_mod

DRIVER = harness.load_module(harness.HERE + "/drivers/train.py")
SEED = 2 ** 31 + 1602
WORKLOAD = "moonlight-16b-a3b.muon-seq8k"


def tiny_cell():
    c = tiny_config()
    return {"workload": {"name": WORKLOAD, "chips": 1}, "config": c,
            "reference": os.path.join(tiny.BENCH, "reference",
                                      c["reference"] + ".py"),
            "traffic": dict(tiny.read("traffic", "muon-seq8k.json"), seq=32,
                            batch=4),
            "limits": tiny.read("limits", WORKLOAD + ".json"),
            "per_layer": [], "readers": {}}


def _run():
    return DRIVER.run(tiny_cell(), seed=SEED, seconds=0.3, traced=False,
                      t0=time.perf_counter(), devs=jax.devices(), peaks=None)


def test_sound_run_is_correct():
    result, checks = _run()
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0


def test_a_step_on_half_the_batch_is_not_correct(monkeypatch):
    real_make = trainer_mod.make_train_step
    monkeypatch.setattr(trainer_mod, "make_train_step",
                        lambda *a, **k: dense._half_batch(real_make(*a, **k)))
    result, checks = _run()
    assert not result["correct"], checks


@pytest.mark.parametrize("control", sorted(dense.CONTROLS))
def test_the_controls_are_not_correct(control):
    """The reference one precision below the configuration's: the
    model's products in float8 (the router's among them), or the polar
    chain's in bfloat16."""
    cell = tiny_cell()
    with tempfile.TemporaryDirectory() as tmp:
        tc = DRIVER.TrainCell(cell, tmp)
        key = jax.random.PRNGKey(SEED)
        batches = traffic_gen.batches(jax.random.fold_in(key, 1),
                                      tc.traffic, tc.c["vocab_size"])
        ref = tc.reference(key, batches)
        run = tc.reference(key, batches, **dense.CONTROLS[control])
        run["chain"] = tc.chain_gaps(run["samples"])
    numbers = DRIVER.compare(run, ref)
    checks = {k: {"value": v, "limit": cell["limits"][k]}
              for k, v in numbers.items()}
    assert not harness.judge(checks), checks
