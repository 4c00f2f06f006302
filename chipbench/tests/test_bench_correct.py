"""``correct`` at a tiny size on the CPU: a sound run passes the cell's
limits; the control and each fault a training cell can have fail them.

The faults are planted under the harness, in the program's train step,
and the rest of a run is driven as on the chip (the look for a chip is
skipped).  Training cells on one chip exchange nothing between chips and
produce no tokens, so a state left unchanged and half of the batch left
out are the faults they can have."""
import tempfile
import time

import jax
import pytest

from chipbench import harness, traffic_gen
from chipbench.reference import controls
from chipbench.tests import tiny
from repro.train import trainer as trainer_mod

DRIVER = harness.load_module(harness.HERE + "/drivers/train.py")
SEED = 2 ** 31 + 12345
CONFIGS = ["gpt2-paper", "qwen3-8b"]


def _unchanged(real):
    def step(params, opt_state, batch, step, refresh=None):
        _, _, metrics = real(params, opt_state, batch, step, refresh)
        return params, opt_state, metrics
    return step


def _half_batch(real):
    def step(params, opt_state, batch, step, refresh=None):
        tokens = batch["tokens"]
        half = {"tokens": tokens[:tokens.shape[0] // 2]}
        return real(params, opt_state, half, step, refresh)
    return step


def _run(name):
    return DRIVER.run(tiny.cell(name), seed=SEED, seconds=0.3, traced=False,
                      t0=time.perf_counter(), devs=jax.devices(), peaks=None)


def test_sound_run_drives_the_whole_loop():
    """The limits were set at full size on the chip; at this size a sound
    run reads about a tenth of the faults' gaps."""
    result, checks = _run("gpt2-paper")
    assert checks["loss_gap"]["value"] < 1e-3
    assert checks["grad_gap"]["value"] < 1e-2
    assert checks["update_gap"]["value"] < 1e-2
    assert checks["chain_gap"]["value"] < 1e-4
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "peak_hbm_gib",
                                      "setup_s"}


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
def test_a_broken_step_is_not_correct(monkeypatch, fault, name):
    real_make = trainer_mod.make_train_step
    monkeypatch.setattr(trainer_mod, "make_train_step",
                        lambda *a, **k: fault(real_make(*a, **k)))
    result, checks = _run(name)
    assert not result["correct"], checks


CONTROLS = {"model": {"q8": controls.fp8_round},
            "chain": {"chain_q": controls.bf16_round}}


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_the_fp8_control_is_not_correct(name, control):
    """The reference put in the program's place one precision below the
    configuration's: the model's products in float8, or the polar
    chain's in bfloat16."""
    cell = tiny.cell(name)
    with tempfile.TemporaryDirectory() as tmp:
        tc = DRIVER.TrainCell(cell, tmp)
        key = jax.random.PRNGKey(SEED)
        batches = traffic_gen.batches(jax.random.fold_in(key, 1),
                                      tc.traffic, tc.c["vocab_size"])
        ref = tc.reference(key, batches)
        run = tc.reference(key, batches, **CONTROLS[control])
        run["chain"] = tc.chain_gaps(run["samples"])
    numbers = DRIVER.compare(run, ref)
    checks = {k: {"value": v, "limit": cell["limits"][k]}
              for k, v in numbers.items()}
    assert not harness.judge(checks), checks
