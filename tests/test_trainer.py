"""Trainer integration: loss goes down, checkpoints resume exactly,
straggler accounting works."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import OptimizerConfig, PrismConfig, TrainConfig
from repro.configs import get_smoke_config
from repro.data import DataConfig
from repro.models import build
from repro.train import Trainer

OCFG = OptimizerConfig(name="muon", learning_rate=0.02,
                       prism=PrismConfig(degree=2, iterations=3,
                                         warm_alpha_iters=3, sketch_dim=8))


def _mk(tmp_path, steps=8, every=4):
    cfg = get_smoke_config("gpt2-paper")
    model = build(cfg)
    tcfg = TrainConfig(steps=steps, checkpoint_dir=str(tmp_path),
                       checkpoint_every=every, log_every=100,
                       async_checkpoint=False)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4,
                      markov_rank=8)
    return Trainer(model, OCFG, tcfg, dcfg)


def test_train_reduces_loss_and_checkpoints(tmp_path):
    tr = _mk(tmp_path, steps=10, every=5)
    _, _, losses = tr.run()
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    assert os.path.exists(tmp_path / "step_00000010")
    assert os.path.exists(tmp_path / "HEARTBEAT")


def test_resume_is_exact(tmp_path):
    # run 1: steps 0..5 with a checkpoint at 4
    tr1 = _mk(tmp_path / "a", steps=6, every=4)
    p1, o1, l1 = tr1.run()
    # run 2: same config, interrupted at step 4, then resumed to 6
    tr2 = _mk(tmp_path / "b", steps=4, every=4)
    tr2.run()
    tr3 = _mk(tmp_path / "b", steps=6, every=4)
    p3, o3, l3 = tr3.run()
    # identical final params: deterministic data + exact state restore
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p3)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_elastic_restore_to_sharded(tmp_path):
    """Checkpoint restores onto explicit shardings (device_put path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import checkpoint as ckpt

    from jax.sharding import AxisType

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    ckpt.save(str(tmp_path), 3, tree)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    step, restored = ckpt.restore(str(tmp_path), tree, shardings=sh)
    assert step == 3
    assert restored["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))


def test_build_trainer_one_device(tmp_path):
    """The launcher's shared construction (launch/train.py, used by
    chip_smoke.py) trains and checkpoints; its ``use_kernels`` keyword
    refuses False, since the chain has one path."""
    from repro.launch.train import build_trainer

    cfg = get_smoke_config("gpt2-paper")
    with pytest.raises(ValueError, match="use_kernels"):
        build_trainer(cfg, ckpt_dir=str(tmp_path / "refused"),
                      use_kernels=False)
    ck = tmp_path / "run"
    tr, ctx = build_trainer(cfg, ckpt_dir=str(ck), steps=3, seq=32,
                            batch=4, ckpt_every=3)
    with ctx:
        _, _, losses = tr.run()
    assert os.path.exists(ck / "step_00000003")
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


@pytest.mark.parametrize("n,shape", [(1, (1, 1)), (2, (2, 1)),
                                     (4, (2, 2)), (8, (4, 2))])
def test_device_mesh_shape(n, shape):
    from repro.launch.mesh import device_mesh_shape

    assert device_mesh_shape(n) == shape
