"""Fault-tolerance watchdog utilities + the kill/resume drill.

On a real fleet a per-host supervisor watches the trainer's HEARTBEAT file
(touched every step) and escalates: log -> preempt slow host -> restart
from the newest checkpoint.  ``Watchdog`` implements the detection logic
in a runner-agnostic way so it is unit-testable on CPU; the trainer writes
the heartbeat, this class judges it.

``python -m repro.train.fault`` is the drill half: a self-contained
pipeline training run (1F1B over an 8-device host mesh) that prints each
step's loss as a bit-exact hex float.  tests/test_fault.py launches it as
a subprocess, SIGKILLs it mid-run on a heartbeat trigger, relaunches it,
and asserts the resumed losses continue bitwise from the newest complete
checkpoint (sync preconditioners) or continue training with the async
plane re-bootstrapped by ``discard_inflight`` (the documented staleness
reset, DESIGN.md §12/§13).
"""
from __future__ import annotations

import argparse
import os
import time
from dataclasses import dataclass
from typing import Optional


@dataclass
class WatchdogConfig:
    stale_after_s: float = 300.0     # no heartbeat -> presume hang
    max_step_regression: int = 0     # heartbeat step must not go backwards


class Watchdog:
    def __init__(self, heartbeat_path: str,
                 cfg: Optional[WatchdogConfig] = None):
        self.path = heartbeat_path
        # never a shared default instance: dataclass defaults are mutable,
        # so one watchdog tweaking its thresholds must not leak into the
        # next (regression-tested in tests/test_fault.py)
        self.cfg = WatchdogConfig() if cfg is None else cfg
        self.last_step: Optional[int] = None

    def read(self):
        """(step, wall_time) from the heartbeat file, or None."""
        try:
            with open(self.path) as f:
                step_s, t_s = f.read().split()
            return int(step_s), float(t_s)
        except (FileNotFoundError, ValueError):
            return None

    def check(self, now: Optional[float] = None) -> str:
        """'ok' | 'missing' | 'stale' | 'regressed'."""
        now = time.time() if now is None else now
        hb = self.read()
        if hb is None:
            return "missing"
        step, t = hb
        if now - t > self.cfg.stale_after_s:
            return "stale"
        if self.last_step is not None and \
                step < self.last_step - self.cfg.max_step_regression:
            return "regressed"
        self.last_step = step
        return "ok"

    def should_restart(self, now: Optional[float] = None) -> bool:
        return self.check(now) in ("stale", "regressed")


def discard_inflight(opt_state):
    """Mark any in-flight pending preconditioner stale after a restore
    (DESIGN.md §12).  Checkpoints drop the pending buffers
    (checkpoint.save(drop=optim.base.PENDING_STATE_KEYS)), so a resumed
    run holds zeros there; clearing ``pending_at`` guarantees the swap
    cond never consumes them — the async service re-bootstraps on the
    first post-restore step instead.  No-op for states without a refresh
    plane, so the trainer calls it unconditionally."""
    from repro.optim import base

    return base.discard_pending(opt_state)


def latest_restart_point(ckpt_dir: str) -> Optional[int]:
    """Step to restart from after a fault (newest COMPLETE checkpoint —
    crash-mid-write temp dirs are ignored by construction)."""
    from repro.checkpoint import latest_step

    if not os.path.isdir(ckpt_dir):
        return None
    return latest_step(ckpt_dir)


# --------------------------------------------------------------- drill

def build_pipeline_trainer(*, arch: str = "qwen3-14b", stages: int = 2,
                           n_micro: int = 4, steps: int = 8,
                           checkpoint_every: int = 2, ckpt_dir: str,
                           async_precond: bool = False, seq_len: int = 32,
                           global_batch: int = 8, data: int = 2,
                           model_ax: int = 2,
                           num_layers: Optional[int] = None):
    """Construct (trainer, enter_ctx) for a smoke-scale 1F1B pipeline run
    on the host mesh: pod=stages slices the layer stack, (data, model)
    shard each stage's params/optimizer exactly like the production
    launcher.  Caller is responsible for having pinned JAX_PLATFORMS /
    XLA_FLAGS before jax was imported (device count = stages*data*model).

    Returns the Trainer plus the context manager that must wrap run()
    (mesh + pipeline-adapted activation rules)."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from repro.config import OptimizerConfig, PrismConfig, TrainConfig
    from repro.configs import get_smoke_config
    from repro.data import DataConfig
    from repro.launch import sharding as sh
    from repro.launch.mesh import make_debug_mesh
    from repro.models import build
    from repro.optim import make_optimizer
    from repro.sharding_ctx import activation_sharding
    from repro.train.state import opt_state_shardings
    from repro.train.trainer import Trainer

    cfg = get_smoke_config(arch).replace(
        d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
        dtype="float32")
    if num_layers is not None:
        # deeper pipelines need num_layers % stages == 0 (smoke = 2)
        cfg = cfg.replace(num_layers=num_layers)
    model = build(cfg)
    ocfg = OptimizerConfig(
        name="muon", matfn_method="prism", precond_every=4,
        precond_async=async_precond, matfn_tol=1e-2,
        prism=PrismConfig(degree=2, iterations=3, warm_alpha_iters=3,
                          sketch_dim=8, tol=1e-2))
    tcfg = TrainConfig(steps=steps, checkpoint_dir=ckpt_dir,
                       checkpoint_every=checkpoint_every, log_every=100,
                       async_checkpoint=False, pipeline_stages=stages,
                       n_micro=n_micro)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                      global_batch=global_batch, seed=0, markov_rank=8)

    mesh = make_debug_mesh(data=data, model=model_ax, multi_pod=True,
                           pods=stages)
    prules = sh.pipeline_rules(sh.param_rules(cfg, mesh))
    arules = sh.pipeline_rules(sh.activation_rules(cfg, mesh))
    pshapes = model.param_shapes()
    master = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), pshapes)
    pshard = sh.tree_shardings(mesh, model.logical_axes(), prules, pshapes)
    opt = make_optimizer(ocfg, model.logical_axes())
    sshard = opt_state_shardings(mesh, opt, master, pshard)
    shardings = {"params": pshard, "opt": sshard,
                 "batch": sh.train_batch_shardings(mesh, cfg,
                                                   pipeline=True)}

    @contextlib.contextmanager
    def enter():
        with mesh, activation_sharding(mesh, arules):
            yield

    with enter():
        trainer = Trainer(model, ocfg, tcfg, dcfg, mesh, shardings)
    return trainer, enter


def main(argv=None):
    """Drill child process: pipeline training that narrates bit-exact
    losses; see module docstring.  Parent controls device count via
    XLA_FLAGS before launch."""
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--ckpt_dir", required=True)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--n_micro", type=int, default=4)
    ap.add_argument("--ckpt_every", type=int, default=2)
    ap.add_argument("--async_precond", action="store_true")
    args = ap.parse_args(argv)

    trainer, enter = build_pipeline_trainer(
        stages=args.stages, n_micro=args.n_micro, steps=args.steps,
        checkpoint_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
        async_precond=args.async_precond)

    def narrate(t, metrics):
        # hex round-trips the float64 readback exactly -> the parent can
        # compare resumed losses bitwise against the uninterrupted run
        print(f"DRILL_LOSS {t} {float(metrics['loss']).hex()}",
              flush=True)

    with enter():
        trainer.run(on_metrics=narrate)
    import json

    print("DRILL_DONE " + json.dumps(trainer.matfn_telemetry), flush=True)


if __name__ == "__main__":
    main()
