"""Training launcher CLI.

  python -m repro.launch.train --arch gpt2-paper --steps 100 \
      --optimizer muon --method prism --seq 512 --batch 8

``--mesh devices`` shards params/optimizer/batch over a (data, model)
mesh of the chips that are present (a v5e 2x2 host -> (2, 2)) with the
rules in launch/sharding.py; ``--mesh production [--multi_pod]`` builds
the fixed 16x16 / 2x16x16 fleet meshes; the default ``--mesh none`` runs
on one device.  ``build_trainer`` is the construction every caller
shares (this CLI and ``chip_smoke.py``).
"""
from __future__ import annotations

import argparse
import contextlib
import os
import tempfile

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, OptimizerConfig, PrismConfig, \
    TrainConfig
from repro.configs import get_config, get_smoke_config
from repro.data import DataConfig
from repro.launch import compile_cache
from repro.launch import sharding as sh
from repro.launch.mesh import make_device_mesh, make_production_mesh
from repro.models import build
from repro.optim import make_optimizer
from repro.sharding_ctx import activation_sharding
from repro.train import Trainer
from repro.train.state import opt_state_shardings


def build_trainer(cfg: ModelConfig, *, ckpt_dir: str, steps: int = 100,
                  seq: int = 512, batch: int = 8, optimizer: str = "muon",
                  method: str = "prism", lr: float = 6e-3,
                  compression: str = "none", precond_every: int = 1,
                  precond_async: bool = False, use_kernels: bool = True,
                  mesh=None, pipeline_stages: int = 1, n_micro: int = 4,
                  ckpt_every: int = 50, log_every: int = 10):
    """(trainer, context): the model, Muon/PRISM optimizer, data stream
    and — with a ``mesh`` — the param, optimizer-state and batch
    shardings.  Run the trainer inside ``context`` (the mesh and its
    activation-sharding rules; a null context without a mesh).

    ``use_kernels`` selects nothing: the matrix-function chain always
    runs the kernels (kernels/ops.py picks how).  The keyword stays only
    because the benchmark's ``chipbench/program.py`` passes it, and goes
    when the benchmark stops passing it; False raises."""
    if not use_kernels:
        raise ValueError("build_trainer(use_kernels=False): the matrix-"
                         "function chain has one path, the kernels")
    model = build(cfg)
    ocfg = OptimizerConfig(
        name=optimizer, learning_rate=lr,
        matfn_method=method, gradient_compression=compression,
        precond_every=precond_every, precond_async=precond_async,
        prism=PrismConfig(degree=2, iterations=3, warm_alpha_iters=3,
                          sketch_dim=8))
    tcfg = TrainConfig(steps=steps, checkpoint_dir=ckpt_dir,
                       checkpoint_every=ckpt_every, log_every=log_every,
                       pipeline_stages=pipeline_stages, n_micro=n_micro)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                      global_batch=batch)
    pipelined = pipeline_stages > 1
    if mesh is None:
        if pipelined:
            raise ValueError("pipeline_stages > 1 needs a mesh with a pod "
                             "axis")
        return Trainer(model, ocfg, tcfg, dcfg), contextlib.nullcontext()
    rules = sh.param_rules(cfg, mesh)
    arules = sh.activation_rules(cfg, mesh)
    if pipelined:
        # 1F1B over pod (DESIGN.md §13): layer stack stage-sliced, batch
        # sharded over data only, async refreshes land in the schedule
        # bubbles
        if mesh.shape.get("pod", 1) != pipeline_stages:
            raise ValueError(f"mesh {dict(mesh.shape)} has no pod axis of "
                             f"{pipeline_stages} stages")
        rules = sh.pipeline_rules(rules)
        arules = sh.pipeline_rules(arules)
    pshapes = model.param_shapes()
    master = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), pshapes)
    pshard = sh.tree_shardings(mesh, model.logical_axes(), rules, pshapes)
    opt = make_optimizer(ocfg, model.logical_axes())
    shardings = {"params": pshard,
                 "opt": opt_state_shardings(mesh, opt, master, pshard),
                 "batch": sh.train_batch_shardings(mesh, cfg,
                                                   pipeline=pipelined)}
    return (Trainer(model, ocfg, tcfg, dcfg, mesh, shardings),
            _sharded(mesh, arules))


@contextlib.contextmanager
def _sharded(mesh, arules):
    with mesh, activation_sharding(mesh, arules):
        yield


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-paper")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--optimizer", default="muon",
                    choices=["muon", "shampoo", "adamw"])
    ap.add_argument("--method", default="prism",
                    choices=["prism", "polar_express", "newton_schulz",
                             "eigh"])
    ap.add_argument("--lr", type=float, default=6e-3)
    ap.add_argument("--mesh", default="none",
                    choices=["none", "devices", "production"])
    ap.add_argument("--multi_pod", action="store_true")
    ap.add_argument("--ckpt_dir", default="",
                    help="checkpoint directory (default: a fresh "
                         "temporary directory); a run resumes from the "
                         "newest checkpoint it finds there")
    ap.add_argument("--ckpt_every", type=int, default=50)
    ap.add_argument("--compression", default="none",
                    choices=["none", "int8"])
    ap.add_argument("--precond_every", type=int, default=1,
                    help="staleness period K: refresh matrix "
                         "preconditioners every K steps (DESIGN.md §8)")
    ap.add_argument("--precond_async", action="store_true",
                    help="drive refreshes from the host-side async "
                         "service (§12) — in pipeline runs the chains "
                         "land in the 1F1B bubbles")
    ap.add_argument("--pipeline_stages", type=int, default=1,
                    help="1F1B pipeline depth over the pod mesh axis "
                         "(DESIGN.md §13); >1 requires --mesh "
                         "production --multi_pod")
    ap.add_argument("--n_micro", type=int, default=4,
                    help="microbatches per step for the 1F1B schedule")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    compile_cache.enable()
    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    pipelined = args.pipeline_stages > 1
    mesh = {"none": lambda: None,
            "devices": make_device_mesh,
            "production": lambda: make_production_mesh(
                multi_pod=args.multi_pod or pipelined)}[args.mesh]()
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_ckpt_")
    print(f"[train] checkpoints in {os.path.abspath(ckpt_dir)}", flush=True)
    trainer, ctx = build_trainer(
        cfg, ckpt_dir=ckpt_dir, steps=args.steps, seq=args.seq,
        batch=args.batch, optimizer=args.optimizer, method=args.method,
        lr=args.lr, compression=args.compression,
        precond_every=args.precond_every, precond_async=args.precond_async,
        mesh=mesh, pipeline_stages=args.pipeline_stages,
        n_micro=args.n_micro, ckpt_every=args.ckpt_every)
    with ctx:
        trainer.run(seed=args.seed)


if __name__ == "__main__":
    main()
