"""The benchmark's one contact with the system under test.

Everything the harness takes from the program goes through here: the
model configuration built from a configuration file (through its
family's translation, ``translations/``), the trainer that
``launch/train.py::build_trainer`` returns, the layout of its parameter
tree, what its optimizer state holds after a step, and, on the step's
shapes, its compiled text, its Pallas launches and its polar chains.
The reference (``reference/``) imports none of it.
"""
from __future__ import annotations

import math
import os
import sys
from typing import Dict

from chipbench.harness import HERE, ROOT, load_module

sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.train import build_trainer  # noqa: E402

# keys of a configuration file that describe the file, not the model
DESCRIPTIVE = ("name", "source", "arch", "reduced", "assumed", "deployment",
               "reference")


def model_config(c: Dict):
    """The program's ModelConfig for configuration file ``c``: the
    registry entry ``c["arch"]`` with every size the file states, as its
    family's translation (``translations/<family>.py``, the family being
    ``c["reference"]``) sets them.  A key that the translation does not
    take, and that does not describe the file, raises: the program would
    build another model than the reference computes."""
    t = load_module(os.path.join(HERE, "translations",
                                 c["reference"] + ".py"))
    unknown = sorted(set(c) - t.KEYS - set(DESCRIPTIVE))
    if unknown:
        raise ValueError(f"configuration {c['name']!r}: the program takes "
                         f"no key {unknown} of family {c['reference']!r}")
    return get_config(c["arch"]).replace(name=c["name"], **t.fields(c))


def trainer_for(c: Dict, traffic: Dict, ckpt_dir: str):
    """The trainer of the normal training path, one device, no
    checkpoints, no periodic logging."""
    o = traffic["optimizer"]
    trainer, _ = build_trainer(
        model_config(c), ckpt_dir=ckpt_dir, steps=1, seq=traffic["seq"],
        batch=traffic["batch"], optimizer=o["name"], method=o["method"],
        lr=o["learning_rate"], precond_every=o["precond_every"],
        use_kernels=o["use_kernels"], ckpt_every=0, log_every=1 << 30)
    _check_optimizer(trainer.ocfg, o)
    return trainer


def _check_optimizer(ocfg, o: Dict) -> None:
    """The reference is told the traffic file's hyperparameters; refuse
    to measure a trainer that was built with others."""
    want = {k: v for k, v in o.items()
            if k not in ("name", "method", "prism", "use_kernels")}
    got = {"learning_rate": ocfg.learning_rate,
           "momentum": ocfg.momentum, "weight_decay": ocfg.weight_decay,
           "beta1": ocfg.beta1, "beta2": ocfg.beta2, "eps": ocfg.eps,
           "adamw_lr_scale": ocfg.adamw_lr_scale,
           "grad_clip_norm": ocfg.grad_clip_norm,
           "precond_every": ocfg.precond_every,
           "matfn_dtype": ocfg.matfn_dtype,
           "grads_dtype": ocfg.grads_dtype,
           "gradient_compression": ocfg.gradient_compression}
    p = ocfg.resolved_prism
    want_p = o["prism"]
    got_p = {"degree": p.degree, "iterations": p.iterations,
             "warm_alpha_iters": p.warm_alpha_iters,
             "sketch_dim": p.sketch_dim}
    bad = {k: (want[k], got.get(k)) for k in want if got.get(k) != want[k]}
    bad.update({f"prism.{k}": (want_p[k], got_p.get(k)) for k in want_p
                if got_p.get(k) != want_p[k]})
    if ocfg.name != o["name"] or ocfg.matfn_method != o["method"]:
        bad["name/method"] = ((o["name"], o["method"]),
                              (ocfg.name, ocfg.matfn_method))
    if bad:
        raise ValueError(f"trainer optimizer differs from the traffic "
                         f"file (want, got): {bad}")


def to_program(ref_params: Dict, prog_shapes: Dict) -> Dict:
    """Reference-layout weights as the program's float32 master tree:
    each leaf zero-padded at the end of every dimension to the program's
    shape (padded heads and vocabulary rows stay exactly zero)."""
    def pad(x, s):
        return jnp.pad(x, [(0, t - n) for n, t in zip(x.shape, s.shape)]
                       ).astype(jnp.float32)
    return jax.tree.map(pad, ref_params, prog_shapes)


def from_program(prog: Dict, ref_shapes: Dict) -> Dict:
    """The program's tree cut back to the reference's leaf shapes."""
    return jax.tree.map(
        lambda x, s: x[tuple(slice(0, n) for n in s)], prog, ref_shapes,
        is_leaf=lambda s: isinstance(s, tuple))


def gradients_from_state(opt_state: Dict, beta1: float) -> Dict:
    """The clipped gradient the optimizer received at its first step,
    from the state it left: a Muon leaf's momentum starts at zero and
    becomes ``g``; an AdamW leaf's first moment becomes ``(1 - b1) g``."""
    def grad(slot):
        if "nu" in slot:
            return slot["mom"] / (1.0 - beta1)
        return slot["mom"]
    return jax.tree.map(grad, opt_state["leaves"],
                        is_leaf=lambda s: isinstance(s, dict) and "mom" in s)


def step_text(trainer, params, opt_state, batch) -> str:
    """The text of the compiled step that ``trainer`` runs, compiled on
    the shapes of its arguments (``jax.eval_shape``'s): the program the
    window ran, found in the persistent compile cache.  It holds every
    operation's scope, which a TPU's trace events do not."""
    return trainer.step_fn.lower(
        params, opt_state, batch, jax.ShapeDtypeStruct((), jnp.int32),
        None).compile().as_text()


def step_launches(trainer, params, opt_state, batch) -> int:
    """Pallas launches of one step of ``trainer``, counted by tracing its
    unjitted step abstractly on the shapes of its arguments."""
    from repro.kernels import ops

    return ops.count_launches(trainer.raw_step_fn, params, opt_state, batch,
                              jnp.int32(0))


def polar_plan(trainer, params) -> Dict:
    """The polar chains of one step of ``trainer``, from the shapes of
    its parameters: the optimizer's matrix views grouped by shape,
    ``[(count, (m, n)), ...]``, and the chain's iteration count, degree
    and dtype."""
    from repro.optim import base

    axes = jax.tree.leaves(trainer.model.logical_axes(),
                           is_leaf=lambda t: isinstance(t, tuple))
    counts: Dict = {}
    for p, a in zip(jax.tree.leaves(params), axes):
        if base.is_matrix_param(a, p.shape):
            view = jax.eval_shape(lambda x, a=a: base.to_matrix_view(x, a)[0],
                                  p)
            mn = tuple(view.shape[-2:])
            counts[mn] = counts.get(mn, 0) + math.prod(view.shape[:-2])
    pc = trainer.ocfg.resolved_prism
    return {"polar_buckets": [(n, mn) for mn, n in sorted(counts.items())],
            "polar_iters": pc.iterations, "polar_degree": pc.degree,
            "polar_dtype": pc.dtype}
