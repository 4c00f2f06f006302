"""The benchmark's one contact with the system under test.

Everything the harness takes from the program goes through here: the
model configuration built from a configuration file, the trainer that
``launch/train.py::build_trainer`` returns, the layout of its parameter
tree, and what its optimizer state holds after a step.  The reference
(``reference/``) imports none of it.
"""
from __future__ import annotations

import os
import sys
from typing import Dict

from chipbench.harness import ROOT

sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.train import build_trainer  # noqa: E402

# configuration-file key -> ModelConfig field
_FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
           "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
           "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
           "tie_word_embeddings": "tie_embeddings", "qk_norm": "qk_norm",
           "attention_bias": "qkv_bias"}
_ACT = {"silu": "silu", "gelu_new": "gelu"}
_DTYPE = {"bfloat16": "bfloat16", "float32": "float32"}


def model_config(c: Dict):
    """The program's ModelConfig for configuration file ``c``: the
    registry entry ``c["arch"]`` with every size the file states."""
    kw = {field: c[key] for key, field in _FIELDS.items()}
    kw.update(name=c["name"], mlp_act=_ACT[c["hidden_act"]],
              dtype=_DTYPE[c["torch_dtype"]],
              emb_dtype=_DTYPE[c["torch_dtype"]])
    return get_config(c["arch"]).replace(**kw)


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
