"""Train-step construction: mixed precision, clipping, compression, Muon.

Master parameters live in fp32; the forward/backward runs in each param's
model dtype (bf16 matrices, fp32 norms/ssm constants).  The PRISM sketch
key is derived from the step counter inside the jitted step, so the step
signature stays (params, opt_state, batch, step) — clean to lower and to
checkpoint.

The step's device work sits under three named scopes, in the op names
the profiler shows: ``fwd_bwd`` (the casts, loss and gradient),
``clip`` (clipping and compression) and ``optimizer`` (the update).
Scopes are metadata only: they change no compiled operation.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import OptimizerConfig
from repro.models.transformer import Model
from repro.optim import base, compression


def master_params(params):
    return jax.tree.map(lambda p: p.astype(jnp.float32), params)


def make_train_step(model: Model, opt: base.Optimizer,
                    ocfg: OptimizerConfig, inject=None) -> Callable:
    """Build train_step(params, opt_state, batch, step, refresh=None).

    ``refresh`` is the preconditioner staleness override (base.Optimizer):
    jit it as a STATIC argument (static_argnums=(4,)) so a Python bool
    compiles separate refresh/skip variants — the skip variant contains
    zero matrix-function work.  None keeps the dynamic in-state schedule.

    ``inject``: optional traced gradient hook ``f(grads, step) -> grads``
    applied BEFORE clipping — the §15 chaos drill's deterministic fault
    injector (train/chaos.py).  Must be pure jax (e.g. a ``jnp.where``
    on the step counter) so the step compiles once and the injection
    fires data-dependently at the target step; None is a no-op.
    """
    cast_tree = model.param_dtypes()

    def train_step(params, opt_state, batch, step, refresh=None):
        with jax.named_scope("fwd_bwd"):
            if ocfg.grads_dtype == "bfloat16":
                # differentiate wrt the bf16 compute params: the DP
                # gradient reduce-scatter then moves bf16 (half the wire
                # bytes); the fp32 master update converts afterwards.
                pc = jax.tree.map(lambda x, dt: x.astype(dt), params,
                                  cast_tree)
                (loss, metrics), grads = jax.value_and_grad(
                    lambda q: model.loss(q, batch), has_aux=True)(pc)
                grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            else:
                def loss_fn(p):
                    pc = jax.tree.map(lambda x, dt: x.astype(dt), p,
                                      cast_tree)
                    return model.loss(pc, batch)

                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
        return _clip_and_update(opt, ocfg, inject, params, opt_state, loss,
                                grads, metrics, step, refresh)

    return train_step


def _clip_and_update(opt: base.Optimizer, ocfg: OptimizerConfig, inject,
                     params, opt_state, loss, grads, metrics, step, refresh):
    """The part of a train step after the gradients, shared by the plain
    and the pipelined step: the chaos hook, clipping and compression
    (scope ``clip``), and the optimizer update (scope ``optimizer``)."""
    with jax.named_scope("clip"):
        if inject is not None:
            grads = inject(grads, step)
        grads, gnorm = base.clip_by_global_norm(grads, ocfg.grad_clip_norm)
        if ocfg.gradient_compression == "int8":
            grads = compression.int8_roundtrip(grads)
    with jax.named_scope("optimizer"):
        key = jax.random.fold_in(jax.random.PRNGKey(0), step)
        params, opt_state = opt.update(grads, opt_state, params, step, key,
                                       refresh=refresh)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm)
        if ocfg.precond_async:
            # §12: surface the cached-preconditioner drift proxy so the
            # host-side AsyncPrecondService can trigger refreshes (a few
            # scalars — free next to the loss readback)
            metrics["precond_drift"] = base.precond_drift(opt_state)
    return params, opt_state, metrics


PIPELINE_FAMILIES = ("dense", "moe", "ssm")


def pipeline_split_params(params, n_stages: int):
    """Split params into (shared, stage-stacked layers).

    The master tree keeps the standard [L, ...] layer stacking — the
    stage view [S, L/S, ...] is a pure reshape, so checkpoints are
    stage-count independent (elastic across pipeline_stages).  When the
    layer stack is sharded over "pod" on dim 0 (launch/sharding.py
    pipeline rules), the reshape is layout-preserving: each pod already
    holds exactly its stage slice."""
    lay = params["layers"]
    shared = {k: v for k, v in params.items() if k != "layers"}

    def split(p):
        L = p.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return p.reshape((n_stages, L // n_stages) + p.shape[1:])

    return shared, jax.tree.map(split, lay)


def pipeline_merge_layer_grads(g_lay_stacked):
    """Inverse of pipeline_split_params on the layers subtree."""
    return jax.tree.map(
        lambda p: p.reshape((p.shape[0] * p.shape[1],) + p.shape[2:]),
        g_lay_stacked)


def make_pipeline_stage_fn(model: Model):
    """Adapt Model to the one_f_one_b stage contract.

    Every stage runs the same SPMD program: cast its fp32 master slices
    to model dtypes, embed (first stage only, via lax.cond), run its
    layer slice through the backbone, and seed its loss terms —
    chunked CE on the last stage, per-stage MoE aux everywhere.  Shared
    params travel replicated, so tied embeddings fall out of the psum
    over stage gradients."""
    cfg = model.cfg
    assert cfg.family in PIPELINE_FAMILIES, cfg.family
    if cfg.first_k_dense:
        raise NotImplementedError(
            "the 1F1B stages split only the scanned ``layers``; leading "
            "dense layers have no stage")
    dtypes = model.param_dtypes()
    sh_dtypes = {k: v for k, v in dtypes.items() if k != "layers"}
    lay_dtypes = dtypes["layers"]

    def stage_fn(shared, lay, tokens, x, is_first, is_last):
        shc = jax.tree.map(lambda a, t: a.astype(t), shared, sh_dtypes)
        lac = jax.tree.map(lambda a, t: a.astype(t), lay, lay_dtypes)
        x0 = jax.lax.cond(is_first,
                          lambda: model._embed_tokens(shc, tokens),
                          lambda: x)
        mb, S = x0.shape[0], x0.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                     (mb, S))
        y, aux, _ = model._backbone({"layers": lac}, x0, positions)
        ce = jax.lax.cond(
            is_last,
            lambda: model._ce_from_hidden(shc, y, tokens),
            lambda: jnp.float32(0.0))
        return y, jnp.stack([ce, jnp.asarray(aux, jnp.float32)])

    return stage_fn


def pipeline_loss_and_grads(model: Model, mesh, n_micro: int,
                            axis: str = "pod"):
    """Build loss_and_grads(params, batch) -> (loss, grads, metrics)
    running the 1F1B schedule over ``axis`` (launch/pipeline.py)."""
    from repro.launch import pipeline

    cfg = model.cfg
    n_stages = mesh.shape[axis]
    stage_fn = make_pipeline_stage_fn(model)

    def loss_and_grads(params, batch):
        tokens = batch["tokens"]
        B, S = tokens.shape
        assert B % n_micro == 0, (B, n_micro)
        mb = B // n_micro
        tok_micro = tokens.reshape(n_micro, mb, S)
        shared, lay_stacked = pipeline_split_params(params, n_stages)
        act = jax.ShapeDtypeStruct((mb, S, cfg.d_model),
                                   jnp.dtype(cfg.dtype))
        loss_parts, g_shared, g_lay = pipeline.pipeline_grads(
            mesh, stage_fn, shared, lay_stacked, tok_micro, act,
            n_micro, axis=axis)
        grads = dict(g_shared, layers=pipeline_merge_layer_grads(g_lay))
        ce, aux = loss_parts[0], loss_parts[1]
        metrics = {"ce": ce, "aux": aux,
                   "ppl_proxy": jnp.exp(jnp.minimum(ce, 20.0))}
        return ce + aux, grads, metrics

    return loss_and_grads


def make_pipeline_train_step(model: Model, opt: base.Optimizer,
                             ocfg: OptimizerConfig, mesh, n_micro: int,
                             axis: str = "pod", inject=None) -> Callable:
    """1F1B variant of make_train_step (same signature/jit contract,
    including the ``inject`` chaos hook).

    Gradients come out of the pipeline engine in fp32 (differentiated
    wrt the fp32 masters), so ``grads_dtype="bfloat16"`` — a data-
    parallel wire-format optimization — is not applicable here."""
    assert ocfg.grads_dtype != "bfloat16", \
        "pipeline training differentiates wrt fp32 masters"
    loss_and_grads = pipeline_loss_and_grads(model, mesh, n_micro,
                                             axis=axis)

    def train_step(params, opt_state, batch, step, refresh=None):
        with jax.named_scope("fwd_bwd"):
            loss, grads, metrics = loss_and_grads(params, batch)
        return _clip_and_update(opt, ocfg, inject, params, opt_state, loss,
                                grads, metrics, step, refresh)

    return train_step


def opt_state_shardings(mesh, opt: base.Optimizer, param_shapes,
                        param_shardings):
    """Sharding tree for the optimizer state: per-param buffers matching
    the param's shape inherit its sharding; everything else replicates."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    state_shapes = jax.eval_shape(opt.init, param_shapes)
    rep = NamedSharding(mesh, P())
    if "leaves" not in state_shapes:
        # adamw: state trees mirror params exactly
        def like(tree):
            return jax.tree.map(
                lambda s, sh: sh if hasattr(s, "shape") and s.shape else rep,
                tree, param_shardings)

        return {k: (like(v) if isinstance(v, dict) else rep)
                for k, v in state_shapes.items()}

    is_slot = lambda x: isinstance(x, dict) and "mom" in x
    from repro.launch.sharding import (PRECOND_CACHE_STATE_KEYS,
                                       precond_cache_sharding)

    def per_param(slot, pshape, pshard):
        out = {}
        for k, v in slot.items():
            if tuple(v.shape) == tuple(pshape.shape):
                out[k] = pshard
            elif k in PRECOND_CACHE_STATE_KEYS and len(v.shape) >= 2:
                # cached preconditioners whose layout differs from the
                # param (matrix views / factor squares): ZeRO-style
                # lead->model, rows->data instead of full replication.
                # Pending twins ("*_p", §12) shard identically, so the
                # double-buffer swap is a local per-shard select.
                out[k] = precond_cache_sharding(mesh, tuple(v.shape))
            else:
                out[k] = rep
        return out

    leaves = jax.tree.map(per_param, state_shapes["leaves"], param_shapes,
                          param_shardings, is_leaf=is_slot)
    # non-leaf scalars ("count", and "pending_at" under §12) replicate
    return dict({k: rep for k in state_shapes if k != "leaves"},
                leaves=leaves)
