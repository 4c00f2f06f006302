"""Muon optimizer with PRISM orthogonalization (paper Sec. 6.2).

Matrix-shaped hidden weights: nesterov momentum -> polar factor of the
momentum (method selectable: prism | newton_schulz | polar_express | svd)
-> aspect-ratio-scaled update.  Everything else (embeddings, norms,
biases, routers) falls back to AdamW with a scaled lr, as in standard Muon
practice.

Orthogonalization dispatch is shape-bucketed by default
(optim/bucketing.py): same-shape momentum matrices stack into one
[B, m, n] batched polar call per bucket, so the whole tree costs a
constant number of compiled NS chains (and Pallas launches) instead of
one per leaf.  ``cfg.bucketed=False`` restores the per-leaf loop.

Under pjit the polar iteration's GEMMs run on *sharded* momentum matrices,
so orthogonalization is distributed for free (DION-style), and the PRISM
sketch fit adds only O(n^2 p / shards) work per fitted iteration.  With an
activation-sharding context the bucketed engine additionally shard_maps
each bucket's batch dim over the (pod, data) axes (DESIGN.md §8).

``cfg.precond_every = K > 1`` amortizes the matrix-function work over K
steps: every matrix leaf carries a cached orthogonalized view ("ortho")
in the state, refreshed when count % K == 0 (exact at step 0) and reused
— against the *fresh* momentum-accumulating state — in between.  The
``refresh`` argument of ``update`` overrides the schedule statically: a
Python bool picks the branch at trace time, so a skip step compiles with
zero matrix-function work (the launch-count contract of DESIGN.md §8).

Precision (DESIGN.md §9): ``cfg.matfn_dtype`` sets the compute dtype of
the whole orthogonalization path (bucket gathers stack directly in bf16);
``cfg.cache_dtype`` sets the storage dtype of the "ortho" cache — every
step (refresh or stale) applies the cache-dtype polar, so the update
direction is schedule-invariant.  Momentum and the applied parameter
delta stay fp32.

Adaptive early stopping (DESIGN.md §11): ``cfg.matfn_tol`` threads a
convergence certificate into every bucketed polar chain — each bucket
iterates only until its slowest slice certifies, instead of the full
static budget — and the realized per-matrix iteration counts surface as
an ``iters`` entry in each matrix leaf's state (``cfg.matfn_telemetry``).

Async refresh plane (DESIGN.md §12): with ``cfg.precond_async`` the
polar chains NEVER run inside ``update``.  Each matrix leaf carries an
active "ortho" buffer (consumed every step) and a pending "ortho_p"
twin; the separately jitted ``refresh`` member recomputes the pending
polars from the stored momentum (bucketing.polar_refresh — the same
computation an in-step refresh would run) and the update swaps
pending -> active under ONE lax.cond once
``count >= pending_at + precond_swap_delay``.  The update also
accumulates the drift proxy ("dnorm"/"rnorm": movement of the momentum
relative to its norm at the last refresh dispatch) that feeds the
drift-triggered schedule.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.config import OptimizerConfig
from repro.core import matfn
from repro.optim import base, bucketing


def _flatten_with_axes(params, axes_tree):
    flat_p = jax.tree.leaves(params)
    flat_a = jax.tree.leaves(
        axes_tree, is_leaf=lambda t: isinstance(t, tuple) and
        all(isinstance(x, (str, type(None))) for x in t))
    treedef = jax.tree.structure(params)
    return flat_p, flat_a, treedef


def _view_shape(shape, axes) -> tuple:
    """Shape of a leaf's matrix view, computed abstractly."""
    return jax.eval_shape(lambda x: base.to_matrix_view(x, axes)[0],
                          jax.ShapeDtypeStruct(shape, jnp.float32)).shape


def matrix_view_shapes(param_shapes, axes_tree,
                       allow_embed: bool = False) -> list:
    """[.., m, n] shapes of the momentum views Muon orthogonalizes — the
    shapes the bucketing planner groups (optim/bucketing.py)."""
    flat_p, flat_a, _ = _flatten_with_axes(param_shapes, axes_tree)
    return [_view_shape(p.shape, a) for p, a in zip(flat_p, flat_a)
            if base.is_matrix_param(a, p.shape, allow_embed)]


def make_muon(cfg: OptimizerConfig, axes_tree) -> base.Optimizer:
    # §11 telemetry: with an adaptive tol the realized per-matrix
    # iteration counts ("iters", the view's lead shape, int32) ride in
    # the state next to the momentum — observability for schedules,
    # benchmarks and tests, refreshed whenever the polar chains run and
    # carried through stale (cached) steps untouched.  §15 adds the
    # per-matrix int8 guardian "status" (prism.STATUS_*) alongside, on
    # the same refresh/stale/swap lifecycle.
    telemetry = cfg.matfn_telemetry
    # §14: with the lowrank tier enabled Muon claims embedding/LM-head/
    # codebook leaves too (base.is_matrix_param), and every matrix leaf
    # carries a static "tier" telemetry scalar naming the kernel tier
    # the bucketing planner resolves for its view shape.
    allow_embed = cfg.lowrank_rank > 0

    def init(params):
        flat_p, flat_a, treedef = _flatten_with_axes(params, axes_tree)
        state = []
        for p, a in zip(flat_p, flat_a):
            mom = jnp.zeros(p.shape, jnp.float32)
            if base.is_matrix_param(a, p.shape, allow_embed):
                s = {"mom": mom}
                # only the view SHAPE is needed for the telemetry/cache
                # entries: eval_shape runs the view reshape abstractly,
                # so init of an embedding-bearing tree never
                # materializes a throwaway full-size zeros view
                vshape = _view_shape(p.shape, a)
                if telemetry:
                    s["iters"] = jnp.zeros(vshape[:-2], jnp.int32)
                    s["status"] = jnp.zeros(vshape[:-2], jnp.int8)
                if allow_embed:
                    s["tier"] = jnp.full(
                        (), bucketing.TIER_CODES[bucketing.resolve_tier(
                            cfg, vshape[-2:])], jnp.int32)
                if cfg.precond_every > 1:
                    # staleness cache: the orthogonalized momentum VIEW
                    # (possibly transposed/flattened vs the param layout);
                    # stored in cfg.cache_dtype — bf16 halves cached
                    # optimizer state, sharding rules unchanged (§9).
                    # Under §14 this is the LIFTED full-size view, so the
                    # §12 double buffer and the precond-cache sharding
                    # rules apply to the lowrank tier without special
                    # cases.
                    s["ortho"] = jnp.zeros(vshape,
                                           jnp.dtype(cfg.cache_dtype))
                if cfg.precond_async:
                    # §12 double buffer: pending twin (sharded like the
                    # active cache) + the drift-proxy scalars
                    s["ortho_p"] = jnp.zeros(vshape,
                                             jnp.dtype(cfg.cache_dtype))
                    s["dnorm"] = jnp.zeros((), jnp.float32)
                    s["rnorm"] = jnp.zeros((), jnp.float32)
                    if telemetry:
                        s["iters_p"] = jnp.zeros(vshape[:-2], jnp.int32)
                        s["status_p"] = jnp.zeros(vshape[:-2], jnp.int8)
                state.append(s)
            else:
                state.append({"mom": mom,
                              "nu": jnp.zeros(p.shape, jnp.float32)})
        out = {"leaves": jax.tree.unflatten(treedef, state),
               "count": jnp.zeros((), jnp.int32)}
        if cfg.precond_async:
            # step index the in-flight refresh was dispatched at;
            # NO_PENDING = nothing in flight (swap cond never taken)
            out["pending_at"] = jnp.full((), base.NO_PENDING, jnp.int32)
        return out

    def _polar_per_leaf(views, leaf_idx, key):
        """Legacy per-leaf dispatch: one polar chain per matrix leaf.
        Returns (outs, iters, statuses) with the latter two None unless
        telemetry."""
        outs, its, sts = [], [], []
        for M, i in zip(views, leaf_idx):
            if cfg.muon_local_reshard and M.ndim >= 3:
                # layers -> model, rows -> data: the NS iterations then
                # need only one [n, n] R-psum over 16 chips per step
                # instead of cross-mesh GEMM collectives
                from repro.sharding_ctx import shard_activation

                M = shard_activation(
                    M, ("opt_layers",) * (M.ndim - 2)
                    + ("opt_rows", None))
            kk = jax.random.fold_in(key, i) if key is not None else None
            if cfg.matfn_method == "svd":
                if telemetry:
                    O, it, st = matfn.polar(M, method="svd",
                                            return_iters=True,
                                            return_status=True)
                    outs.append(O)
                    its.append(it)
                    sts.append(st)
                else:
                    outs.append(matfn.polar(M, method="svd"))
            elif telemetry:
                O, it, st = matfn.polar(M, method=cfg.matfn_method,
                                        cfg=cfg.resolved_prism, key=kk,
                                        return_iters=True,
                                        return_status=True)
                outs.append(O)
                its.append(it)
                sts.append(st)
            else:
                outs.append(matfn.polar(M, method=cfg.matfn_method,
                                        cfg=cfg.resolved_prism, key=kk))
        return (outs, its, sts) if telemetry else (outs, None, None)

    def update(grads, state, params, step, key, refresh=None):
        flat_g, flat_a, treedef = _flatten_with_axes(grads, axes_tree)
        flat_p = jax.tree.leaves(params)
        flat_s = treedef.flatten_up_to(state["leaves"])
        lr = cfg.learning_rate
        new_p = [None] * len(flat_g)
        new_s = [None] * len(flat_g)
        # pass 1: momentum everywhere; AdamW leaves finish immediately,
        # matrix leaves only queue their nesterov momentum view
        views, metas, leaf_idx = [], [], []
        for i, (g, a, p, s) in enumerate(zip(flat_g, flat_a, flat_p,
                                             flat_s)):
            g = g.astype(jnp.float32)
            if base.is_matrix_param(a, p.shape, allow_embed):
                mom = cfg.momentum * s["mom"] + g
                gm = g + cfg.momentum * mom  # nesterov
                M, meta = base.to_matrix_view(gm, a)
                views.append(M)
                metas.append(meta)
                leaf_idx.append(i)
                new_s[i] = {"mom": mom}
                if allow_embed:
                    new_s[i]["tier"] = s["tier"]
                if cfg.precond_every > 1:
                    new_s[i]["ortho"] = s["ortho"]
                if cfg.precond_async:
                    # drift proxy (§12): accumulate the Frobenius
                    # movement of the momentum (the matrix the cached
                    # polar was computed from) since the last refresh
                    # dispatch; read back as dnorm/rnorm by
                    # base.precond_drift
                    new_s[i]["dnorm"] = s["dnorm"] + jnp.sqrt(
                        jnp.sum(jnp.square(mom - s["mom"])))
                    new_s[i]["rnorm"] = s["rnorm"]
            else:
                # AdamW for non-matrix params
                b1, b2 = cfg.beta1, cfg.beta2
                mom = b1 * s["mom"] + (1 - b1) * g
                nu = b2 * s["nu"] + (1 - b2) * jnp.square(g)
                t = (state["count"] + 1).astype(jnp.float32)
                mhat = mom / (1 - b1 ** t)
                vhat = nu / (1 - b2 ** t)
                alr = lr * cfg.adamw_lr_scale
                p32 = p.astype(jnp.float32) * (1.0 - alr * cfg.weight_decay) \
                    - alr * mhat / (jnp.sqrt(vhat) + cfg.eps)
                new_s[i] = {"mom": mom, "nu": nu}
                new_p[i] = p32.astype(p.dtype)
        # orthogonalize: one batched call per shape bucket (the per-leaf
        # Python loop survives only behind cfg.bucketed=False).  With
        # precond_every=K>1 the polar chains run behind the staleness
        # schedule: refreshed when count % K == 0 (or when the static
        # ``refresh`` override says so), served from the "ortho" cache
        # otherwise — a skip step moves zero matrix-function bytes.
        def compute_polars():
            if cfg.bucketed:
                if telemetry:
                    return bucketing.polar_bucketed(views, cfg, key,
                                                    with_iters=True)
                return (bucketing.polar_bucketed(views, cfg, key),
                        None, None)
            return _polar_per_leaf(views, leaf_idx, key)

        if cfg.precond_async and views:
            # §12 steady state: NEVER compute polars here.  Serve the
            # active cache, except when an in-flight refresh has had
            # precond_swap_delay steps to land — then ONE lax.cond swaps
            # every leaf's pending buffer in (a local per-shard select,
            # no matfn launches, no collectives).
            pend = [flat_s[i]["ortho_p"] for i in leaf_idx]
            act = [flat_s[i]["ortho"] for i in leaf_idx]
            pending_at = state["pending_at"]
            do_swap = (pending_at > base.NO_PENDING) & (
                state["count"] >= pending_at + cfg.precond_swap_delay)
            none_pending = jnp.full((), base.NO_PENDING, jnp.int32)
            if telemetry:
                it_p = [flat_s[i]["iters_p"] for i in leaf_idx]
                it_a = [flat_s[i]["iters"] for i in leaf_idx]
                st_p = [flat_s[i]["status_p"] for i in leaf_idx]
                st_a = [flat_s[i]["status"] for i in leaf_idx]
                polars, its, sts, new_pending_at = jax.lax.cond(
                    do_swap,
                    lambda: (pend, it_p, st_p, none_pending),
                    lambda: (act, it_a, st_a, pending_at))
            else:
                its = sts = None
                polars, new_pending_at = jax.lax.cond(
                    do_swap,
                    lambda: (pend, none_pending),
                    lambda: (act, pending_at))
            for j, i in enumerate(leaf_idx):
                new_s[i]["ortho"] = polars[j]
                new_s[i]["ortho_p"] = pend[j]
                if telemetry:
                    new_s[i]["iters_p"] = it_p[j]
                    new_s[i]["status_p"] = st_p[j]
        elif cfg.precond_every > 1 and views:
            cache_dt = jnp.dtype(cfg.cache_dtype)
            cached = [flat_s[i]["ortho"] for i in leaf_idx]
            cached_it = ([flat_s[i]["iters"] for i in leaf_idx]
                         if telemetry else None)
            cached_st = ([flat_s[i]["status"] for i in leaf_idx]
                         if telemetry else None)

            def compute_cached():
                # round to the cache dtype up front: both lax.cond
                # branches carry the same dtype, and refresh vs stale
                # steps apply identical (cache-rounded) polars
                polars, its, sts = compute_polars()
                return [O.astype(cache_dt) for O in polars], its, sts

            def stale():
                # stale steps reuse the cache AND its telemetry: "iters"
                # and "status" always describe the most recent refresh
                return (list(cached),
                        list(cached_it) if telemetry else None,
                        list(cached_st) if telemetry else None)

            if isinstance(refresh, bool):  # static: picked at trace time
                polars, its, sts = compute_cached() if refresh else stale()
            else:
                do = (state["count"] % cfg.precond_every) == 0
                polars, its, sts = jax.lax.cond(do, compute_cached, stale)
            for j, i in enumerate(leaf_idx):
                new_s[i]["ortho"] = polars[j]
        else:
            polars, its, sts = compute_polars()
        if telemetry:
            for j, i in enumerate(leaf_idx):
                new_s[i]["iters"] = its[j]
                new_s[i]["status"] = sts[j]
        # pass 2: aspect-scale, un-view, apply
        for O, meta, i in zip(polars, metas, leaf_idx):
            p = flat_p[i]
            m_, n_ = O.shape[-2], O.shape[-1]
            scale = jnp.sqrt(jnp.maximum(1.0, m_ / n_))
            upd = base.from_matrix_view(O * scale, meta)
            p32 = p.astype(jnp.float32) * (1.0 - lr * cfg.weight_decay) \
                - lr * upd
            new_p[i] = p32.astype(p.dtype)
        out_state = {"leaves": jax.tree.unflatten(treedef, new_s),
                     "count": state["count"] + 1}
        if cfg.precond_async:
            out_state["pending_at"] = (new_pending_at if views
                                       else state["pending_at"])
        return jax.tree.unflatten(treedef, new_p), out_state

    def refresh(state, key):
        """§12 refresh plane: recompute the pending polar buffers from
        the STORED momentum (the matrix the active cache will have been
        computed from by swap time) as one standalone jittable program.
        Returns per-slot partial dicts for base.install_pending."""
        slots, _ = base._flat_slots(state["leaves"])
        flat_a = jax.tree.leaves(
            axes_tree, is_leaf=lambda t: isinstance(t, tuple) and
            all(isinstance(x, (str, type(None))) for x in t))
        views, idx = [], []
        for i, (s, a) in enumerate(zip(slots, flat_a)):
            if "ortho_p" in s:
                M, _meta = base.to_matrix_view(s["mom"], a)
                views.append(M)
                idx.append(i)
        partials: list = [{} for _ in slots]
        if not views:
            return partials
        outs, its, sts = bucketing.polar_refresh(views, cfg, key)
        cache_dt = jnp.dtype(cfg.cache_dtype)
        for j, i in enumerate(idx):
            # zero-slice guard: the bootstrap dispatch runs before any
            # update, so the momentum can be exactly zero — the PRISM
            # alpha fit on zero traces is 0/0.  A zero matrix's polar
            # serves as zero (a no-op update), not NaN.
            nrm = jnp.sqrt(jnp.sum(jnp.square(views[j]), axis=(-2, -1),
                                   keepdims=True))
            O = jnp.where(nrm > 0, outs[j], jnp.zeros_like(outs[j]))
            p = {"ortho_p": O.astype(cache_dt),
                 # drift baseline resets to the dispatched matrix
                 "rnorm": jnp.sqrt(jnp.sum(jnp.square(views[j]))),
                 "dnorm": jnp.zeros((), jnp.float32)}
            if telemetry:
                p["iters_p"] = its[j]
                p["status_p"] = sts[j]
            partials[i] = p
        return partials

    return base.Optimizer(init, update, refresh)
