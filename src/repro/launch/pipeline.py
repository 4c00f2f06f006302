"""Pipeline parallelism over a mesh axis: GPipe forward + 1F1B training.

``pipeline_apply`` runs a stack of identical stages (each owning an equal
slice of the layer stack) over a mesh axis — on the production mesh the
"pod" axis, so each pod holds a contiguous layer slice and activations
stream between pods via collective_permute, replacing cross-pod parameter
replication with a fill-drain microbatch schedule.

Forward schedule (GPipe): T = n_micro + n_stages - 1 ticks; stage s
processes microbatch (t - s) at tick t.

Training schedule (1F1B, ``one_f_one_b``): backward interleaves into the
same tick scan.  For microbatch m on stage s (S stages, M microbatches):

    forward  tick:  m + s
    backward tick:  m + 2*(S-1) - s
    total ticks  :  T = M + 2*(S-1)            (``n_ticks_1f1b``)

so the last stage's backward of microbatch m lands one tick pattern that
interleaves 1 forward with 1 backward in steady state; the fill+drain
bubble is the 2*(S-1) tick overhead (``bubble_fraction`` = 2*(S-1)/T,
strictly decreasing in M).  Activations hand over s→s+1 and gradients
s→s-1 via ``lax.ppermute`` at the end of every tick.  Each stage stashes
only its *inputs* (one [M, mb, ...] buffer, written in place so XLA
aliases it across the scan — the donated microbatch buffer); the
backward re-runs the stage forward under ``jax.vjp`` (rematerialization),
so fill/drain never holds more than the input stash.

Both wrappers run inside ``shard_map`` manual over the pipeline axis;
everything else (data/tensor sharding inside a stage) composes via the
remaining mesh axes left in "auto" mode.  Stage bodies contain no
collectives, so gating them under ``lax.cond`` with a device-varying
(fill/drain) predicate is legal and skips the wasted compute.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding_ctx import suspend_activation_sharding


def n_ticks_1f1b(n_stages: int, n_micro: int) -> int:
    """Ticks in one 1F1B step: n_micro + fill + drain."""
    return n_micro + 2 * (n_stages - 1)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """Fraction of ticks a stage idles (fill+drain) under 1F1B."""
    return 2.0 * (n_stages - 1) / n_ticks_1f1b(n_stages, n_micro)


def gpipe_forward(stage_fn: Callable, axis: str, n_stages: int,
                  n_micro: int):
    """Build the per-device pipelined forward.

    stage_fn(stage_params, x) -> y : one stage's computation; x/y share
    shape [micro_batch, ...].

    Returns fn(stage_params_local, x_micro [n_micro, mb, ...]) -> y
    (valid on the LAST stage; other stages return zeros) to be used
    inside shard_map with the stage dim of params mapped over ``axis``.
    """

    def run(params_local, x_micro):
        # shard_map keeps the sharded stage dim with local size 1: drop it
        params_local = jax.tree.map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis)
        T = n_micro + n_stages - 1
        mb_shape = x_micro.shape[1:]

        def tick(carry, t):
            buf, outs = carry
            mb = t - stage
            active = jnp.logical_and(mb >= 0, mb < n_micro)
            # stage 0 reads its own microbatch; later stages read the
            # activation handed over by the previous stage
            x0 = jax.lax.dynamic_index_in_dim(
                x_micro, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            x_in = jnp.where(stage == 0, x0, buf)
            # fill/drain ticks skip the stage compute entirely (legal:
            # stage bodies are collective-free, so a device-varying
            # predicate cannot deadlock the mesh)
            y = jax.lax.cond(
                active,
                lambda x: stage_fn(params_local, x),
                lambda x: jnp.zeros_like(x), x_in)
            # hand over to the next stage
            buf_next = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(n_stages - 1)])
            # last stage records its finished microbatch
            is_last = stage == n_stages - 1
            outs = jax.lax.cond(
                jnp.logical_and(active, is_last),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(mb, 0, n_micro - 1), 0),
                lambda o: o, outs)
            return (buf_next, outs), None

        buf0 = jnp.zeros(mb_shape, x_micro.dtype)
        outs0 = jnp.zeros((n_micro,) + mb_shape, x_micro.dtype)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(T))
        return outs

    return run


def pipeline_apply(mesh, stage_fn: Callable, stage_params, x,
                   n_micro: int, axis: str = "pod"):
    """Run x [B, ...] through n_stages pipelined stages over ``axis``.

    stage_params: pytree with a leading stage dimension == mesh.shape[axis]
    on every leaf.  Returns y [B, ...] (gathered from the last stage).
    """
    n_stages = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro
    x_micro = x.reshape((n_micro, mb) + x.shape[1:])
    run = gpipe_forward(stage_fn, axis, n_stages, n_micro)

    mapped = jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(axis),  # per-stage outputs stacked; last stage valid
        axis_names={axis},  # other mesh axes stay "auto"
        check_vma=False)
    outs = mapped(stage_params, x_micro)
    # outs [n_stages * n_micro, mb, ...]: only the last stage's block is
    # the real output (earlier stages contributed zeros)
    outs = outs.reshape((n_stages, n_micro, mb) + x.shape[1:])
    return outs[-1].reshape((B,) + x.shape[1:])


def _handover(y, axis: str, n_stages: int, stage, direction: int,
              use_ppermute: bool):
    """Ship y one stage over (+1 forward, -1 backward); edges get zeros.

    The natural collective is ``ppermute``, which lowers cleanly under
    the fully-manual shard_map ``pipeline_grads`` builds and is the
    default.  ``use_ppermute=False`` keeps a one-hot scatter + psum over
    the stage axis as an escape hatch (same values, S× the wire bytes):
    partial-manual lowering — where XLA's SPMD partitioner hard-crashes
    on collective-permute (hlo_sharding_util CHECK: IsManualSubgroup) —
    is exactly the kind of regression a future mesh layout could
    reintroduce, and the fallback is parity-tested against it."""
    if use_ppermute:
        pairs = ([(i, i + 1) for i in range(n_stages - 1)]
                 if direction > 0
                 else [(i, i - 1) for i in range(1, n_stages)])
        return jax.lax.ppermute(y, axis, pairs)
    tgt = stage + direction
    valid = jnp.logical_and(tgt >= 0, tgt < n_stages)
    buf = jnp.zeros((n_stages,) + y.shape, y.dtype)
    buf = jax.lax.dynamic_update_index_in_dim(
        buf, jnp.where(valid, y, jnp.zeros_like(y)),
        jnp.clip(tgt, 0, n_stages - 1), 0)
    buf = jax.lax.psum(buf, axis)
    return jax.lax.dynamic_index_in_dim(buf, stage, 0, keepdims=False)


def one_f_one_b(stage_fn: Callable, axis: str, n_stages: int,
                n_micro: int, act, use_ppermute: bool = True,
                dp_axes=(), dp_size: int = 1):
    """Build the per-device 1F1B loss+grad engine.

    stage_fn(shared, lay_local, inp, x, is_first, is_last) -> (y, loss)
      one stage's forward: ``shared`` are the replicated parameters
      (embedding / final norm / head), ``lay_local`` this stage's layer
      slice, ``inp`` the microbatch input (e.g. tokens [mb, seq]), ``x``
      the incoming activation (ignored when ``is_first``), and
      ``is_first`` / ``is_last`` traced stage predicates.  ``y`` must
      have the shape/dtype of ``act`` (a ShapeDtypeStruct [mb, ...]);
      ``loss`` is a fixed-shape fp32 array of per-microbatch loss parts
      (zero where the stage doesn't own that term).

    Returns run(shared, lay_stacked, inp_micro [M, ...]) ->
      (loss_parts, g_shared, g_lay_stacked) where loss_parts and
      g_shared are psum'd over ``axis`` (hence replicated — this is what
      makes tied embeddings and per-stage MoE aux losses "just work"),
      losses and gradients are microbatch *means*, and g_lay_stacked
      keeps the local stage dim of size 1 for a P(axis) out_spec.
    """
    S, M = n_stages, n_micro
    T = n_ticks_1f1b(S, M)
    scale = 1.0 / M

    def run(shared, lay_stacked, inp_micro, stage_arr):
        lay = jax.tree.map(lambda p: p[0], lay_stacked)
        # stage index arrives as a pod-sharded iota ([1] per device)
        # rather than lax.axis_index: under partial-manual shard_map
        # (data/model auto) axis_index lowers to a PartitionId op that
        # GSPMD refuses to partition
        stage = stage_arr[0]
        is_first = stage == 0
        is_last = stage == S - 1

        def full_stage(sh, la, inp, x):
            with suspend_activation_sharding():
                return stage_fn(sh, la, inp, x, is_first, is_last)

        inp0 = jax.tree.map(lambda a: a[0], inp_micro)
        x_zero = jnp.zeros(act.shape, act.dtype)
        _, loss_shape = jax.eval_shape(full_stage, shared, lay, inp0,
                                       x_zero)
        loss_zero = jnp.zeros(loss_shape.shape, loss_shape.dtype)
        cotangent = jnp.full(loss_shape.shape, scale, loss_shape.dtype)

        def tick(carry, t):
            fwd_buf, bwd_buf, stash, g_sh, g_lay, loss_acc = carry

            # ---- forward half: microbatch (t - stage) ----
            f_mb = t - stage
            f_valid = jnp.logical_and(f_mb >= 0, f_mb < M)
            fc = jnp.clip(f_mb, 0, M - 1)
            inp_f = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, fc, 0, keepdims=False), inp_micro)
            x_in = fwd_buf
            # stash the stage INPUT (the only activation kept per
            # microbatch; the backward rematerializes the rest).  The
            # in-place dynamic update lets XLA alias one [M, mb, ...]
            # buffer across the whole scan.
            stash = jax.lax.cond(
                f_valid,
                lambda s: jax.lax.dynamic_update_index_in_dim(
                    s, x_in, fc, 0),
                lambda s: s, stash)
            # the last stage's forward is fused into its backward tick
            # (the vjp recomputes it), so only feeder stages run the
            # forward-for-handover here
            y = jax.lax.cond(
                jnp.logical_and(f_valid, jnp.logical_not(is_last)),
                lambda: full_stage(shared, lay, inp_f, x_in)[0],
                lambda: x_zero)
            fwd_next = _handover(y, axis, S, stage, +1, use_ppermute)

            # ---- backward half: microbatch (t - 2(S-1) + stage) ----
            b_mb = t - 2 * (S - 1) + stage
            b_valid = jnp.logical_and(b_mb >= 0, b_mb < M)
            bc = jnp.clip(b_mb, 0, M - 1)
            inp_b = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, bc, 0, keepdims=False), inp_micro)
            x_b = jax.lax.dynamic_index_in_dim(stash, bc, 0,
                                               keepdims=False)

            def do_bwd():
                (y2, loss), vjp = jax.vjp(
                    lambda sh, la, x: full_stage(sh, la, inp_b, x),
                    shared, lay, x_b)
                # downstream cotangent: the grad handed back by stage
                # s+1; the last stage seeds only through the loss
                g_y = jnp.where(is_last, jnp.zeros_like(y2), bwd_buf)
                d_sh, d_la, dx = vjp((g_y, cotangent))
                return d_sh, d_la, dx, loss

            def no_bwd():
                return (jax.tree.map(jnp.zeros_like, shared),
                        jax.tree.map(jnp.zeros_like, lay),
                        jnp.zeros(act.shape, act.dtype), loss_zero)

            d_sh, d_la, dx, loss_b = jax.lax.cond(b_valid, do_bwd,
                                                  no_bwd)
            g_sh = jax.tree.map(jnp.add, g_sh, d_sh)
            g_lay = jax.tree.map(jnp.add, g_lay, d_la)
            loss_acc = loss_acc + loss_b
            bwd_next = _handover(dx, axis, S, stage, -1, use_ppermute)
            return (fwd_next, bwd_next, stash, g_sh, g_lay,
                    loss_acc), None

        carry0 = (x_zero, jnp.zeros(act.shape, act.dtype),
                  jnp.zeros((M,) + act.shape, act.dtype),
                  jax.tree.map(jnp.zeros_like, shared),
                  jax.tree.map(jnp.zeros_like, lay), loss_zero)
        (_, _, _, g_sh, g_lay, loss_acc), _ = jax.lax.scan(
            tick, carry0, jnp.arange(T))

        # shared params see every microbatch on every stage slot that
        # uses them; layer grads live on their stage.  psum makes losses
        # and shared grads replicated (P() out_specs).
        # the 1/M mean is baked into the backward cotangent seed, so the
        # accumulated grads are already microbatch means; only the raw
        # loss sum still needs the scale.  With the microbatch dim
        # sharded over dp_axes, each DP shard holds the mean over its
        # slice — psum over (stage, dp) and divide by the DP degree.
        red = (axis,) + tuple(dp_axes)
        inv = 1.0 / dp_size
        loss_tot = jax.lax.psum(loss_acc * (scale * inv), red)
        g_sh = jax.tree.map(lambda a: jax.lax.psum(a, red) * inv, g_sh)
        if dp_axes:
            g_lay = jax.tree.map(
                lambda a: jax.lax.psum(a, tuple(dp_axes)) * inv, g_lay)
        g_lay = jax.tree.map(lambda a: a[None], g_lay)
        return loss_tot, g_sh, g_lay

    return run


def pipeline_grads(mesh, stage_fn: Callable, shared, lay_stacked,
                   inp_micro, act, n_micro: int, axis: str = "pod"):
    """shard_map wrapper around ``one_f_one_b`` over ``axis``.

    shared: replicated parameter pytree; lay_stacked: pytree with a
    leading stage dim == mesh.shape[axis] on every leaf; inp_micro:
    per-microbatch inputs [n_micro, mb, ...] (every stage needs the
    tokens that seed its loss terms; the mb dim is sharded over "data"
    when the mesh has one and mb divides); act: ShapeDtypeStruct of one
    GLOBAL microbatch activation [mb, ...] (divided by the data degree
    internally).  Returns (loss_parts, g_shared, g_lay_stacked) — the
    first two replicated, the last stage-sharded.
    """
    n_stages = mesh.shape[axis]
    # The shard_map is FULLY manual over every mesh axis: XLA's
    # partial-manual (manual-subgroup) lowering hard-crashes on the
    # transformer backbone in this jax/XLA generation (CHECK failure in
    # hlo_sharding_util IsManualSubgroup), so nothing may be left in
    # auto mode.  Data parallelism is therefore explicit: the
    # per-microbatch dim is sharded over "data" and the engine psums /
    # averages grads over it (dp_axes); any tensor-model axes replicate
    # the stage compute (params enter replicated via P()).  Full-manual
    # also means ppermute lowers cleanly, so handover uses the real
    # collective.
    dp_axes: tuple = ()
    leaves = jax.tree.leaves(inp_micro)
    if "data" in mesh.axis_names and axis != "data" and \
            all(a.ndim >= 2 and a.shape[1] % mesh.shape["data"] == 0
                for a in leaves):
        dp_axes = ("data",)
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    inp_spec = P(None, *dp_axes) if dp_axes else P()
    if dp_size > 1:  # per-device activation: mb shrinks by the DP degree
        act = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                (s.shape[0] // dp_size,) + tuple(s.shape[1:]), s.dtype),
            act)
    run = one_f_one_b(stage_fn, axis, n_stages, n_micro, act,
                      use_ppermute=True, dp_axes=dp_axes, dp_size=dp_size)
    mapped = jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(), P(axis), inp_spec, P(axis)),
        out_specs=(P(), P(), P(axis)),
        check_vma=False)
    return mapped(shared, lay_stacked, inp_micro,
                  jnp.arange(n_stages, dtype=jnp.int32))
