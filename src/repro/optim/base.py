"""Optimizer interface + shared utilities (pure pytree, optax-free)."""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class Optimizer(NamedTuple):
    """init(params) -> state;
    update(grads, state, params, step, key, refresh=None)
        -> (new_params, new_state).

    ``refresh`` is the staleness-schedule override for cached matrix
    preconditioners (OptimizerConfig.precond_every, DESIGN.md §8):
      None  — dynamic: the optimizer decides from state["count"] under a
              lax.cond (single compiled step, both branches traced);
      bool  — static: the branch is picked at trace time, so the trainer
              can compile a skip-step variant that contains zero
              matrix-function work (and a refresh variant that always
              recomputes).  Optimizers without caches ignore it.

    The REFRESH PLANE (DESIGN.md §12) extends that contract from "skip"
    to "never-in-step": with ``OptimizerConfig.precond_async`` the update
    only ever consumes the ACTIVE preconditioner buffer (and swaps a
    PENDING one in under a lax.cond — zero matrix-function launches on
    every step), while the matfn chains themselves live in the separate
    ``refresh`` callable:

        refresh(state, key) -> flat list of per-slot partial dicts

    — one dict per state slot (the flattened order of
    ``_flat_slots(state["leaves"])``), holding exactly the entries to
    overwrite: the pending buffers (``ortho_p`` / ``Linv_p`` /
    ``Rinv_p``), the drift-reference norm ``rnorm``, a zeroed drift
    accumulator ``dnorm``, and pending telemetry twins.  The trainer jits
    and dispatches it WITHOUT blocking and installs the result via
    ``install_pending`` (pure pytree surgery — no device compute), so
    steps overlap the chains instead of waiting on them.  ``None`` for
    optimizers without a cached-preconditioner plane (AdamW).
    """

    init: Callable[[Any], Any]
    update: Callable[..., Tuple[Any, Any]]
    refresh: Optional[Callable] = None


#: optimizer-state entries that belong to the in-flight half of the
#: double buffer (DESIGN.md §12): the pending preconditioners and their
#: telemetry twins.  Checkpoints may drop them (checkpoint.save(drop=))
#: — a restore then starts from a mark-stale state (discard_pending)
#: instead of ever consuming a half-written buffer.
PENDING_STATE_KEYS = frozenset({
    "ortho_p", "Linv_p", "Rinv_p",
    "iters_p", "Linv_iters_p", "Rinv_iters_p",
    "status_p", "Linv_status_p", "Rinv_status_p",
})

#: ``state["pending_at"]`` value meaning "no refresh in flight".  A large
#: negative sentinel rather than -1: the bootstrap dispatch BACK-DATES
#: ``pending_at = step - precond_swap_delay`` (possibly negative) so its
#: swap fires on the dispatching step itself, and that must stay
#: distinguishable from "none".
NO_PENDING = -(1 << 30)


def resolve_refresh_period(cfg, name: Optional[str] = None) -> int:
    """Effective preconditioner refresh period K for one optimizer.

    The single source of truth for the staleness clock (DESIGN.md §8/
    §12): Muon refreshes every ``precond_every`` steps; Shampoo honors
    its legacy ``precondition_every`` knob too, so its period is the max
    of the two.  ``name`` overrides ``cfg.name`` (for callers holding a
    config reused across optimizers).  Trainers, the async service and
    the optimizers themselves all derive the modulus from here.
    """
    name = cfg.name if name is None else name
    k = max(1, int(cfg.precond_every))
    if name == "shampoo":
        k = max(k, int(cfg.precondition_every))
    return k


# ----------------------------------------------------------- refresh plane

def _is_slot(x) -> bool:
    return isinstance(x, dict) and "mom" in x


def _flat_slots(leaves_tree):
    """Flatten a state["leaves"] tree up to its per-param slot dicts.

    Returns (slots, treedef); the slot order matches the flattened param
    order (the tree has the params' structure with a dict at every leaf
    position), so it aligns with the optimizers' flat gradient lists and
    with the partial-update lists an ``Optimizer.refresh`` returns.
    """
    treedef = jax.tree.structure(leaves_tree, is_leaf=_is_slot)
    return treedef.flatten_up_to(leaves_tree), treedef


def install_pending(state, partials, at_step: int):
    """Merge an ``Optimizer.refresh`` result into the state (§12).

    Pure Python pytree surgery — replaces leaf references, runs zero
    device compute, and in particular does NOT make subsequent steps'
    unchanged leaves depend on the refresh computation (the whole point
    of dispatching the chains asynchronously).  ``at_step`` stamps
    ``pending_at``: the update swaps pending -> active once
    ``count >= pending_at + precond_swap_delay``.
    """
    slots, treedef = _flat_slots(state["leaves"])
    merged = [dict(s, **p) if p else s for s, p in zip(slots, partials)]
    return dict(state, leaves=treedef.unflatten(merged),
                pending_at=jnp.asarray(at_step, jnp.int32))


def snapshot_overwritten_active(state, partials):
    """Per-slot snapshot of the ACTIVE (non-pending) keys a refresh
    result is about to overwrite (§15).

    ``install_pending`` merges the whole partial into the slot — the
    ``*_p`` twins stay inert until the swap, but active keys riding
    along (the ``rnorm``/``dnorm`` drift trackers, reset at dispatch)
    land immediately.  If the buffer later fails validation, the service
    restores this snapshot so a poisoned refresh leaves ZERO residue in
    the active plane (a NaN ``rnorm`` would silently disarm the drift
    trigger: NaN comparisons are False).  Pure reference capture — no
    device compute or copies."""
    slots, _ = _flat_slots(state["leaves"])
    return [{k: s[k] for k in p if k not in PENDING_STATE_KEYS and k in s}
            if p else None for s, p in zip(slots, partials)]


def discard_pending(state):
    """Mark any in-flight pending preconditioner stale (§12): a state
    restored mid-interval (checkpoint resume, elastic restart) must
    never swap in a buffer whose payload was dropped from the checkpoint
    or written by a run with a different schedule.  No-op for states
    without a refresh plane."""
    if not isinstance(state, dict) or "pending_at" not in state:
        return state
    return dict(state, pending_at=jnp.full((), NO_PENDING, jnp.int32))


def precond_drift(state) -> jax.Array:
    """Relative drift of the cached preconditioners since the last
    refresh dispatch (§12): max over slots of ``dnorm / rnorm``, where
    ``rnorm`` is the Frobenius norm of the matrix the cache was computed
    from and ``dnorm`` the accumulated per-step movement of that matrix.
    0 for states without drift tracking.  Cheap (a handful of scalars) —
    the trainer surfaces it in the step metrics and feeds it back to the
    AsyncPrecondService's trigger."""
    if not isinstance(state, dict) or "leaves" not in state:
        return jnp.zeros((), jnp.float32)
    slots, _ = _flat_slots(state["leaves"])
    ds = [s["dnorm"] / jnp.maximum(s["rnorm"], 1e-12)
          for s in slots if _is_slot(s) and "dnorm" in s]
    if not ds:
        return jnp.zeros((), jnp.float32)
    return jnp.max(jnp.stack(ds))


def _partials_finite(partials) -> jax.Array:
    """0-d bool: every float entry of a refresh result is finite.  A
    tiny jitted reduction dispatched ALONGSIDE the refresh chains (§15)
    — reading it later costs one scalar transfer, not a sync on the
    chains' GEMMs beyond what the swap itself would pay."""
    checks = [jnp.all(jnp.isfinite(l)) for l in jax.tree.leaves(partials)
              if jnp.issubdtype(l.dtype, jnp.floating)]
    if not checks:
        return jnp.asarray(True)
    return jnp.all(jnp.stack(checks))


class AsyncPrecondService:
    """Host-side scheduler of the double-buffered refresh plane (§12).

    Owns the Python half of the async contract: decides WHEN to dispatch
    a refresh (drift trigger with the fixed clock as ceiling), dispatches
    the jitted ``Optimizer.refresh`` without blocking, installs the
    pending buffers via ``install_pending``, and keeps the
    ``matfn_telemetry`` counters the trainer logs.

    Validated install (§15): every dispatch also enqueues a tiny
    finiteness reduction over the pending twins.  The verdict is read
    just before the swap would fire; a non-finite buffer is DISCARDED
    (``discard_pending`` — the poisoned twin is never swapped active)
    and the refresh re-dispatched with capped exponential backoff
    (1, 2, 4, ... steps, capped at the refresh period).  After
    ``cfg.precond_max_retries`` consecutive failures the slot degrades
    gracefully: the update keeps serving the last good ACTIVE buffer,
    the loud ``degraded`` counter increments, and only the next regular
    clock/drift trigger tries again.

    >>> svc.matfn_telemetry                      # doctest: +SKIP
    {'refreshes': 7, 'drift_triggered': 5, 'clock_triggered': 1,
     'bootstrap': 1, 'discarded': 0, 'retries': 0, 'degraded': 0,
     'last_drift': 0.013}
    """

    def __init__(self, opt: Optimizer, cfg, refresh_jit=None):
        assert opt.refresh is not None, \
            "optimizer has no refresh plane (AdamW?)"
        self.cfg = cfg
        self.period = resolve_refresh_period(cfg)
        self.swap_delay = int(cfg.precond_swap_delay)
        self.threshold = cfg.drift_threshold
        self.max_retries = int(getattr(cfg, "precond_max_retries", 3))
        self._refresh = refresh_jit if refresh_jit is not None \
            else jax.jit(opt.refresh)
        self._validate = jax.jit(_partials_finite)
        self._pending_check = None  # in-flight finiteness verdict
        self._overwritten = None  # active-key snapshot for clean discard
        self._retry_at: Optional[int] = None
        self.failures = 0  # consecutive validation failures
        self.last_dispatch: Optional[int] = None
        self.last_drift: float = 0.0
        self.counters = {"refreshes": 0, "drift_triggered": 0,
                         "clock_triggered": 0, "bootstrap": 0,
                         "discarded": 0, "retries": 0, "degraded": 0}

    def due(self, step: int, drift: float) -> Optional[str]:
        """None, or why a refresh should dispatch at ``step``."""
        if self.last_dispatch is None:
            return "bootstrap"
        if step <= self.last_dispatch + self.swap_delay:
            # previous refresh's swap has not run yet (it runs inside the
            # update of step last_dispatch + swap_delay): dispatching now
            # would overwrite a never-consumed pending buffer.  (A
            # discarded buffer's retry is scheduled past this window, so
            # backoff re-dispatches are never blocked here.)
            return None
        if self._retry_at is not None and step >= self._retry_at:
            return "retries"  # backoff re-dispatch after a discard
        if step - self.last_dispatch >= self.period:
            return "clock_triggered"  # the fixed-schedule ceiling
        if self.threshold is not None and drift >= self.threshold:
            return "drift_triggered"
        return None

    def _check_pending(self, state, step: int, force: bool = False):
        """Read the in-flight validation verdict once the swap is about
        to fire; discard + schedule a backoff retry on failure."""
        if self._pending_check is None or self.last_dispatch is None:
            return state
        if not force and step < self.last_dispatch + self.swap_delay:
            return state  # swap not due yet — keep the check in flight
        ok = bool(self._pending_check)
        self._pending_check = None
        if ok:
            self.failures = 0
            self._retry_at = None
            self._overwritten = None
            return state
        # poisoned twin: never swap it in, and roll back the active keys
        # (drift trackers) its install overwrote — zero residue
        if self._overwritten is not None:
            state = install_pending(state, self._overwritten, 0)
            self._overwritten = None
        state = discard_pending(state)
        self.counters["discarded"] += 1
        self.failures += 1
        if self.failures >= self.max_retries:
            # degrade: keep serving the last good active buffer; only
            # the next regular clock/drift trigger re-attempts
            self.counters["degraded"] += 1
            self._retry_at = None
        else:
            backoff = min(2 ** (self.failures - 1), self.period)
            self._retry_at = step + backoff
        return state

    def step_begin(self, state, step: int, key, drift: float = 0.0):
        """Phase 1 of the two-phase step loop: validate any in-flight
        pending buffer whose swap is due, then maybe dispatch a refresh.

        Non-blocking — the chains are enqueued and the pending buffers
        installed as futures; nothing here waits on device compute
        except the one-scalar validation verdict at swap time.  The
        bootstrap dispatch back-dates ``pending_at`` so its swap fires on
        this very step (the first step then waits on its own
        preconditioner, exactly like a blocking first step would) — its
        validation is therefore read immediately too.
        """
        self.last_drift = drift
        state = self._check_pending(state, step)
        reason = self.due(step, drift)
        if reason is None:
            return state
        partials = self._refresh(state, key)
        at = step - self.swap_delay if reason == "bootstrap" else step
        self._overwritten = snapshot_overwritten_active(state, partials)
        state = install_pending(state, partials, at)
        self._pending_check = self._validate(partials)
        self.last_dispatch = step
        self._retry_at = None
        self.counters["refreshes"] += 1
        self.counters[reason] += 1
        if reason == "bootstrap":
            # bootstrap swaps inside this very step's update: the
            # verdict must be read now, not next step
            state = self._check_pending(state, step, force=True)
        return state

    @property
    def matfn_telemetry(self) -> dict:
        return dict(self.counters, last_drift=self.last_drift)


def skip_nonfinite(opt: Optimizer, cfg=None) -> Optimizer:
    """§15 skip-step guard: gate the whole (params, state) write on ONE
    fused finiteness check over the gradients and the proposed params.

    A non-finite gradient (loss spike, bf16 overflow, a poisoned batch)
    would otherwise contaminate the momentum/EMA accumulators FOREVER —
    0 * NaN is NaN, so no later step washes it out.  The guard instead
    replays the step as an exact no-op: both params and the inner state
    roll back under a single ``lax.cond`` (a per-buffer select — zero
    extra matrix-function launches, the §12 steady-state contract is
    untouched), and only a ``bad_steps`` int32 counter at the state root
    advances.  ``count`` does NOT advance on a skipped step, so the
    staleness clock never serves a cache computed across a hole.

    Checking grads AND proposed params covers both poisoning paths:
    bad inputs (grads) and bad arithmetic on good inputs (an overflowing
    EMA factor surfaces as a non-finite update before it can land).

    Wrapped via ``make_optimizer`` when ``cfg.skip_nonfinite`` — off by
    default so existing state trees stay bit-identical.  The refresh
    plane passes through unchanged (it reads ``state["leaves"]`` only,
    and install/discard_pending preserve unknown root keys).
    """
    def init(params):
        return dict(opt.init(params), bad_steps=jnp.zeros((), jnp.int32))

    def update(grads, state, params, step, key, refresh=None):
        inner = {k: v for k, v in state.items() if k != "bad_steps"}
        new_p, new_s = opt.update(grads, inner, params, step, key,
                                  refresh=refresh)
        bad = sum(jnp.sum(~jnp.isfinite(l.astype(jnp.float32)))
                  for l in jax.tree.leaves(grads) + jax.tree.leaves(new_p))
        ok = bad == 0
        out_p, out_s = jax.lax.cond(ok,
                                    lambda: (new_p, new_s),
                                    lambda: (params, inner))
        return out_p, dict(out_s, bad_steps=state["bad_steps"]
                           + (~ok).astype(jnp.int32))

    return Optimizer(init, update, opt.refresh)


def global_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def clip_by_global_norm(grads, max_norm: float):
    """Scale grads so their global norm is at most ``max_norm``.

    Guarded (§15): a zero tree keeps scale 1 instead of dividing by
    zero, and a NON-FINITE global norm passes the gradients through
    UNSCALED — the naive ``max_norm / gn`` would turn one inf gradient
    entry into an all-zero (gn=inf => scale 0) or all-NaN step that the
    skip-step guard downstream could no longer distinguish from a real
    signal.  The raw (possibly non-finite) norm is still returned for
    telemetry."""
    gn = global_norm(grads)
    scale = jnp.where(jnp.isfinite(gn),
                      jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-12)),
                      1.0)
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale), grads), gn


def is_matrix_param(path_axes: tuple, shape: tuple,
                    allow_embed: bool = False) -> bool:
    """Muon applies to hidden weight matrices: >=2D, both matrix dims
    reasonably large, and not an embedding/vocab/codebook table.

    ``allow_embed`` lifts the table exclusion: with the §14 lowrank tier
    enabled (OptimizerConfig.lowrank_rank > 0) Muon claims vocab/codebook
    leaves too — the bucketing planner then routes any view too large or
    too rectangular for the cubic path through the sketched subspace
    chains instead of letting it fall back to scaled AdamW.
    """
    if not allow_embed and any(a in ("vocab", "codebooks")
                               for a in path_axes if a):
        return False
    dims = matrix_view_dims(path_axes, shape)
    if dims is None:
        return False
    m, n = dims
    return min(m, n) >= 16


# logical axes that mark a matrix view's rows, the first found winning:
# the model width, else MLA's latent (wkv_b [kv_lora, heads, head_dim])
ROW_AXES = ("embed", "kv_lora")


def _row_axis(names) -> Optional[str]:
    return next((a for a in ROW_AXES if a in names), None)


def matrix_view_dims(path_axes: tuple, shape: tuple) -> Optional[tuple]:
    """(rows, cols) of the Muon matrix view; None if not matrix-like.

    A ``ROW_AXES`` logical axis marks the contraction side: the matrix is
    (that dim) x (product of remaining non-batch dims).  Leading 'layers'
    / 'experts' axes are batch.  Without such a tag, the last two dims
    form the matrix (generic case).
    """
    axes = tuple(path_axes)
    batch = {"layers", "experts"}
    non_batch = [(i, a) for i, a in enumerate(axes) if a not in batch]
    if len(non_batch) < 2:
        return None
    idxs = [i for i, _ in non_batch]
    names = [a for _, a in non_batch]
    row = _row_axis(names)
    if row:
        e = idxs[names.index(row)]
        rest = [i for i in idxs if i != e]
        m = shape[e]
        n = 1
        for i in rest:
            n *= shape[i]
        return (m, n)
    m = shape[idxs[-2]]
    n = shape[idxs[-1]]
    for i in idxs[:-2]:
        m *= shape[i]
    return (m, n)


def to_matrix_view(p: jax.Array, path_axes: tuple) -> jax.Array:
    """Reshape p to [..batch.., m, n] with the ``ROW_AXES`` dim as the
    rows (possibly transposed into place).  Inverse via
    from_matrix_view."""
    axes = tuple(path_axes)
    batch = {"layers", "experts"}
    batch_idx = [i for i, a in enumerate(axes) if a in batch]
    other_idx = [i for i, a in enumerate(axes) if a not in batch]
    names = [axes[i] for i in other_idx]
    row = _row_axis(names)
    if row:
        e = other_idx[names.index(row)]
        rest = [i for i in other_idx if i != e]
        perm = batch_idx + [e] + rest
        q = jnp.transpose(p, perm)
        lead = tuple(p.shape[i] for i in batch_idx)
        m = p.shape[e]
        n = 1
        for i in rest:
            n *= p.shape[i]
        return q.reshape(lead + (m, n)), (perm, q.shape)
    lead = tuple(p.shape[i] for i in batch_idx)
    m = p.shape[other_idx[-2]] if len(other_idx) >= 2 else 1
    rest = tuple(p.shape[i] for i in other_idx)
    q = jnp.transpose(p, batch_idx + other_idx)
    mm = 1
    for d in rest[:-1]:
        mm *= d
    return q.reshape(lead + (mm, rest[-1])), \
        (batch_idx + other_idx, q.shape)


def from_matrix_view(q: jax.Array, meta) -> jax.Array:
    perm, mid_shape = meta
    q = q.reshape(mid_shape)
    inv = [0] * len(perm)
    for i, p in enumerate(perm):
        inv[p] = i
    return jnp.transpose(q, inv)
