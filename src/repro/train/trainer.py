"""Fault-tolerant training loop.

Features (DESIGN.md §4):
  * checkpoint/restart: atomic checkpoints every N steps (optionally via a
    background writer thread); on start, resumes from the newest complete
    checkpoint and re-shards it onto the *current* mesh (elastic scaling);
  * deterministic stateless data: batch_for_step(step) is pure, so resume
    replays the exact token stream with no iterator state;
  * straggler / hang detection: per-step wall times vs the median of
    the last ``STRAGGLER_WINDOW`` steps; steps slower than
    ``straggler_slack`` x median are flagged (on a real fleet this feeds
    the slow-host eviction hook) and a heartbeat file is touched every
    step for external watchdogs;
  * observability at no cost while the profiler is off: each step is a
    ``trainer.step`` host span whose children (``trainer.batch``,
    ``.precond``, ``.dispatch``, ``.sync``, ``.heartbeat``, ``.stats``,
    ``.callback``, ``.checkpoint``) tile the loop body, and
    ``counters["compiles_by_step"]`` records, for each step during which
    JAX compiled a program, how many it compiled, and
    ``counters["attention_path"]`` how many ``attend`` calls took each
    attention path, ``counters["moe_path"]`` how many expert layers took
    each dispatch path, and ``counters["polar_apply"]`` how many
    ``newton_schulz.apply_g`` calls took each evaluation order, when the
    step was last traced; the expert layers' held rows and load ratio
    ride in the step's metrics and are printed with the loss at
    ``log_every``;
  * multi-pod: the same code lowers under the production mesh — the
    launcher passes (mesh, shardings); on CPU tests mesh=None runs local.
"""
from __future__ import annotations

import collections
import os
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro.config import OptimizerConfig, TrainConfig
from repro.core import newton_schulz
from repro.data import DataConfig, make_batch_fn
from repro.models import attention, moe
from repro.models.transformer import Model
from repro.optim import base, make_optimizer
from repro.train import fault
from repro.train.state import (make_pipeline_train_step, make_train_step,
                               master_params)

# straggler detection compares a step with the median of this many
# recent steps: bounded work per step however long the run
STRAGGLER_WINDOW = 256
# the event JAX records around every backend compilation (a persistent
# cache hit included)
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = [0]  # backend compilations in this process so far


def _count_compile(event: str, duration: float, **kwargs) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        _compiles[0] += 1


jax.monitoring.register_event_duration_secs_listener(_count_compile)


class Trainer:
    def __init__(self, model: Model, ocfg: OptimizerConfig,
                 tcfg: TrainConfig, dcfg: DataConfig,
                 mesh=None, shardings: Optional[Dict[str, Any]] = None,
                 inject=None):
        self.model = model
        self.ocfg, self.tcfg, self.dcfg = ocfg, tcfg, dcfg
        self.mesh = mesh
        self.shardings = shardings
        self.opt = make_optimizer(ocfg, model.logical_axes())
        self.batch_fn = make_batch_fn(model.cfg, dcfg)
        self.pipelined = tcfg.pipeline_stages > 1
        if self.pipelined:
            # 1F1B over the pod axis (launch/pipeline.py): requires the
            # production-style mesh with pod == pipeline_stages
            assert mesh is not None and "pod" in mesh.axis_names, \
                "pipeline_stages > 1 needs a mesh with a pod axis"
            assert mesh.shape["pod"] == tcfg.pipeline_stages, \
                (mesh.shape, tcfg.pipeline_stages)
            step_fn = make_pipeline_train_step(model, self.opt, ocfg,
                                               mesh, tcfg.n_micro,
                                               inject=inject)
        else:
            step_fn = make_train_step(model, self.opt, ocfg, inject=inject)
        # the unjitted step stays reachable for trace-only observability:
        # ops.count_launches(raw_step_fn, ...) counts its Pallas launches
        self.raw_step_fn = step_fn
        # refresh (arg 4) is static: with precond_every=K>1 the loop picks
        # the refresh/skip step variant per step in Python (exact at step
        # 0), and the skip variant compiles with ZERO matrix-function
        # work.  K=1 passes None throughout — a single compiled step.
        if mesh is not None and shardings is not None:
            self.step_fn = jax.jit(
                step_fn,
                in_shardings=(shardings["params"], shardings["opt"],
                              shardings["batch"], None),
                out_shardings=(shardings["params"], shardings["opt"], None),
                donate_argnums=(0, 1), static_argnums=(4,))
        else:
            self.step_fn = jax.jit(step_fn, donate_argnums=(0, 1),
                                   static_argnums=(4,))
        # §12 async refresh plane: a host-side service owns WHEN to
        # dispatch the (separately jitted) refresh program; the step
        # itself then always runs the refresh=False variant — steady
        # state compiles with zero matrix-function launches.
        self.precond = (base.AsyncPrecondService(self.opt, ocfg)
                        if ocfg.precond_async else None)
        self._last_drift = 0.0
        self._ckpt_thread = None
        self.step_times = collections.deque(maxlen=STRAGGLER_WINDOW)
        self.straggler_events = 0
        self.counters: Dict[str, Any] = {"compiles_by_step": {}}

    @property
    def matfn_telemetry(self) -> Dict[str, Any]:
        """Refresh/drift counters of the async service ({} when sync)."""
        return {} if self.precond is None else self.precond.matfn_telemetry

    # ------------------------------------------------------------- state

    def init_state(self, seed: int = 0):
        params = master_params(self.model.init(jax.random.PRNGKey(seed)))
        opt_state = self.opt.init(params)
        return params, opt_state, 0

    def restore_or_init(self, seed: int = 0):
        cdir = self.tcfg.checkpoint_dir
        if ckpt.latest_step(cdir) is not None:
            params, opt_state, _ = self.init_state(seed)
            tree = {"params": params, "opt": opt_state}
            # pending buffers are dropped at save time (§12), so keep the
            # freshly initialized zeros for any key absent on disk...
            step, restored = ckpt.restore(
                cdir, tree, allow_missing=base.PENDING_STATE_KEYS)
            # ...and mark the refresh plane stale: a resumed run must
            # never swap in a buffer it did not dispatch itself
            opt_state = fault.discard_inflight(restored["opt"])
            print(f"[trainer] resumed from step {step}", flush=True)
            return restored["params"], opt_state, step
        return self.init_state(seed)

    def _checkpoint(self, step: int, params, opt_state):
        if self._ckpt_thread is not None:
            self._ckpt_thread.join()  # one in-flight write at a time
        self._ckpt_thread = ckpt.save(
            self.tcfg.checkpoint_dir, step,
            {"params": params, "opt": opt_state},
            keep=self.tcfg.keep_checkpoints,
            async_write=self.tcfg.async_checkpoint,
            # in-flight pending preconditioners are schedule-local state:
            # dropping them keeps checkpoints smaller and restore marks
            # the plane stale anyway (discard_inflight)
            drop=base.PENDING_STATE_KEYS)

    # ------------------------------------------------------------- loop

    def run(self, steps: Optional[int] = None, seed: int = 0,
            on_metrics: Optional[Callable] = None):
        steps = steps or self.tcfg.steps
        with jax.profiler.TraceAnnotation("trainer.restore_or_init"):
            params, opt_state, start = self.restore_or_init(seed)
        hb_path = os.path.join(self.tcfg.checkpoint_dir, "HEARTBEAT")
        os.makedirs(self.tcfg.checkpoint_dir, exist_ok=True)
        losses = []
        # effective staleness period (shared with the optimizers and the
        # async service via the single resolve_refresh_period helper)
        K = base.resolve_refresh_period(self.ocfg)
        for t in range(start, steps):
            compiles = _compiles[0]
            paths = attention.path_counts.copy()
            moe_paths = moe.path_counts.copy()
            applies = newton_schulz.path_counts.copy()
            with jax.profiler.StepTraceAnnotation("trainer.step",
                                                  step_num=t):
                params, opt_state, loss = self._step(
                    t, start, K, params, opt_state, hb_path, on_metrics)
            losses.append(loss)
            if _compiles[0] > compiles:
                self.counters["compiles_by_step"][t] = _compiles[0] - compiles
            traced = attention.path_counts - paths
            if traced:
                self.counters["attention_path"] = dict(traced)
            traced = moe.path_counts - moe_paths
            if traced:
                self.counters["moe_path"] = dict(traced)
            traced = newton_schulz.path_counts - applies
            if traced:
                self.counters["polar_apply"] = dict(traced)
        if self._ckpt_thread is not None:
            self._ckpt_thread.join()
        return params, opt_state, losses

    def _step(self, t: int, start: int, K: int, params, opt_state,
              hb_path: str, on_metrics: Optional[Callable]):
        """One iteration of ``run``'s loop, each part in its own host
        span; returns the new state and the step's loss."""
        span = jax.profiler.TraceAnnotation
        t0 = time.perf_counter()
        with span("trainer.batch"):
            batch = self.batch_fn(jnp.asarray(t))
        if self.precond is not None:
            # §12 two-phase step.  Phase 1: maybe dispatch a refresh
            # (non-blocking — the chains overlap the step below);
            # drift is read from the PREVIOUS step's metrics, so no
            # extra device sync here.  Phase 2: the step itself, with
            # refresh=False pinned statically — the only compiled
            # step variant, and it contains zero matfn launches.
            with span("trainer.precond"):
                opt_state = self.precond.step_begin(
                    opt_state, t,
                    jax.random.fold_in(jax.random.PRNGKey(1), t),
                    drift=self._last_drift)
                if self.shardings is not None and \
                        self.precond.last_dispatch == t:
                    # the refresh program's outputs carry compiler-chosen
                    # shardings; pin the freshly installed pending
                    # buffers back onto the step's expected layout (all
                    # other leaves already match -> no-copy)
                    opt_state = jax.device_put(opt_state,
                                               self.shardings["opt"])
            refresh = False
        else:
            refresh = (t % K == 0) if K > 1 else None
        with span("trainer.dispatch"):
            params, opt_state, metrics = self.step_fn(
                params, opt_state, batch, jnp.asarray(t, jnp.int32),
                refresh)
        with span("trainer.sync"):
            jax.block_until_ready(metrics["loss"])
            if self.precond is not None:
                self._last_drift = float(metrics["precond_drift"])
            loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        with span("trainer.heartbeat"):
            now = time.time()
            with open(hb_path, "w") as f:
                f.write(f"{t} {now}")
            if self.pipelined:
                # per-stage heartbeats: an external supervisor watching a
                # single stage (the unit that fails on a real fleet) gets
                # the same Watchdog-parseable "<step> <time>" contract
                for s in range(self.tcfg.pipeline_stages):
                    with open(f"{hb_path}.stage{s}", "w") as f:
                        f.write(f"{t} {now}")
        with span("trainer.stats"):
            if t > start:  # exclude compile step from straggler stats
                self.step_times.append(dt)
                med = float(np.median(self.step_times))
                if dt > self.tcfg.straggler_slack * med and \
                        len(self.step_times) > 5:
                    self.straggler_events += 1
                    print(f"[trainer] straggler: step {t} took {dt:.2f}s "
                          f"(median {med:.2f}s)", flush=True)
            if t % self.tcfg.log_every == 0:
                moe_stats = "".join(
                    f" {k} {float(metrics[k]):.4g}"
                    for k in ("moe_held_rows", "moe_load_max")
                    if k in metrics)
                print(f"[trainer] step {t} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f}"
                      f"{moe_stats} {dt:.2f}s", flush=True)
        if on_metrics is not None:
            with span("trainer.callback"):
                on_metrics(t, metrics)
        if self.tcfg.checkpoint_every and \
                (t + 1) % self.tcfg.checkpoint_every == 0:
            with span("trainer.checkpoint"):
                self._checkpoint(t + 1, params, opt_state)
        return params, opt_state, loss
