"""Training cells: the program's own training loop, timed over a window.

One run builds the trainer that ``launch/train.py::build_trainer``
returns and drives it with ``Trainer.run``: the weights and the optimizer
state come from the seed (one jitted call each), the token batches from
the benchmark's generator, handed in as the trainer's batch source.  The
first ``WARM_STEPS`` steps are set-up: they compile (or load) the step,
and the first three are the steps the reference checks (the trainer's
own step is wrapped for those three only, to take their readings).
Then the window runs for ``--seconds``; only steps whose loss reached
the host count.
With ``--trace 1`` the window is followed by ``TRACED_STEPS`` steps under
the profiler; the per-layer readers read that trace through the scopes
of the compiled step that ran, the program's host spans, and counts
taken on the step's shapes.  After the loop has ended and its state is
freed, the reference follows the same three steps and the run is judged
against it.  The reference is the configuration's model family's
(``reference/<family>.py``, found by ``harness.resolve``): the driver
knows no family by name.
"""
from __future__ import annotations

import functools
import math
import statistics
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from chipbench import harness, program, step_trace, trace, traffic_gen
from chipbench.reference import controls
from chipbench.reference import muon as ref_muon

WARM_STEPS = 4      # steps 0-2 are checked; step 3 runs on warm programs
CHECKED_STEPS = 3
TRACED_STEPS = 4
# a leaf whose reference gradient is below this share of the median
# leaf's moves by round-off alone (a key bias under softmax)
MOVING_SHARE = 1e-3
# rows of the batch the float32 reference differentiates at a time
REF_BLOCK_ROWS = 2


class _WindowClosed(Exception):
    pass


def leaf_norms(tree) -> Dict[str, jax.Array]:
    """Frobenius norm of every leaf, keyed by its path."""
    return {path: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for path, x in ref_muon.leaf_paths(tree).items()}


def _floats(d: Dict) -> Dict[str, float]:
    return {k: float(v) for k, v in d.items()}


class TrainCell:
    """The trainer of one cell and what it is checked against."""

    def __init__(self, cell: Dict, ckpt_dir: str):
        c, traffic = cell["config"], cell["traffic"]
        self.c, self.traffic = c, traffic
        self.ref = ref = harness.load_module(cell["reference"])
        self.trainer = program.trainer_for(c, traffic, ckpt_dir)
        self.tokens_per_step = traffic["seq"] * traffic["batch"]
        prog_shapes = self.trainer.model.param_shapes()
        ref_shapes = ref.param_shapes(c)
        self.hparams = dict(traffic["optimizer"])
        beta1 = self.hparams["beta1"]

        def weights(key):
            return program.to_program(ref.init(key, c), prog_shapes)

        self.weights = jax.jit(weights)
        self.opt_init = jax.jit(self.trainer.opt.init)
        self.grad_norms = jax.jit(lambda s: leaf_norms(program.from_program(
            program.gradients_from_state(s, beta1), ref_shapes)))
        self.delta_norms = jax.jit(lambda p, key: leaf_norms(
            program.from_program(jax.tree.map(jnp.subtract, p, weights(key)),
                                 ref_shapes)))
        self.muon = ref_muon.muon_leaves(ref_shapes, ref.muon_views(c))
        flat = ref_muon.leaf_paths(ref_shapes)
        self.muon_shapes = {path: flat[path] for path in self.muon}
        self.pick = jax.jit(lambda tree, layers: pick_layer(
            program.from_program(tree, ref_shapes), layers))
        self.pick_grads = jax.jit(lambda s, layers: pick_layer(
            program.from_program(program.gradients_from_state(s, beta1),
                                 ref_shapes), layers))
        self._step = self.trainer.step_fn

    def layers(self, key) -> Dict[str, int]:
        """The layer of each Muon leaf that the chain check reads."""
        layers = sampled_layers(key, self.muon_shapes)
        check_layers(layers, self.muon_shapes)
        return layers

    def step_shapes(self, tokens: jax.Array) -> Tuple:
        """The shapes of the trainer step's parameters, optimizer state
        and batch, as ``jax.eval_shape`` gives them."""
        params = jax.eval_shape(self.weights, jax.random.PRNGKey(0))
        return (params, jax.eval_shape(self.opt_init, params),
                {"tokens": jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)})

    # ---------------------------------------------------------- program

    def _checked_step(self, params, opt_state, batch, step, refresh):
        """The trainer's own compiled step; after the first call it also
        takes the first gradient's norms and one layer of each Muon
        leaf's parameters and gradient, after the third the norms of the
        change, and then hands the trainer its own step back."""
        params, opt_state, metrics = self._step(params, opt_state, batch,
                                                step, refresh)
        n = self._calls
        self._calls += 1
        if n == 0:
            self._got["grad"] = self.grad_norms(opt_state)
            self._got["samples"]["p1"] = jax.device_get(
                self.pick(params, self._layers))
            self._got["samples"]["g"] = jax.device_get(
                self.pick_grads(opt_state, self._layers))
        elif n == CHECKED_STEPS - 1:
            self._got["delta"] = self.delta_norms(params, self._wkey)
            self.trainer.step_fn = self._step
        return params, opt_state, metrics

    def drive(self, key, batches: List[jax.Array],
              on_step: Callable[[int, float], None], steps: int = 1 << 40
              ) -> Dict:
        """Run the trainer from weights made from ``key`` until
        ``on_step(t, loss)`` raises ``_WindowClosed`` or ``steps`` steps
        have run.  Returns the checked readings of the first steps."""
        wkey = jax.random.fold_in(key, 0)
        layers = self.layers(key)
        first = [self.weights(wkey)]
        first.append(self.opt_init(first[0]))
        self._got = {"samples": {"p0": jax.device_get(
            self.pick(first[0], layers))}}

        def init_state(seed):
            params, state = first
            first.clear()  # the step donates them; hold no reference
            return params, state, 0

        self.trainer.init_state = init_state
        served = [0]

        def batch_fn(t):
            with jax.profiler.TraceAnnotation("chipbench.batch"):
                served[0] += 1
                return {"tokens": batches[(served[0] - 1) % len(batches)]}

        self.trainer.batch_fn = batch_fn
        self.trainer.step_fn = self._checked_step
        self._calls, self._wkey, self._layers = 0, wkey, layers
        losses: List[float] = []

        def on_metrics(t, metrics):
            with jax.profiler.TraceAnnotation("chipbench.callback"):
                loss = float(metrics["loss"])
                if t < CHECKED_STEPS:
                    losses.append(loss)
                on_step(t, loss)

        try:
            self.trainer.run(steps=steps, on_metrics=on_metrics)
        except _WindowClosed:
            pass
        finally:
            del self.trainer.init_state
            self.trainer.step_fn = self._step
        return {"loss": losses, "grad": _floats(self._got["grad"]),
                "delta": _floats(self._got["delta"]),
                "samples": self._got["samples"]}

    # -------------------------------------------------------- reference

    def reference(self, key, batches: List[jax.Array],
                  q8: Callable = controls.identity,
                  chain_q: Callable = controls.identity,
                  half_batch: bool = False) -> Dict:
        """The reference's readings of the first ``CHECKED_STEPS`` steps
        from the same weights and batches, with the same layers sampled
        as the program's.  ``q8`` (the model's products), ``chain_q``
        (the polar chain's products) and ``half_batch`` plant the
        control's precision or a fault in its place."""
        c, h, ref, muon = self.c, self.hparams, self.ref, self.muon
        wkey = jax.random.fold_in(key, 0)
        layers = self.layers(key)

        def step(p, s, tok, layers):
            if half_batch:
                tok = tok[: tok.shape[0] // 2]
            loss, g = ref.loss_and_grad(
                p, tok, c, q8, min(REF_BLOCK_ROWS, tok.shape[0]))
            p0 = pick_layer(p, layers)
            with jax.default_matmul_precision("highest"):
                p, s, used = ref_muon.update(g, s, p, h, muon, chain_q)
            samples = {"p0": p0, "p1": pick_layer(p, layers),
                       "g": pick_layer(used, layers)}
            return p, s, loss, leaf_norms(used), samples

        step = jax.jit(step, donate_argnums=(0, 1))
        p = jax.jit(lambda k: ref.init(k, c))(wkey)
        s = jax.jit(lambda p: ref_muon.init_state(p, muon))(p)
        losses, grad, samples = [], None, None
        for t in range(CHECKED_STEPS):
            p, s, loss, gn, sm = step(p, s, batches[t % len(batches)],
                                      layers)
            losses.append(float(loss))
            if t == 0:
                grad, samples = _floats(gn), jax.device_get(sm)
        delta = jax.jit(lambda p, k: leaf_norms(jax.tree.map(
            jnp.subtract, p, ref.init(k, c))))(p, wkey)
        return {"loss": losses, "grad": grad, "delta": _floats(delta),
                "samples": samples}

    def chain_gaps(self, samples: Dict) -> Dict[str, float]:
        return chain_gaps(samples, self.muon, self.hparams)


def sampled_layers(key, shapes: Dict[str, tuple]) -> Dict[str, int]:
    """The layer of each leaf (path -> shape, the layer axis first) whose
    matrices the chain check reads, drawn from the seed within that
    leaf's own layer axis: leaves of one depth share their draw."""
    k = jax.random.fold_in(key, 2)
    by_depth = {n: int(jax.random.randint(k, (), 0, n))
                for n in sorted({s[0] for s in shapes.values()})}
    return {path: by_depth[s[0]] for path, s in shapes.items()}


def check_layers(layers: Dict[str, int], shapes: Dict[str, tuple]) -> None:
    """Raise unless every index lies on its leaf's layer axis: a slice
    on the device would clamp it and read another layer."""
    for path, i in layers.items():
        if not 0 <= i < shapes[path][0]:
            raise IndexError(f"layer {i} of {path} outside its "
                             f"{shapes[path][0]} layers")


def pick_layer(tree: Dict, layers: Dict[str, int]) -> Dict:
    """One layer (kept as a leading axis of 1) of each leaf in
    ``layers`` (path -> index, checked by ``check_layers``)."""
    flat = ref_muon.leaf_paths(tree)
    return {path: jax.lax.dynamic_slice_in_dim(
        flat[path], i, 1, 0).astype(jnp.float32)
        for path, i in layers.items()}


def chain_gaps(samples: Dict, views: Dict, h: Dict) -> Dict[str, float]:
    """For each Muon leaf of ``views``, by its sampled layer: the gap
    between the parameters' first change and the one the reference's
    polar chain makes from the same gradient, over the latter's norm."""
    lr, wd, mu = h["learning_rate"], h["weight_decay"], h["momentum"]
    prism = tuple(sorted(h["prism"].items()))
    out = {}
    for path, view in views.items():
        p0, p1, g = (jnp.asarray(samples[k][path])
                     for k in ("p0", "p1", "g"))
        out[path] = float(_chain_gap(view, p0, p1, g, lr, wd, mu, prism))
    return out


@functools.partial(jax.jit, static_argnums=(0, 7))
def _chain_gap(view, p0, p1, g, lr, wd, mu, prism_items):
    prism = dict(prism_items)
    u = g + mu * g  # the first step: momentum is the gradient itself
    want = -lr * (wd * p0 + ref_muon.direction(view, u, prism))
    return (jnp.sqrt(jnp.sum(jnp.square((p1 - p0) - want)))
            / jnp.maximum(jnp.sqrt(jnp.sum(jnp.square(want))), 1e-30))


def compare(got: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers the check compares, each a gap of ``got`` from
    ``ref``: the largest relative loss gap of the checked steps, and, by
    the worst leaf, the gap of the first gradient's norm and of the
    norm of the parameters' change after the checked steps, each against
    the larger of that leaf's reference norm and the median leaf's.
    Leaves whose reference gradient is below ``MOVING_SHARE`` of the
    median leaf's are left out of the change.  ``chain_gap`` is the
    worst of ``got["chain"]`` (``TrainCell.chain_gaps``)."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(got["loss"],
                                                       ref["loss"]))
    med_g = statistics.median(ref["grad"].values())
    grad_gap = max(abs(got["grad"][k] - g) / max(g, med_g)
                   for k, g in ref["grad"].items())
    moving = [k for k, g in ref["grad"].items() if g >= MOVING_SHARE * med_g]
    med_d = statistics.median(ref["delta"][k] for k in moving)
    update_gap = max(abs(got["delta"][k] - ref["delta"][k])
                     / max(ref["delta"][k], med_d) for k in moving)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "update_gap": update_gap,
            "chain_gap": max(got["chain"].values())}


# ------------------------------------------------------------ readers


def layer_context(tc: TrainCell, rec: Dict, window_ns: Tuple[float, float],
                  steps_per_s: float, peaks: Optional[Dict], chips: int,
                  shapes: Tuple) -> Dict:
    """What the per-layer readers read: the traced window's record (with
    the program's scopes and host spans, ``step_trace.extract``), the
    untraced window's rate, the step's FLOPs by the family's reference,
    and, on the step's ``shapes`` (``TrainCell.step_shapes``), its
    Pallas launches and polar chains."""
    params, state, batch = shapes
    return dict(program.polar_plan(tc.trainer, params), trace=rec,
                window_ns=window_ns, peaks=peaks, chips=chips,
                steps_per_s=steps_per_s,
                step_flops=tc.ref.train_step_flops(
                    tc.c, tc.traffic["seq"], tc.traffic["batch"]),
                matfn_launches=program.step_launches(
                    tc.trainer, params, state, batch))


# ---------------------------------------------------------------- run


def run(cell: Dict, *, seed: int, seconds: float, traced: bool, t0: float,
        devs, peaks: Optional[Dict]) -> Tuple[Dict, Dict]:
    """One run of a training cell.  Returns the result line's fields
    (without ``checks``) and the checks."""
    key = jax.random.PRNGKey(seed)
    marks = {"imports": time.perf_counter()}
    with tempfile.TemporaryDirectory() as tmp:
        tc = TrainCell(cell, tmp)
        marks["trainer"] = time.perf_counter()
        batches = traffic_gen.batches(jax.random.fold_in(key, 1),
                                      tc.traffic, tc.c["vocab_size"])
        jax.block_until_ready(batches)
        marks["batches"] = time.perf_counter()
        clock = {"start": None, "end": None, "n": 0, "bad": 0,
                 "traced": 0, "ends": []}

        def on_step(t, loss):
            now = time.perf_counter()
            if t == 0:
                marks["step 0"] = now
            if t < WARM_STEPS - 1:
                return
            if t == WARM_STEPS - 1:
                clock["start"] = now
                return
            if clock["end"] is None:
                clock["n"] += 1
                clock["ends"].append(now)
                clock["bad"] += not math.isfinite(loss)
                if now - clock["start"] >= seconds:
                    clock["end"] = now
                    if not traced:
                        raise _WindowClosed
                    jax.profiler.start_trace(tmp + "/steps")
                    clock["span"] = jax.profiler.TraceAnnotation(
                        "chipbench.window")
                    clock["span"].__enter__()
                return
            clock["traced"] += 1
            if clock["traced"] == TRACED_STEPS:
                clock["span"].__exit__(None, None, None)
                jax.profiler.stop_trace()
                raise _WindowClosed

        got = tc.drive(key, batches, on_step)
        peak = harness.memory_peak(devs)
        window = clock["end"] - clock["start"]
        result = {"attempted": clock["n"], "failed": clock["bad"],
                  "device": harness.device_record(devs, peak)}
        if traced:
            rec = trace.extract(tmp + "/steps")
            lo, hi = trace.span_window(rec, "chipbench.window")
            ops = rec["devices"][0]
            busy = [trace.busy_ns(o, lo, hi)
                    for o in rec["devices"][:len(devs)]]
            shapes = tc.step_shapes(batches[0])
            rec.update(step_trace.extract(
                tmp + "/steps", program.step_text(tc.trainer, *shapes)))
            ctx = layer_context(tc, rec, (lo, hi), clock["n"] / window,
                                peaks, len(devs), shapes)
            metrics = {}
            for m in cell["per_layer"]:
                reader = harness.load_module(cell["readers"][m["name"]])
                value = reader.read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            compiles = sum(n for t, n in
                           tc.trainer.counters["compiles_by_step"].items()
                           if t >= WARM_STEPS)
            print(step_trace.summary(rec, lo, hi, len(devs), compiles),
                  file=sys.stderr)
            result["device"].update(
                busy_s=sum(busy) / len(busy) * 1e-9,
                window_s=(hi - lo) * 1e-9)
            result["breakdown"] = {
                "device_ops": trace.top_ops(ops, lo, hi),
                "idle_gaps": trace.labelled_gaps(ops, rec["spans"], lo, hi)}
        else:
            metrics = {
                "train_tokens_per_s": {
                    "value": clock["n"] * tc.tokens_per_step / window,
                    "unit": "tokens/s"},
                "peak_hbm_gib": {"value": peak / 2 ** 30, "unit": "GiB"},
                "setup_s": {"value": clock["start"] - t0, "unit": "s"}}
        result["metrics"] = metrics
        marks["steps 1-3"] = clock["start"]
        last, phases = t0, []
        for name, at in marks.items():
            phases.append(f"{name} {at - last:.3f}")
            last = at
        ends = [clock["start"]] + clock["ends"]
        step_ms = sorted((b - a) * 1e3 for a, b in zip(ends, ends[1:]))
        print(f"window: {clock['n']} steps in {window:.6f} s (step ms min "
              f"{step_ms[0]:.3f}, median {statistics.median(step_ms):.3f}, "
              f"max {step_ms[-1]:.3f}), set-up {clock['start'] - t0:.6f} s "
              f"({', '.join(phases)})", file=sys.stderr)
        tr0 = time.perf_counter()
        ref = tc.reference(key, batches)
        got["chain"] = tc.chain_gaps(got["samples"])
        numbers = compare(got, ref)
        print(f"reference: {time.perf_counter() - tr0:.3f} s; losses "
              f"program {got['loss']} reference {ref['loss']}",
              file=sys.stderr)
    checks = {k: {"value": v, "limit": cell["limits"][k]}
              for k, v in numbers.items()}
    result["correct"] = harness.judge(checks)
    return result, checks
