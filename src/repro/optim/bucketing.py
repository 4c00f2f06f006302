"""Shape-bucketed batched matrix-function engine (DESIGN.md §7).

Muon/Shampoo call ``matfn.polar`` / inverse roots once per parameter
matrix; a transformer with L distinct weight matrices therefore compiles L
independent unrolled Newton-Schulz chains and launches every kernel L
times per step.  This module collapses that dispatch layer:

  1. ``plan_buckets`` partitions the matrix views of a param tree into
     shape buckets — exact-shape groups, plus (optionally) near-miss
     shapes merged into a shared padded bucket when the area overhead
     stays under a slack bound;
  2. ``gather_bucket`` stacks each bucket into ONE [B, m, n] array
     (leading scanned-layer dims of a view flatten into B, near-miss
     shapes zero-pad to the bucket shape);
  3. one batched PRISM call runs per bucket — a single residual, a single
     shared-sketch alpha fit broadcast over B, and a constant number of
     batch-grid Pallas launches per iteration, independent of B and of
     the sketch chain length;
  4. ``scatter_bucket`` splits, un-pads and reshapes the results back.

Zero-padding is exact for the Newton-Schulz polar iterations (pad
rows/cols of X stay identically zero; the real block evolves as if
unpadded), and the sketched alpha fit is made exactly pad-blind via the
``n_real`` trace correction in ``prism.fit_alpha``.  Padding is NOT used
for the SVD method (null-space rotations can leak into the real block) or
for the coupled sqrtm family (the damped pad block perturbs the fit), so
those paths bucket exact shapes only.

The plan is pure Python over static shapes — it runs at trace time and
costs nothing inside jit.

Adaptive early stopping (DESIGN.md §11): with a resolved ``tol`` the
batched call per bucket runs only until its slowest slice certifies;
``polar_bucketed(with_iters=True)`` / ``transform_bucketed(
with_aux=True)`` scatter the realized per-slice iteration counts back
per view for the optimizers' telemetry state.

Mesh-sharded dispatch (DESIGN.md §8): a batched bucket call is exact
per-slice math — per-slice Frobenius normalization and a per-slice alpha
fit against a sketch S shared only through the PRNG key — so the batch
dim partitions freely across devices.  When an activation-sharding
context is installed (launcher, multi-device tests) each bucket's
[B, m, n] batch dim is shard_map'ed over the (pod, data) mesh axes:
every device runs the fitted chain on B/shards matrices instead of all
B replicated, and the slice results are all-gathered back into the full
bucket before ``scatter_bucket``.  Buckets whose B does not divide the
shard count pad with identity slices (finite, self-contained chains
that are dropped after the gather).
"""
from __future__ import annotations

from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import sharding_ctx
from repro.config import OptimizerConfig
from repro.core import matfn
from repro.scopes import scoped


class Entry(NamedTuple):
    """One matrix view's slot inside a bucket."""

    index: int                  # position in the caller's list of views
    lead: Tuple[int, ...]       # leading (stacked-layer) dims of the view
    mshape: Tuple[int, int]     # real matrix shape (m, n)
    offset: int                 # first slice in the bucket's batch dim

    @property
    def count(self) -> int:
        c = 1
        for d in self.lead:
            c *= d
        return c


class Bucket(NamedTuple):
    shape: Tuple[int, int]      # bucket (possibly padded-to) matrix shape
    entries: Tuple[Entry, ...]
    size: int                   # total stacked batch B

    @property
    def padded(self) -> bool:
        return any(e.mshape != self.shape for e in self.entries)


def plan_buckets(shapes: Sequence[Tuple[int, ...]], *, pad: bool = False,
                 pad_slack: float = 0.25) -> Tuple[Bucket, ...]:
    """Partition view shapes [..lead.., m, n] into shape buckets.

    Exact (m, n) groups never mix orientations — (m, n) and (n, m) are
    distinct buckets.  With ``pad``, a shape joins an existing larger
    bucket target (M, N) when padding is needed ONLY on the target's
    Gram side (the min side, where polar forms its residual: cols when
    M >= N, else rows) and the padded area stays within
    M*N <= (1 + pad_slack) * m*n; targets are seeded from the largest
    shapes first so the merge is deterministic.  Gram-side-only padding
    keeps the residual's pad block coordinate-aligned (exactly I), which
    is what the n_real trace correction subtracts exactly; padding the
    other side would instead inject non-aligned rank-deficiency modes
    into the fit — analytically argmin-invariant (h(1; a) = 1) but only
    fp-approximately so near convergence — so such merges are refused.
    """
    mshapes = [(int(s[-2]), int(s[-1])) for s in shapes]
    distinct = sorted(set(mshapes), key=lambda s: (-s[0] * s[1], s))
    target = {}
    targets: List[Tuple[int, int]] = []
    for m, n in distinct:
        tgt = (m, n)
        if pad:
            for M, N in targets:
                fits = (m == M and n <= N) if M >= N else \
                    (n == N and m <= M)
                if fits and M * N <= (1 + pad_slack) * m * n:
                    tgt = (M, N)
                    break
        target[(m, n)] = tgt
        if tgt == (m, n):
            targets.append(tgt)
    groups = {}
    for i, s in enumerate(shapes):
        groups.setdefault(target[mshapes[i]], []).append(i)
    buckets = []
    for tgt in sorted(groups):
        entries, offset = [], 0
        for i in groups[tgt]:
            e = Entry(i, tuple(int(d) for d in shapes[i][:-2]),
                      mshapes[i], offset)
            entries.append(e)
            offset += e.count
        buckets.append(Bucket(tgt, tuple(entries), offset))
    return tuple(buckets)


def gather_bucket(bucket: Bucket, views: Sequence[jax.Array],
                  dtype=None) -> jax.Array:
    """Stack a bucket's views into one [B, M, N] array (zero-padded).

    ``dtype`` casts each view BEFORE stacking (DESIGN.md §9): under a
    bf16 compute policy the gathered bucket — the array every chain GEMM
    streams from HBM — is materialized directly in bf16, halving the
    gather/concat footprint instead of stacking fp32 and casting inside
    the matfn call.  Zero padding is exact in any dtype.
    """
    M, N = bucket.shape
    parts = []
    for e in bucket.entries:
        v = views[e.index]
        if dtype is not None and v.dtype != dtype:
            v = v.astype(dtype)
        v = v.reshape((e.count,) + e.mshape)
        pm, pn = M - e.mshape[0], N - e.mshape[1]
        if pm or pn:
            v = jnp.pad(v, ((0, 0), (0, pm), (0, pn)))
        parts.append(v)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def scatter_bucket(bucket: Bucket, batch: jax.Array,
                   outs: List[Optional[jax.Array]]) -> None:
    """Split [B, M, N] results back into per-view arrays (in place)."""
    for e in bucket.entries:
        m, n = e.mshape
        sl = batch[e.offset:e.offset + e.count, :m, :n]
        outs[e.index] = sl.reshape(e.lead + e.mshape)


def scatter_bucket_aux(bucket: Bucket, aux: jax.Array,
                       outs: List[Optional[jax.Array]]) -> None:
    """Split a per-slice companion [B, ...] (e.g. the §11 ``iters_used``
    telemetry) back into per-view arrays of the views' lead shapes."""
    for e in bucket.entries:
        sl = aux[e.offset:e.offset + e.count]
        outs[e.index] = sl.reshape(e.lead + sl.shape[1:])


def _gram_real_dims(bucket: Bucket) -> jax.Array:
    """Per-slice real extent of the polar Gram dimension, shape [B].

    ``newton_schulz.polar`` transposes when M < N, so the Gram residual
    lives on the min side of the BUCKET shape; each slice's real extent on
    that side feeds the n_real trace correction.
    """
    M, N = bucket.shape
    side = 1 if M >= N else 0
    reals = []
    for e in bucket.entries:
        reals.extend([e.mshape[side]] * e.count)
    return jnp.asarray(reals, jnp.int32)


def resolve_lowrank_tier(cfg: OptimizerConfig,
                         mshape: Tuple[int, int]) -> Optional[int]:
    """Sketch width l when a bucket routes the §14 lowrank tier, None for
    the cubic (§7/§10) tiers.

    Like the fused tier's ``ops.fused_fits`` this is a trace-time,
    batch-size-blind choice from the bucket's static matrix shape.  The
    tier engages when (a) it is enabled (``cfg.lowrank_rank > 0``) and
    the view's shape crosses the size threshold — max dim above
    ``lowrank_max_dim`` or aspect ratio at least ``lowrank_aspect`` —
    and (b) projection actually wins: l = rank + oversample leaves a
    strict subspace (l < min(m, n)) and the modeled projected-chain
    FLOPs beat the cubic path (kernels/ops.py), so pathological knob
    choices degrade to the exact tiers instead of a slower
    "acceleration".
    """
    if not cfg.lowrank_rank:
        return None
    if cfg.matfn_method not in ("prism", "newton_schulz"):
        return None
    m, n = int(mshape[-2]), int(mshape[-1])
    hi, lo = max(m, n), min(m, n)
    if hi <= cfg.lowrank_max_dim and hi < cfg.lowrank_aspect * lo:
        return None
    l = cfg.lowrank_rank + cfg.lowrank_oversample
    if l >= lo:
        return None
    from repro.kernels import ops as kops

    pcfg = cfg.resolved_prism
    it = pcfg.iterations + pcfg.warm_alpha_iters
    if kops.lowrank_polar_flops((hi, lo), l, iters=it,
                                degree=pcfg.degree) >= \
            kops.polar_flops((hi, lo), iters=it, degree=pcfg.degree):
        return None
    return l


#: telemetry encoding of the per-bucket kernel tier (the int32 "tier"
#: entry Muon carries per matrix leaf when the §14 tier is enabled)
TIER_CODES = {"grid": 0, "fused": 1, "lowrank": 2}


def resolve_tier(cfg: OptimizerConfig, mshape: Tuple[int, int]) -> str:
    """Name of the kernel tier the planner picks for a view shape:
    "lowrank" (§14) | "fused" (§10) | "grid" (§7).  Pure static-shape
    logic — usable from tests/telemetry without building a Bucket."""
    if resolve_lowrank_tier(cfg, mshape) is not None:
        return "lowrank"
    from repro.kernels import ops as kops

    m, n = int(mshape[-2]), int(mshape[-1])
    fits = kops.fused_fits((max(m, n), min(m, n)),  # polar: m >= n
                           jnp.dtype(cfg.resolved_prism.dtype))
    return "fused" if fits else "grid"


# ------------------------------------------------------------------ sharding

def mesh_batch_axes(cfg: Optional[OptimizerConfig]):
    """(mesh, axes) for batch-dim sharding, or (None, ()) when inactive.

    Active iff ``cfg.precond_shard == "auto"``, an activation-sharding
    context is installed, and the mesh has a >1-sized batch axis.  Only
    the pure data-parallel axes partition the bucket — the model axis
    keeps its role of sharding the matrices themselves (TP), so each
    model-slice group computes the same batch slice redundantly, exactly
    as the forward pass replicates data-parallel work across model.
    """
    if cfg is None or getattr(cfg, "precond_shard", "off") != "auto":
        return None, ()
    ctx = sharding_ctx.current()
    if ctx is None:
        return None, ()
    mesh, rules = ctx
    # pipeline runs repurpose pod as a stage axis and install an
    # "opt_batch" override (launch/sharding.py::pipeline_rules) so the
    # bucket batch dim partitions over the remaining DP axes only
    allowed = rules.get("opt_batch", ("pod", "data"))
    axes = tuple(a for a in allowed
                 if a in mesh.axis_names and mesh.shape[a] > 1)
    return (mesh, axes) if axes else (None, ())


def shard_over_batch(fn: Callable, mesh, axes: Tuple[str, ...],
                     stacked: jax.Array,
                     slice_args: Sequence[jax.Array] = (),
                     slice_pads: Sequence = (),
                     out_ranks: Optional[Tuple[int, ...]] = None):
    """Run ``fn(stacked, *slice_args)`` with the leading batch dim
    partitioned over mesh ``axes`` via shard_map; all-gather the result.

    ``slice_args`` are per-slice companions ([B, ...], e.g. the n_real
    trace-correction vector) that shard with the batch; ``slice_pads``
    gives the fill value appended to each when B pads up to a multiple of
    the shard count.  Batch padding uses identity slices: every PRISM/NS
    path normalizes and fits per slice, so pad slices run finite,
    self-contained chains that cannot perturb the real ones and are
    sliced away after the gather.

    ``out_ranks``: when fn returns a TUPLE of batch-leading arrays (the
    §11 telemetry path returns (O [B, M, N], iters_used [B])), gives each
    output's rank so the shard_map out_specs can be built; every output
    is all-gathered over the batch dim and un-padded.  None (default)
    keeps the single-array contract.  Note the §11 while_loops run
    PER-SHARD under this partitioning: each device iterates only until
    its own slowest slice certifies — adaptivity composes with §8
    sharding for free.
    """
    from jax.sharding import PartitionSpec as P

    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    B, M, N = stacked.shape
    pad = (-B) % n_shards
    if pad:
        eye = jnp.broadcast_to(jnp.eye(M, N, dtype=stacked.dtype),
                               (pad, M, N))
        stacked = jnp.concatenate([stacked, eye], axis=0)
        slice_args = [
            jnp.concatenate([s, jnp.full((pad,) + s.shape[1:], v,
                                         dtype=s.dtype)], axis=0)
            for s, v in zip(slice_args, slice_pads)]
    ax = axes if len(axes) > 1 else axes[0]

    def local(x, *extras):
        return jax.tree.map(
            lambda o: jax.lax.all_gather(o, ax, axis=0, tiled=True),
            fn(x, *extras))

    def batch_spec(r):
        return P(*((ax,) + (None,) * (r - 1)))

    out_specs = (P(*((None,) * stacked.ndim)) if out_ranks is None else
                 tuple(P(*((None,) * r)) for r in out_ranks))
    # replication checking off: the all-gathered chain outputs are
    # replicated, which the checker cannot always infer
    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=tuple(batch_spec(a.ndim)
                       for a in [stacked, *slice_args]),
        out_specs=out_specs, check_vma=False)(stacked, *slice_args)
    return jax.tree.map(lambda o: o[:B], out) if pad else out


@scoped("polar")
def polar_bucketed(views: Sequence[jax.Array], cfg: OptimizerConfig,
                   key: Optional[jax.Array],
                   with_iters: bool = False):
    """Polar factor of every matrix view via one batched call per bucket.

    Buckets gather directly in the engine's compute dtype
    (``cfg.matfn_dtype`` via the resolved MatfnPrecision policy) — the
    SVD method excepted, whose LAPACK path is pinned fp32 (DESIGN.md §9).

    ``with_iters`` (NS family only, i.e. method prism/newton_schulz)
    additionally returns per-view ``iters_used`` AND guardian ``status``
    telemetry (DESIGN.md §11/§15): the realized iteration count and the
    int8 prism.STATUS_* code of every slice, scattered back to each
    view's lead shape — returns (outs, iters, statuses).  With ``cfg``'s
    resolved ``tol`` set the counts are data-dependent (each bucket's
    while_loop exits when its slowest slice certifies); otherwise they
    are the static budget and the statuses all-zeros.
    """
    method = cfg.matfn_method
    pcfg = cfg.resolved_prism
    compute = None if method == "svd" else \
        cfg.matfn_precision.compute_dtype
    pad = cfg.bucket_pad and method != "svd"
    if with_iters:
        assert method in ("prism", "newton_schulz"), method
    buckets = plan_buckets([v.shape for v in views], pad=pad,
                           pad_slack=cfg.bucket_pad_slack)
    mesh, mesh_axes = mesh_batch_axes(cfg)
    outs: List[Optional[jax.Array]] = [None] * len(views)
    iters: List[Optional[jax.Array]] = [None] * len(views)
    statuses: List[Optional[jax.Array]] = [None] * len(views)
    for bi, b in enumerate(buckets):
        # one scope per bucket around its gather, chain and scatter
        with jax.named_scope("bucket_%dx%dx%d" % ((b.size,) + b.shape)):
            stacked = gather_bucket(b, views, dtype=compute)
            local_reshard = (cfg.muon_local_reshard
                             and all(e.lead for e in b.entries))
            if local_reshard:
                # layers -> model, rows -> data (see make_muon): the batched NS
                # iterations then need only one [n, n] R-psum per step.  Like
                # the per-leaf path (which resharded only M.ndim >= 3 views),
                # this applies only to buckets built purely from scanned-layer
                # stacks — plain 2-D leaves keep their layout, and a mixed
                # bucket is not co-sharded unevenly over opt_layers.  Takes
                # precedence over the batch-dim shard_map engine: the two are
                # alternative distribution strategies for the same bucket.
                stacked = sharding_ctx.shard_activation(
                    stacked, ("opt_layers", "opt_rows", None))
            kk = (jax.random.fold_in(key, bi) if key is not None else None)
            lowrank_l = resolve_lowrank_tier(cfg, b.shape)
            if lowrank_l is not None:
                # §14 lowrank tier: the projected chains live on the l side,
                # which is never padded (plan_buckets pads the Gram side of
                # the FULL view only, and zero pad rows/cols stay zero
                # through the sketch -> project -> lift composition), so no
                # n_real correction is threaded.  The §10 tier choice
                # resolves inside the inner polar calls from the SMALL
                # [m, l] / [l, n] shapes (ops.fused_fits), not from the
                # full bucket shape.
                from repro.core import lowrank as lr

                def run(x, _kk=kk, _l=lowrank_l):
                    return lr.polar_lowrank(
                        x, cfg.lowrank_rank, cfg.lowrank_oversample,
                        cfg=pcfg, key=_kk, method=method,
                        return_iters=with_iters, return_status=with_iters)

                n_real = None
            else:
                n_real = (_gram_real_dims(b)
                          if b.padded and method == "prism" else None)

                def run(x, *nr, _kk=kk):
                    if method == "svd":
                        return matfn.polar(x, method="svd")
                    kw = {"n_real": nr[0]} if nr else {}
                    if with_iters:  # NS family only (asserted above)
                        kw["return_iters"] = True
                        kw["return_status"] = True
                    return matfn.polar(x, method=method, cfg=pcfg, key=_kk,
                                       **kw)

            if mesh is not None and not local_reshard:
                gram_full = min(b.shape)  # pad slices carry no intra-slice pad
                O = shard_over_batch(
                    run, mesh, mesh_axes, stacked,
                    slice_args=() if n_real is None else (n_real,),
                    slice_pads=() if n_real is None else (gram_full,),
                    out_ranks=(3, 1, 1) if with_iters else None)
            else:
                O = run(stacked) if n_real is None else run(stacked, n_real)
            if with_iters:
                O, it, st = O
                scatter_bucket_aux(b, it, iters)
                scatter_bucket_aux(b, st, statuses)
            scatter_bucket(b, O, outs)
    if with_iters:
        return outs, iters, statuses
    return outs  # type: ignore[return-value]


def polar_refresh(views: Sequence[jax.Array], cfg: OptimizerConfig,
                  key: Optional[jax.Array]):
    """The Muon preconditioner refresh as one standalone callable
    (DESIGN.md §12): polar factors of every view, telemetry included iff
    ``cfg.matfn_telemetry``.  Returns ``(outs, iters, statuses)`` with
    ``iters``/``statuses`` None when telemetry is off.

    This is the exact computation a blocking in-step refresh runs —
    factored out of the update so the async service can jit and dispatch
    it as its own program (and so the in-step path and the refresh plane
    can never drift apart).  Dispatch tier (§7 bucketing, §8 sharding,
    §10 fusion, §11 adaptivity) all resolve inside ``polar_bucketed``
    as usual.
    """
    if not cfg.bucketed:
        outs, its, sts = [], [], []
        for i, M in enumerate(views):
            kk = jax.random.fold_in(key, i) if key is not None else None
            if cfg.matfn_method == "svd":
                outs.append(matfn.polar(M, method="svd"))
            elif cfg.matfn_telemetry:
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
        if cfg.matfn_telemetry:
            return outs, its, sts
        return outs, None, None
    if cfg.matfn_telemetry:
        return polar_bucketed(views, cfg, key, with_iters=True)
    return polar_bucketed(views, cfg, key), None, None


def transform_bucketed(mats: Sequence[jax.Array], fn,
                       cfg: Optional[OptimizerConfig] = None,
                       with_aux: bool = False):
    """Apply ``fn(stacked, bucket, bucket_index)`` once per exact-shape
    bucket and scatter the [B, n, n] results back.

    ``with_aux``: an int N (bool True == 1) — fn returns
    (out [B, n, n], aux_1 [B], ..., aux_N [B]), per-slice companions
    (the §11 ``iters_used`` and §15 ``status`` telemetry of Shampoo's
    inverse roots) scattered back alongside; returns
    (outs, auxs_1, ..., auxs_N).  Each aux must be per-slice like the
    output itself, so it shards/gathers with the batch dim unchanged.

    The generic engine for matrix functions without a pad-exactness story
    (Shampoo inverse roots).  Gathers stay fp32 here: the stacked arrays
    are fp32 EMA Kronecker factors whose eps-ridge must be applied in
    fp32 before the chain casts down (DESIGN.md §9) — fn owns the cast.  With a ``cfg`` and an active sharding
    context the batch dim shard_maps over the mesh like
    ``polar_bucketed`` (identity pad slices are SPD, so the Shampoo
    inverse-root chains on them stay finite) — fn's ``stacked`` argument
    is then a LOCAL, possibly identity-padded batch slice, NOT the full
    bucket.  fn must therefore be per-slice (elementwise over the batch
    dim); use the Bucket/index only for static metadata (shape, PRNG
    folding), never to index companion arrays by entry offset.

    Fused tier: fn's inner matfn calls carry their own PrismConfig, so
    the §10 tier resolves inside the iteration family (``ops.fused_fits``
    in newton_schulz) from the same static bucket shape — callers pick it
    up with zero changes, exactly like ``polar_bucketed``.
    """
    n_aux = int(with_aux)
    buckets = plan_buckets([m.shape for m in mats], pad=False)
    mesh, mesh_axes = mesh_batch_axes(cfg)
    outs: List[Optional[jax.Array]] = [None] * len(mats)
    auxs = [[None] * len(mats) for _ in range(n_aux)]
    for bi, b in enumerate(buckets):
        stacked = gather_bucket(b, mats)
        if mesh is not None:
            out = shard_over_batch(
                lambda x, _b=b, _bi=bi: fn(x, _b, _bi),
                mesh, mesh_axes, stacked,
                out_ranks=(3,) + (1,) * n_aux if n_aux else None)
        else:
            out = fn(stacked, b, bi)
        if n_aux:
            out, *aux = out
            for k in range(n_aux):
                scatter_bucket_aux(b, aux[k], auxs[k])
        scatter_bucket(b, out, outs)
    if n_aux:
        return (outs, *auxs)
    return outs  # type: ignore[return-value]
