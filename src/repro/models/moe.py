"""Mixture-of-Experts FFN: capacity-based sort dispatch, or a dropless
grouped layer that holds a share of the experts.

Capacity dispatch (``dispatch`` "global" / "per_sample"): top-k
routing -> flatten assignments -> stable-sort by expert -> fixed
capacity C = ceil(k*T/E * capacity_factor) slots per expert -> gather,
batched per-expert SwiGLU, weighted scatter-combine.  Fully jittable and
shardable:

  * EP  (granite, 32e % 16 == 0): expert dim of the stacked weights maps
    to the "model" mesh axis; the gather/scatter become all-to-alls.
  * TP  (mixtral, 8e < 16): each expert's hidden dim maps to "model".

Its aux load-balancing loss (Switch-style) is returned alongside the
output.

Grouped dispatch (``dispatch="grouped"``, DeepSeek-V3 / Moonlight): the
router scores all ``num_experts`` (sigmoid, a fixed correction bias for
selection, the chosen scores normalized and scaled); the layer holds
rank 0's share of ``ep_size`` (``MoEConfig.held_experts``) and computes
only the assignments routed to it: sorted by expert, run as grouped
products whose work follows the rows routed here (nothing dropped, no
capacity), weighted and scattered back.  Shared experts see every
token.  The sequence-wise balance loss covers all experts.  Scopes
``moe/route``, ``moe/dispatch``, ``moe/experts``, ``moe/combine`` and
``moe/shared``; ``path_counts`` counts the path each traced call took
(``Trainer.counters["moe_path"]`` copies it).
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from repro.config import ModelConfig, MoEConfig
from repro.models import layers as L

# the correction bias's seed scale (held fixed: its update speed is 0)
BIAS_INIT_STD = 1e-2
# megablox gmm's (rows, contraction, columns) tile: 512-row tiles ran
# the Moonlight cell's expert layer in 60.6 ms forward and backward on a
# v5e, 128-row tiles in 109.1 ms, XLA's ragged_dot in 59.6 ms
GMM_TILING = (512, 512, 128)

# trace-time count of ``moe_layer`` calls by path: "capacity" or "grouped"
path_counts: collections.Counter = collections.Counter()


def init_moe(key, cfg: ModelConfig):
    m = cfg.moe
    dtype = jnp.dtype(cfg.dtype)
    d, f, e = cfg.d_model, cfg.d_ff, m.held_experts
    ks = jax.random.split(key, 6)
    p = {
        "router": L.dense_init(ks[0], (d, m.num_experts), -2, jnp.float32),
        "w_gate": L.dense_init(ks[1], (e, d, f), -2, dtype),
        "w_up": L.dense_init(ks[2], (e, d, f), -2, dtype),
        "w_down": L.dense_init(ks[3], (e, f, d), -2, dtype),
    }
    if m.selection_bias:
        p["correction_bias"] = BIAS_INIT_STD * jax.random.normal(
            ks[4], (m.num_experts,), jnp.float32)
    if m.n_shared_experts:
        p["shared"] = L.init_mlp(ks[5], cfg, d_ff=m.n_shared_experts * f)
    return p


def moe_axes(cfg: ModelConfig):
    p = {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", "expert_mlp"),
        "w_up": ("experts", "embed", "expert_mlp"),
        "w_down": ("experts", "expert_mlp", "embed"),
    }
    if cfg.moe.selection_bias:
        p["correction_bias"] = (None,)
    if cfg.moe.n_shared_experts:
        p["shared"] = L.mlp_axes(cfg)
    return p


def _dispatch_indices(topi, gates, E: int, C: int):
    """[T, K] assignments -> (tok_for_slot [E*C], gate_for_slot [E*C]).

    Stable sort by expert id + fixed per-expert capacity C; overflow
    assignments land in a scratch slot and are dropped.
    """
    T, K = topi.shape
    flat_e = topi.reshape(-1)                         # [T*K] expert ids
    flat_t = jnp.arange(T * K, dtype=jnp.int32) // K  # token of assignment
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st_, sg = flat_e[order], flat_t[order], flat_g[order]
    # position within expert = index - first index of this expert id
    first = jnp.searchsorted(se, se, side="left")
    pos = jnp.arange(T * K, dtype=jnp.int32) - first.astype(jnp.int32)
    keep = pos < C
    slot = jnp.where(keep, se * C + pos, E * C)  # overflow -> scratch slot
    tok_for_slot = jnp.zeros(E * C + 1, jnp.int32).at[slot].set(st_)
    gate_for_slot = jnp.zeros(E * C + 1, jnp.float32).at[slot].set(
        jnp.where(keep, sg, 0.0))
    return tok_for_slot[:-1], gate_for_slot[:-1]


def moe_layer(params, x, cfg: ModelConfig):
    """x [B, S, D] -> (y [B, S, D], aux_loss scalar, stats) on the path
    ``dispatch`` names.  ``stats`` is empty on the capacity path; the
    grouped path's is documented in ``_grouped_ffn``."""
    if cfg.moe.dispatch == "grouped":
        path_counts["grouped"] += 1
        with jax.named_scope("moe"):
            return _grouped_ffn(params, x, cfg)
    path_counts["capacity"] += 1
    y, aux = moe_ffn(params, x, cfg)
    return y, aux, {}


def moe_ffn(params, x, cfg: ModelConfig):
    """The capacity path: x [B, S, D] -> (y [B, S, D], aux_loss
    scalar)."""
    from repro.sharding_ctx import shard_activation

    m: MoEConfig = cfg.moe
    B, S, D = x.shape
    E, K = m.num_experts, m.num_experts_per_tok
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"])
    probs_full = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(logits, K)  # [B, S, K]
    gates = jax.nn.softmax(topv, axis=-1)  # renormalize over selected

    # ---- aux load-balance loss (Switch): E * sum_e f_e * P_e
    onehot = jax.nn.one_hot(topi, E, dtype=jnp.float32)  # [B, S, K, E]
    f_e = jnp.mean(jnp.sum(onehot, axis=2), axis=(0, 1))
    P_e = jnp.mean(probs_full, axis=(0, 1))
    aux = E * jnp.sum(f_e * P_e) * m.router_aux_loss_coef

    if m.dispatch == "per_sample":
        # batch-local dispatch: gathers/scatters never cross the batch
        # shard, so a data-sharded token stream routes with zero token
        # all-gathers (§Perf MoE iteration).  Capacity is per sample.
        C = max(int(-(-K * S // E) * m.capacity_factor), 4)
        tok_slot, gate_slot = jax.vmap(
            lambda ti, g: _dispatch_indices(ti, g, E, C))(topi, gates)
        xg = jax.vmap(lambda xb, tb: xb[tb])(x, tok_slot)  # [B, E*C, D]
        xg = xg.reshape(B, E, C, D)
        xg = shard_activation(xg, ("batch", "experts", None, "embed_act"))
        gate_h = jnp.einsum("becd,edf->becf", xg, params["w_gate"])
        up_h = jnp.einsum("becd,edf->becf", xg, params["w_up"])
        h = (jax.nn.silu(gate_h.astype(jnp.float32))
             * up_h.astype(jnp.float32)).astype(x.dtype)
        h = shard_activation(h, ("batch", "experts", None, "expert_mlp"))
        y_e = jnp.einsum("becf,efd->becd", h,
                         params["w_down"]).reshape(B, E * C, D)
        y = jax.vmap(
            lambda ts, ye, gs: jnp.zeros((S, D), jnp.float32)
            .at[ts].add(ye.astype(jnp.float32) * gs[:, None]))(
                tok_slot, y_e, gate_slot)
        return y.astype(x.dtype), aux

    # ---- global dispatch over all B*S tokens (baseline)
    T = B * S
    xf = x.reshape(T, D)
    C = max(int(-(-K * T // E) * m.capacity_factor), 4)
    tok_for_slot, gate_for_slot = _dispatch_indices(
        topi.reshape(T, K), gates.reshape(T, K), E, C)
    xg = xf[tok_for_slot].reshape(E, C, D)  # [E, C, D]
    xg = shard_activation(xg, ("experts", None, "embed_act"))
    # per-expert SwiGLU
    gate_h = jnp.einsum("ecd,edf->ecf", xg, params["w_gate"])
    up_h = jnp.einsum("ecd,edf->ecf", xg, params["w_up"])
    h = (jax.nn.silu(gate_h.astype(jnp.float32))
         * up_h.astype(jnp.float32)).astype(x.dtype)
    h = shard_activation(h, ("experts", None, "expert_mlp"))
    y_e = jnp.einsum("ecf,efd->ecd", h, params["w_down"]).reshape(E * C, D)

    y = jnp.zeros((T, D), jnp.float32).at[tok_for_slot].add(
        y_e.astype(jnp.float32) * gate_for_slot[:, None])
    return y.reshape(B, S, D).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# dropless grouped layer over the held share


def route_sigmoid(params, x, m: MoEConfig):
    """x [T, D] -> (scores [T, E], ids [T, K], gates [T, K]), float32.

    ``s = sigmoid(x W_g)``, contracted in float32 (``HIGHEST``: a TPU's
    default would round W_g to bfloat16); the top K of ``s + b`` (``b``
    the correction bias, selection only: no gradient reaches it) are
    chosen, and their gates are ``s`` at the chosen ids, normalized to
    sum 1 and scaled by ``routed_scaling_factor``."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), params["router"],
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    sel = s + params["correction_bias"] if m.selection_bias else s
    _, ids = jax.lax.top_k(sel, m.num_experts_per_tok)
    gates = jnp.take_along_axis(s, ids, axis=-1)
    if m.norm_topk_prob:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return s, ids, gates * m.routed_scaling_factor


def seq_aux_loss(scores, ids, m: MoEConfig):
    """DeepSeek-V3's sequence-wise balance loss over all experts:
    ``alpha * sum_i f_i P_i`` per sequence, averaged over sequences,
    with ``f_i = E / (K S) * #{t: i chosen at t}`` and ``P_i`` the mean
    over the sequence of ``s_i / sum_j s_j``.  scores [B, S, E], ids
    [B, S, K]."""
    B, S, E = scores.shape
    K = ids.shape[-1]
    chosen = jnp.zeros((B, E), jnp.float32).at[
        jnp.arange(B)[:, None], ids.reshape(B, S * K)].add(1.0)
    f = chosen * (E / (K * S))
    p = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=1)
    return m.router_aux_loss_coef * jnp.mean(jnp.sum(f * p, axis=-1))


def grouped_dot(lhs, rhs, group_sizes):
    """lhs [M, k] @ rhs [G, k, n] by groups of consecutive rows (group g
    takes the next ``group_sizes[g]`` rows); rows past the groups are
    left out of the work and their output rows are not read.  On a TPU,
    the shipped megablox kernel (``gmm``: its grid covers only the tiles
    the groups fill, and its launches carry their scope in a trace,
    which XLA's ragged-dot kernels do not), with M padded to its row
    tile; elsewhere XLA's ``ragged_dot``."""
    if jax.default_backend() != "tpu":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=lhs.dtype)
    M = lhs.shape[0]
    pad = -M % GMM_TILING[0]
    out = megablox.gmm(jnp.pad(lhs, ((0, pad), (0, 0))), rhs, group_sizes,
                       lhs.dtype, GMM_TILING)
    return out[:M]


def _grouped_ffn(params, x, cfg: ModelConfig):
    """The held share's routed experts, dropless, plus the shared MLP.

    ``stats``: ``moe_held_rows``, the assignments computed here, and
    ``moe_load_max``, the most-loaded expert's assignments over the mean
    (T K / E), across all experts."""
    m: MoEConfig = cfg.moe
    B, S, D = x.shape
    T, K, E, Eh = B * S, m.num_experts_per_tok, m.num_experts, m.held_experts
    xf = x.reshape(T, D)
    with jax.named_scope("route"):
        scores, ids, gates = route_sigmoid(params, xf, m)
        aux = (seq_aux_loss(scores.reshape(B, S, E), ids.reshape(B, S, K), m)
               if m.seq_aux else jnp.float32(0.0))
        load = jnp.zeros((E,), jnp.int32).at[ids.reshape(-1)].add(1)
    with jax.named_scope("dispatch"):
        # assignments to other shares sort after the held ones; a token
        # picks an expert at most once, so the held ones fit in M rows
        flat = ids.reshape(-1)
        order = jnp.argsort(jnp.where(flat < Eh, flat, Eh), stable=True)
        M = T * min(K, Eh)
        order = order[:M]
        tok = order // K
        group_sizes = load[:Eh]
        held = jnp.sum(group_sizes)
        valid = jnp.arange(M) < held
        rows = jnp.where(valid[:, None], xf[tok], 0).astype(x.dtype)
        w = jnp.where(valid, gates.reshape(-1)[order], 0.0)
    with jax.named_scope("experts"):
        g = grouped_dot(rows, params["w_gate"], group_sizes)
        u = grouped_dot(rows, params["w_up"], group_sizes)
        h = (jax.nn.silu(g.astype(jnp.float32))
             * u.astype(jnp.float32)).astype(x.dtype)
        y = grouped_dot(h, params["w_down"], group_sizes)
    with jax.named_scope("combine"):
        y = jnp.where(valid[:, None], y.astype(jnp.float32) * w[:, None], 0.0)
        out = jnp.zeros((T, D), jnp.float32).at[tok].add(y)
    if m.n_shared_experts:
        with jax.named_scope("shared"):
            out = out + L.mlp(params["shared"], x, cfg).reshape(
                T, D).astype(jnp.float32)
    stats = {"moe_held_rows": held.astype(jnp.float32),
             "moe_load_max": jnp.max(load).astype(jnp.float32) * E / (T * K)}
    return out.reshape(B, S, D).astype(x.dtype), aux, stats
