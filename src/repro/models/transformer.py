"""Composable decoder model covering every assigned architecture family.

One ``Model`` object (bound to a ModelConfig) provides:

  init(key)            parameters (nested dict, layers stacked for scan)
  logical_axes()       same pytree of logical-axis-name tuples
  param_dtypes()       same pytree of dtypes (mixed-precision cast targets)
  loss(params, batch)  -> (scalar loss, metrics)  [train shapes]
  forward(...)         -> logits                   [prefill shapes]
  init_cache(batch)    decode state (KV / conv+ssm / lru, rolling for SWA)
  cache_logical_axes()
  decode_step(params, cache, tokens, pos) -> (logits, cache)  [decode shapes]

Families:
  dense  — [attn, mlp] x L, one lax.scan over stacked layer params
  moe    — [attn, moe_ffn] x L; with ``first_k_dense`` the leading layers
           are [attn, mlp] of width ``dense_d_ff``, scanned on their own
           (``dense_layers``) before the expert layers (``layers``)
  ssm    — [mamba] x L (attention-free)
  hybrid — scan over (recurrent, recurrent, attention) periods + tail
  vlm    — dense backbone; stub frontend projects precomputed patch embeds
  audio  — dense backbone over summed EnCodec codebook embeddings,
           one LM head per codebook
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import ssm as ssm_lib
from repro.scopes import scoped
from repro.sharding_ctx import shard_activation

INT_SENTINEL = np.iinfo(np.int32).max
VOCAB_PAD_MULTIPLE = 16  # pad odd vocab tables so TP sharding divides


# ---------------------------------------------------------------------------
# per-layer blocks


def _init_block(key, cfg: ModelConfig, dense: bool = False):
    """One decoder layer (dense / moe families); ``dense`` makes a moe
    model's leading dense layer, its MLP ``dense_d_ff`` wide."""
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": L.init_rms_norm(cfg.d_model),
        "ln2": L.init_rms_norm(cfg.d_model),
        "attn": attn.init_attention(k1, cfg),
    }
    if cfg.family in ("moe",) and not dense:
        p["moe"] = moe_lib.init_moe(k2, cfg)
    else:
        p["mlp"] = L.init_mlp(k2, cfg, d_ff=cfg.dense_d_ff if dense
                              else None)
    return p


def _block_axes(cfg: ModelConfig, dense: bool = False):
    p = {"ln1": ("embed",), "ln2": ("embed",),
         "attn": attn.attention_axes(cfg)}
    if cfg.family in ("moe",) and not dense:
        p["moe"] = moe_lib.moe_axes(cfg)
    else:
        p["mlp"] = L.mlp_axes(cfg)
    return p


def _apply_block(p, x, positions, cfg: ModelConfig):
    """One decoder layer -> (x, aux, stats, (k, v)).  ``stats`` is the
    expert layer's (``moe.moe_layer``), empty otherwise.  The per-layer
    (k, v) are what ``attend`` already projects; serving prefill (§16)
    stacks them into the decode cache, the train/loss path discards
    them."""
    h, kv = attn.attend(p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps),
                        positions, cfg)
    x = x + h
    xin = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        h, aux, stats = moe_lib.moe_layer(p["moe"], xin, cfg)
    else:
        h, aux, stats = L.mlp(p["mlp"], xin, cfg), 0.0, {}
    return x + h, aux, stats, kv


def _init_mamba_layer(key, cfg: ModelConfig):
    return {"ln": L.init_rms_norm(cfg.d_model),
            "mamba": ssm_lib.init_mamba(key, cfg)}


def _init_rec_layer(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    return {"ln1": L.init_rms_norm(cfg.d_model),
            "rglru": rglru_lib.init_rglru(k1, cfg),
            "ln2": L.init_rms_norm(cfg.d_model),
            "mlp": L.init_mlp(k2, cfg)}


def _rec_layer_axes(cfg):
    return {"ln1": ("embed",), "rglru": rglru_lib.rglru_axes(cfg),
            "ln2": ("embed",), "mlp": L.mlp_axes(cfg)}


def _apply_rec_layer(p, x, cfg: ModelConfig):
    x = x + rglru_lib.rglru_block(p["rglru"],
                                  L.rms_norm(x, p["ln1"], cfg.norm_eps), cfg)
    x = x + L.mlp(p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
    return x


def _init_attn_layer(key, cfg: ModelConfig):
    """Hybrid attention layer (local window) with its own MLP."""
    k1, k2 = jax.random.split(key)
    return {"ln1": L.init_rms_norm(cfg.d_model),
            "attn": attn.init_attention(k1, cfg),
            "ln2": L.init_rms_norm(cfg.d_model),
            "mlp": L.init_mlp(k2, cfg)}


def _attn_layer_axes(cfg):
    return {"ln1": ("embed",), "attn": attn.attention_axes(cfg),
            "ln2": ("embed",), "mlp": L.mlp_axes(cfg)}


def _apply_attn_layer(p, x, positions, cfg: ModelConfig, window: int):
    h, _ = attn.attend(p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps),
                       positions, cfg, window=window)
    x = x + h
    x = x + L.mlp(p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
    return x


# ---------------------------------------------------------------------------
# stacking helpers


def _stacked_init(init_fn, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def _add_layer_axis(axes_tree):
    return jax.tree.map(lambda t: ("layers",) + tuple(t), axes_tree,
                        is_leaf=lambda t: isinstance(t, tuple))


def _gate_cache(new, old, active, batch_axis: int):
    """Per-lane cache freeze (§16): where ``active`` [B] is False the
    OLD leaf value survives bitwise.  Works on a pytree whose every leaf
    carries the batch dim at ``batch_axis``; active=None is a no-op."""
    if active is None:
        return new

    def gate(n, o):
        shape = [1] * n.ndim
        shape[batch_axis] = -1
        return jnp.where(active.reshape(shape), n, o)

    return jax.tree.map(gate, new, old)


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        # Megatron-style "make vocab divisible": granite's 49155 would
        # otherwise force a replicated LM head / embedding (DESIGN.md §4)
        self.padded_vocab = -(-cfg.vocab_size // VOCAB_PAD_MULTIPLE) \
            * VOCAB_PAD_MULTIPLE
        if cfg.family == "hybrid":
            period = len(cfg.rglru.pattern)
            self.n_periods = cfg.num_layers // period
            self.n_tail = cfg.num_layers - self.n_periods * period
            # decode path assumes any partial tail period is recurrent-only
            assert all(cfg.rglru.pattern[i] == "recurrent"
                       for i in range(self.n_tail)), cfg.rglru.pattern

    # ------------------------------------------------------------- init

    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        kemb, klay, khead, ktail, kproj = jax.random.split(key, 5)
        dtype = jnp.dtype(cfg.emb_dtype)
        params: Dict[str, Any] = {"final_ln": L.init_rms_norm(cfg.d_model)}

        V = self.padded_vocab
        if cfg.family == "audio":
            params["embed"] = L.embed_init(
                kemb, (cfg.num_codebooks, V, cfg.d_model), dtype)
            params["heads"] = L.dense_init(
                khead, (cfg.num_codebooks, cfg.d_model, V), -2, dtype)
        else:
            params["embed"] = L.embed_init(kemb, (V, cfg.d_model), dtype)
            if not cfg.tie_embeddings:
                params["head"] = L.dense_init(
                    khead, (cfg.d_model, V), -2, dtype)
        if cfg.family == "vlm":
            params["vision_proj"] = L.dense_init(
                kproj, (cfg.vision_dim, cfg.d_model), -2, dtype)

        if cfg.family == "ssm":
            params["layers"] = _stacked_init(
                lambda k: _init_mamba_layer(k, cfg), klay, cfg.num_layers)
        elif cfg.family == "hybrid":
            def init_period(k):
                ks = jax.random.split(k, len(cfg.rglru.pattern))
                return {
                    f"p{i}": (_init_rec_layer(ks[i], cfg)
                              if kind == "recurrent"
                              else _init_attn_layer(ks[i], cfg))
                    for i, kind in enumerate(cfg.rglru.pattern)
                }
            params["layers"] = _stacked_init(init_period, klay,
                                             self.n_periods)
            if self.n_tail:
                tks = jax.random.split(ktail, self.n_tail)
                params["tail"] = [
                    (_init_rec_layer(tks[i], cfg)
                     if cfg.rglru.pattern[i] == "recurrent"
                     else _init_attn_layer(tks[i], cfg))
                    for i in range(self.n_tail)
                ]
        else:
            params["layers"] = _stacked_init(
                lambda k: _init_block(k, cfg), klay,
                cfg.num_layers - cfg.first_k_dense)
            if cfg.first_k_dense:
                params["dense_layers"] = _stacked_init(
                    lambda k: _init_block(k, cfg, dense=True), ktail,
                    cfg.first_k_dense)
        return params

    def logical_axes(self):
        cfg = self.cfg
        axes: Dict[str, Any] = {"final_ln": ("embed",)}
        if cfg.family == "audio":
            axes["embed"] = ("codebooks", "vocab", "embed")
            axes["heads"] = ("codebooks", "embed", "vocab")
        else:
            axes["embed"] = ("vocab", "embed")
            if not cfg.tie_embeddings:
                axes["head"] = ("embed", "vocab")
        if cfg.family == "vlm":
            axes["vision_proj"] = (None, "embed")
        if cfg.family == "ssm":
            lay = {"ln": ("embed",), "mamba": ssm_lib.mamba_axes(cfg)}
        elif cfg.family == "hybrid":
            lay = {
                f"p{i}": (_rec_layer_axes(cfg) if kind == "recurrent"
                          else _attn_layer_axes(cfg))
                for i, kind in enumerate(cfg.rglru.pattern)
            }
        else:
            lay = _block_axes(cfg)
        axes["layers"] = _add_layer_axis(lay)
        if cfg.first_k_dense:
            axes["dense_layers"] = _add_layer_axis(_block_axes(cfg, True))
        if cfg.family == "hybrid" and self.n_tail:
            axes["tail"] = [
                (_rec_layer_axes(cfg) if cfg.rglru.pattern[i] == "recurrent"
                 else _attn_layer_axes(cfg))
                for i in range(self.n_tail)
            ]
        return axes

    def param_shapes(self):
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    def param_dtypes(self):
        return jax.tree.map(lambda s: s.dtype, self.param_shapes())

    # ------------------------------------------------------------- embed

    @scoped("embed")
    def _embed_tokens(self, params, tokens):
        cfg = self.cfg
        if cfg.family == "audio":
            # tokens [B, K, S]; sum codebook embeddings
            x = jnp.sum(jax.vmap(
                lambda emb, tok: emb[tok], in_axes=(0, 1), out_axes=1)(
                    params["embed"], tokens), axis=1)
        else:
            x = params["embed"][tokens]
        if cfg.scale_embeddings:
            x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
        x = x.astype(jnp.dtype(cfg.dtype))
        return shard_activation(x, ("batch", "seq", "embed"))

    def _lm_logits(self, params, x):
        cfg = self.cfg
        x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
        if cfg.family == "audio":
            logits = jnp.einsum("bsd,kdv->bksv", x, params["heads"])
        elif cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
        else:
            logits = x @ params["head"]
        logits = L.softcap(logits.astype(jnp.float32), cfg.logits_softcap)
        if self.padded_vocab != cfg.vocab_size:  # mask pad rows out of CE
            iota = jnp.arange(self.padded_vocab)
            logits = jnp.where(iota < cfg.vocab_size, logits, L.NEG_INF)
        return logits

    # ------------------------------------------------------------- forward

    @scoped("layers")
    def _backbone(self, params, x, positions):
        """x [B, S, D] -> (x, aux_loss, stats): ``stats`` the expert
        layers' (``moe.moe_layer``), their held rows averaged over the
        layers and their load ratio the largest, or empty."""
        cfg = self.cfg
        remat = cfg.remat == "block"

        if cfg.family == "ssm":
            def body(carry, p):
                h = carry + ssm_lib.mamba_block(
                    p["mamba"],
                    L.rms_norm(carry, p["ln"], cfg.norm_eps), cfg)
                return h, 0.0
            body = jax.checkpoint(body) if remat else body
            x, _ = jax.lax.scan(body, x, params["layers"])
            return x, 0.0, {}

        if cfg.family == "hybrid":
            def body(carry, p):
                h = carry
                for i, kind in enumerate(cfg.rglru.pattern):
                    if kind == "recurrent":
                        h = _apply_rec_layer(p[f"p{i}"], h, cfg)
                    else:
                        h = _apply_attn_layer(p[f"p{i}"], h, positions, cfg,
                                              cfg.rglru.attention_window)
                return h, 0.0
            body = jax.checkpoint(body) if remat else body
            x, _ = jax.lax.scan(body, x, params["layers"])
            for i in range(self.n_tail):
                p = params["tail"][i]
                if cfg.rglru.pattern[i] == "recurrent":
                    x = _apply_rec_layer(p, x, cfg)
                else:
                    x = _apply_attn_layer(p, x, positions, cfg,
                                          cfg.rglru.attention_window)
            return x, 0.0, {}

        def body(carry, p):
            h, aux, stats, _ = _apply_block(p, carry, positions, cfg)
            return h, (aux, stats)
        body = jax.checkpoint(body) if remat else body
        if "dense_layers" in params:
            x, _ = jax.lax.scan(body, x, params["dense_layers"])
        x, (auxs, stats) = jax.lax.scan(body, x, params["layers"])
        if stats:
            stats = {"moe_held_rows": jnp.mean(stats["moe_held_rows"]),
                     "moe_load_max": jnp.max(stats["moe_load_max"])}
        return x, jnp.sum(auxs), stats

    def _backbone_kv(self, params, x, positions):
        """Dense/moe/vlm/audio backbone that also stacks each layer's
        projected (k, v) — [L, B, S, KV, Hd] — for the §16 serving
        prefill.  No remat: prefill is forward-only."""
        cfg = self.cfg
        self._no_cache()

        def body(carry, p):
            h, aux, _, (k, v) = _apply_block(p, carry, positions, cfg)
            return h, (aux, k, v)

        x, (auxs, ks, vs) = jax.lax.scan(body, x, params["layers"])
        return x, ks, vs

    def forward(self, params, batch) -> jax.Array:
        """Full-sequence logits (train / prefill)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed_tokens(params, tokens)
        B, S = x.shape[0], x.shape[1]
        if cfg.family == "vlm":
            patches = batch["patches"].astype(x.dtype)  # [B, P, vision_dim]
            px = patches @ params["vision_proj"]
            x = jnp.concatenate([px.astype(x.dtype), x], axis=1)
            S = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, aux, _ = self._backbone(params, x, positions)
        if cfg.family == "vlm":
            x = x[:, patches.shape[1]:]
        return self._lm_logits(params, x), aux

    def prefill(self, params, batch) -> jax.Array:
        """Serving prefill: backbone over the full prompt, logits for the
        LAST position only (next-token sampling semantics) — the full
        [B, S, V] logit tensor is never needed when serving."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed_tokens(params, tokens)
        B = x.shape[0]
        if cfg.family == "vlm":
            px = batch["patches"].astype(x.dtype) @ params["vision_proj"]
            x = jnp.concatenate([px.astype(x.dtype), x], axis=1)
        S = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, _, _ = self._backbone(params, x, positions)
        return self._lm_logits(params, x[:, -1:])

    def loss(self, params, batch,
             chunk: Optional[int] = None
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Next-token CE with seq-chunked logits.

        The LM head is applied per sequence chunk inside a scan so the
        [B, S, V] logits (0.5-4 TB fp32 for the 256k-vocab train cells)
        are never materialized — peak extra memory is [B, chunk, V].
        """
        chunk = chunk or self.cfg.loss_chunk
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed_tokens(params, tokens)
        B = x.shape[0]
        if cfg.family == "vlm":
            px = batch["patches"].astype(x.dtype) @ params["vision_proj"]
            x = jnp.concatenate([px.astype(x.dtype), x], axis=1)
        S = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, aux, stats = self._backbone(params, x, positions)
        if cfg.family == "vlm":
            x = x[:, batch["patches"].shape[1]:]
        ce = self._ce_from_hidden(params, x, tokens, chunk)
        loss = ce + aux
        return loss, dict(stats, ce=ce, aux=aux,
                          ppl_proxy=jnp.exp(jnp.minimum(ce, 20.0)))

    @scoped("ce")
    def _ce_from_hidden(self, params, x, tokens,
                        chunk: Optional[int] = None) -> jax.Array:
        """Chunked next-token CE given backbone output x [B, S, D].

        Factored out of ``loss`` so the last pipeline stage
        (train/state.py) can seed CE from its local activations without
        re-running the embedding path."""
        chunk = chunk or self.cfg.loss_chunk
        cfg = self.cfg
        B = x.shape[0]
        if cfg.family == "audio":  # targets [B, K, S]
            tg = tokens[:, :, 1:]
            xs = x[:, :-1]
        else:
            tg = tokens[:, 1:]
            xs = x[:, :-1]
        Sm1 = xs.shape[1]
        nb = -(-Sm1 // chunk)
        pad = nb * chunk - Sm1
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        tgp = jnp.pad(tg, ((0, 0),) * (tg.ndim - 1) + ((0, pad),))
        mask = jnp.pad(jnp.ones((B, Sm1), jnp.float32),
                       ((0, 0), (0, pad)))

        def ce_chunk(carry, idx):
            sl = jax.lax.dynamic_slice_in_dim(xs, idx * chunk, chunk, 1)
            logits = self._lm_logits(params, sl)  # fp32, [B,(K),chunk,V]
            msl = jax.lax.dynamic_slice_in_dim(mask, idx * chunk, chunk, 1)
            if cfg.family == "audio":
                tsl = jax.lax.dynamic_slice_in_dim(tgp, idx * chunk, chunk,
                                                   2)
                logp = jax.nn.log_softmax(logits, axis=-1)
                nll = -jnp.take_along_axis(logp, tsl[..., None],
                                           axis=-1)[..., 0]
                nll = jnp.mean(nll, axis=1)  # average codebooks
            else:
                tsl = jax.lax.dynamic_slice_in_dim(tgp, idx * chunk, chunk,
                                                   1)
                logp = jax.nn.log_softmax(logits, axis=-1)
                nll = -jnp.take_along_axis(logp, tsl[..., None],
                                           axis=-1)[..., 0]
            return carry + jnp.sum(nll * msl), None

        total, _ = jax.lax.scan(jax.checkpoint(ce_chunk), jnp.float32(0.0),
                                jnp.arange(nb))
        return total / (B * Sm1)

    # ------------------------------------------------------------- decode

    def cache_len(self, seq_len: int) -> int:
        """Decode-state length actually required (rolling for SWA/local)."""
        cfg = self.cfg
        if cfg.family == "ssm":
            return 0
        if cfg.sliding_window:
            return min(seq_len, cfg.sliding_window)
        if cfg.family == "hybrid":
            return min(seq_len, cfg.rglru.attention_window)
        return seq_len

    def _no_cache(self):
        if self.cfg.mla or self.cfg.first_k_dense:
            raise NotImplementedError(
                "no decode cache for latent attention or leading dense "
                "layers")

    def init_cache(self, batch: int, seq_len: int):
        cfg = self.cfg
        self._no_cache()
        dtype = jnp.dtype(cfg.dtype)
        hd = cfg.resolved_head_dim
        kv = attn.padded_heads(cfg)[1] if cfg.family != "ssm" \
            else cfg.num_kv_heads
        clen = self.cache_len(seq_len)
        if cfg.family == "ssm":
            return {
                "ssm": jax.vmap(lambda _: ssm_lib.init_mamba_cache(
                    cfg, batch, dtype))(jnp.arange(cfg.num_layers)),
            }
        if cfg.family == "hybrid":
            n_attn_per_period = sum(
                1 for k in cfg.rglru.pattern if k == "attention")
            n_rec_per_period = len(cfg.rglru.pattern) - n_attn_per_period
            cache = {
                "rec": jax.vmap(lambda _: {
                    f"r{i}": rglru_lib.init_rglru_cache(cfg, batch, dtype)
                    for i in range(n_rec_per_period)})(
                        jnp.arange(self.n_periods)),
                "k": jnp.zeros((self.n_periods, n_attn_per_period, batch,
                                clen, kv, hd), dtype),
                "v": jnp.zeros((self.n_periods, n_attn_per_period, batch,
                                clen, kv, hd), dtype),
                "kpos": jnp.full((batch, clen), INT_SENTINEL, jnp.int32),
            }
            if self.n_tail:
                cache["tail"] = [
                    rglru_lib.init_rglru_cache(cfg, batch, dtype)
                    for i in range(self.n_tail)
                    if cfg.rglru.pattern[i] == "recurrent"
                ]
            return cache
        return {
            "k": jnp.zeros((cfg.num_layers, batch, clen, kv, hd), dtype),
            "v": jnp.zeros((cfg.num_layers, batch, clen, kv, hd), dtype),
            "kpos": jnp.full((batch, clen), INT_SENTINEL, jnp.int32),
        }

    def prefill_cache(self, params, batch, cache_len: int, lengths=None):
        """ONE-launch serving prefill (DESIGN.md §16): run the whole
        (right-padded) prompt through the backbone once, returning
        ``(last-real-token logits, populated decode cache)`` — the cache
        has the structure of ``init_cache(B, cache_len)`` and is ready
        for ``decode_step`` at ``pos = length``.

        ``lengths`` [B] int32 gives each lane's true prompt length
        (None = every token of the padded batch is real).  Ragged lanes
        are exact: pad positions never enter the cache (their ``kpos``
        stays the sentinel, so causal masking excludes them — and with a
        rolling window each lane keeps its OWN last ``cache_len`` real
        positions, not the padded batch's), and the returned logits are
        gathered at ``lengths - 1`` per lane.

        dense/moe/vlm/audio run the parallel flash-prefill backbone with
        the per-layer (k, v) stacked straight into the cache — one
        compiled program for the whole prompt instead of ``prompt_len``
        decode launches.  ssm/hybrid (recurrences, not KV tables) fall
        back to one compiled ``lax.scan`` of ``decode_step`` over the
        prompt: still a single launch, bitwise-identical to the streamed
        decode loop it replaces.

        Parity contract: matches a streamed decode loop iff the rolling
        cache never discards a position still inside the attention
        window — i.e. ``cache_len >= prompt_len`` for full attention
        (SWA archs clamp to their window via ``cache_len()``, which is
        lossless).  A smaller cache is a *different* (truncated-context)
        model in both paths and they diverge; the serving engine always
        sizes ``cache_len = prompt_pad + max_gen``.
        """
        cfg = self.cfg
        if cfg.family in ("ssm", "hybrid"):
            return self._prefill_scan(params, batch, cache_len, lengths)
        tokens = batch["tokens"]
        x = self._embed_tokens(params, tokens)
        B = x.shape[0]
        if cfg.family == "vlm":
            assert lengths is None, \
                "ragged prompts are not supported for vlm prefill"
            px = batch["patches"].astype(x.dtype) @ params["vision_proj"]
            x = jnp.concatenate([px.astype(x.dtype), x], axis=1)
        S = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, ks, vs = self._backbone_kv(params, x, positions)

        # slot-centric scatter: slot s of lane b holds that lane's newest
        # real position p ≡ s (mod clen), exactly what a streamed decode
        # loop would have left behind (rolling writes at pos % clen)
        clen = self.cache_len(cache_len)
        L_real = jnp.full((B,), S, jnp.int32) if lengths is None \
            else lengths.astype(jnp.int32)
        s_idx = jnp.arange(clen, dtype=jnp.int32)
        q = (L_real[:, None] - 1 - s_idx[None, :]) // clen  # [B, clen]
        win = s_idx[None, :] + clen * q
        has = win >= 0
        src = jnp.clip(win, 0, S - 1)
        gather_idx = src[None, :, :, None, None]
        mask5 = has[None, :, :, None, None]
        new_k = jnp.where(mask5, jnp.take_along_axis(ks, gather_idx, axis=2),
                          0).astype(jnp.dtype(cfg.dtype))
        new_v = jnp.where(mask5, jnp.take_along_axis(vs, gather_idx, axis=2),
                          0).astype(jnp.dtype(cfg.dtype))
        kpos = jnp.where(has, win, INT_SENTINEL).astype(jnp.int32)

        if lengths is None:
            x_last = x[:, -1:]
        else:
            idx = (L_real - 1).reshape(B, 1, 1)
            x_last = jnp.take_along_axis(x, idx, axis=1)
        return self._lm_logits(params, x_last), \
            {"k": new_k, "v": new_v, "kpos": kpos}

    def _prefill_scan(self, params, batch, cache_len: int, lengths=None):
        """Prefill fallback for recurrent-state families: one compiled
        scan of decode_step over the prompt, active-masked past each
        lane's true length so pad steps freeze the state bitwise."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape[0], tokens.shape[-1]
        cache = self.init_cache(B, cache_len)
        V = self.padded_vocab
        shape = (B, cfg.num_codebooks, 1, V) if cfg.family == "audio" \
            else (B, 1, V)
        last0 = jnp.zeros(shape, jnp.float32)
        L_real = None if lengths is None else lengths.astype(jnp.int32)

        def body(carry, t):
            cache, last = carry
            tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=-1)
            pos = jnp.full((B, 1), t, jnp.int32)
            act = None if L_real is None else (t < L_real)
            logits, cache = self.decode_step(params, cache, tok, pos,
                                             active=act)
            if L_real is None:
                last = jnp.where(t == S - 1, logits, last)
            else:
                cond = (t == L_real - 1).reshape(
                    (B,) + (1,) * (logits.ndim - 1))
                last = jnp.where(cond, logits, last)
            return (cache, last), None

        (cache, last), _ = jax.lax.scan(body, (cache, last0),
                                        jnp.arange(S, dtype=jnp.int32))
        return last, cache

    def decode_step(self, params, cache, tokens, pos, active=None):
        """tokens [B, 1] ([B, K, 1] audio); pos [B, 1] absolute position.

        Returns (logits for the new token, updated cache).  Rolling caches
        write at slot pos % window.

        ``active`` [B] bool is the serving slot mask (DESIGN.md §16):
        inactive lanes run as dead compute in the fixed-capacity batch
        but leave EVERY cache leaf bitwise-frozen — KV rows, kpos, SSM /
        RG-LRU state — so a retired or not-yet-admitted slot can never
        scribble state that a later request would observe.  Their logits
        are garbage by construction; callers (serving/engine.py) mask
        them at the sampling layer.  active=None is the pre-§16
        every-lane-live path, bit-identical to before.
        """
        cfg = self.cfg
        x = self._embed_tokens(params, tokens)  # audio sums codebooks
        if cfg.family == "ssm":
            def body(carry, inp):
                h = carry
                p, c = inp
                y, c2 = ssm_lib.mamba_decode_step(
                    p["mamba"], L.rms_norm(h, p["ln"], cfg.norm_eps), c, cfg)
                return h + y, c2
            x, new_ssm = jax.lax.scan(body, x,
                                      (params["layers"], cache["ssm"]))
            new_cache = {"ssm": _gate_cache(new_ssm, cache["ssm"], active,
                                            batch_axis=1)}
            return self._lm_logits(params, x), new_cache

        if cfg.family == "hybrid":
            return self._decode_hybrid(params, cache, x, pos, active)

        clen = cache["k"].shape[2]
        slot = (pos[:, 0] % clen).astype(jnp.int32)  # [B]
        new_kpos = jax.vmap(
            lambda kp, s, p: kp.at[s].set(p))(cache["kpos"], slot, pos[:, 0])
        new_kpos = _gate_cache(new_kpos, cache["kpos"], active, batch_axis=0)

        def body(carry, inp):
            h = carry
            p, ck, cv = inp
            y, ck, cv = attn.decode_attend(
                p["attn"], L.rms_norm(h, p["ln1"], cfg.norm_eps), pos,
                ck, cv, new_kpos, slot, cfg, active=active)
            h = h + y
            hin = L.rms_norm(h, p["ln2"], cfg.norm_eps)
            if cfg.family == "moe":
                ff, _, _ = moe_lib.moe_layer(p["moe"], hin, cfg)
            else:
                ff = L.mlp(p["mlp"], hin, cfg)
            h = h + ff
            return h, (ck, cv)

        x, (new_k, new_v) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
        logits = self._lm_logits(params, x)
        return logits, {"k": new_k, "v": new_v, "kpos": new_kpos}

    def _decode_hybrid(self, params, cache, x, pos, active=None):
        cfg = self.cfg
        clen = cache["k"].shape[3]
        slot = (pos[:, 0] % clen).astype(jnp.int32)
        new_kpos = jax.vmap(
            lambda kp, s, p: kp.at[s].set(p))(cache["kpos"], slot, pos[:, 0])
        new_kpos = _gate_cache(new_kpos, cache["kpos"], active, batch_axis=0)

        def body(carry, inp):
            h = carry
            p, crec, ck, cv = inp
            new_rec = {}
            ai = 0
            ri = 0
            for i, kind in enumerate(cfg.rglru.pattern):
                pi = p[f"p{i}"]
                if kind == "recurrent":
                    y, c2 = rglru_lib.rglru_decode_step(
                        pi["rglru"],
                        L.rms_norm(h, pi["ln1"], cfg.norm_eps),
                        crec[f"r{ri}"], cfg)
                    new_rec[f"r{ri}"] = c2
                    ri += 1
                else:
                    y, ck_new, cv_new = attn.decode_attend(
                        pi["attn"], L.rms_norm(h, pi["ln1"], cfg.norm_eps),
                        pos, ck[ai], cv[ai], new_kpos, slot, cfg,
                        window=cfg.rglru.attention_window, active=active)
                    ck = ck.at[ai].set(ck_new)
                    cv = cv.at[ai].set(cv_new)
                    ai += 1
                h = h + y
                h = h + L.mlp(pi["mlp"],
                              L.rms_norm(h, pi["ln2"], cfg.norm_eps), cfg)
            return h, (new_rec, ck, cv)

        x, (new_rec, new_k, new_v) = jax.lax.scan(
            body, x, (params["layers"], cache["rec"], cache["k"],
                      cache["v"]))
        new_rec = _gate_cache(new_rec, cache["rec"], active, batch_axis=1)
        new_cache = {"rec": new_rec, "k": new_k, "v": new_v,
                     "kpos": new_kpos}
        ti = 0
        new_tail = []
        for i in range(self.n_tail):
            p = params["tail"][i]
            if cfg.rglru.pattern[i] == "recurrent":
                y, c2 = rglru_lib.rglru_decode_step(
                    p["rglru"], L.rms_norm(x, p["ln1"], cfg.norm_eps),
                    cache["tail"][ti], cfg)
                new_tail.append(_gate_cache(c2, cache["tail"][ti], active,
                                            batch_axis=0))
                ti += 1
                x = x + y
                x = x + L.mlp(p["mlp"],
                              L.rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
        if self.n_tail:
            new_cache["tail"] = new_tail
        return self._lm_logits(params, x), new_cache


def build(cfg: ModelConfig) -> Model:
    return Model(cfg)
