"""Grouped-query and latent attention: a fused Pallas kernel or a jnp
chunked softmax.

Supports: GQA (num_kv_heads < num_heads), qk-norm (Qwen3), QKV bias
(Qwen2.5), RoPE, sliding-window (Mixtral) / local (RecurrentGemma)
attention, single-token decode against a KV cache, and DeepSeek's latent
attention (MLA, ``cfg.kv_lora_rank > 0``, train and prefill only): q/k
heads of ``qk_nope + qk_rope`` dims, v heads of ``v_head_dim``, keys and
values projected up from a normed latent, one rotary key shared by every
head (``_project_mla``, under the scope ``mla``).

The train/prefill path has two implementations of one algorithm
(causal online-softmax attention, bf16 operands, float32 accumulation),
chosen in ``attend`` from what the code observes:

* on one TPU, with no window inside the sequence and a sequence the
  kernel's block divides, a fused Pallas kernel (``_kernel_attend``):
  scores and probabilities stay in VMEM, forward and backward;
* everywhere else (the CPU, windows shorter than the sequence, a mesh of
  several devices, odd shapes) a jnp scan over KV blocks of ``KV_BLOCK``
  keys with a running (max, denom, acc) carry (``_flash_attend``).  Its
  activation memory is O(S * block), but at S <= ``KV_BLOCK`` it runs
  one block and materializes the whole [B, S, H, S] score matrix in
  float32 in HBM.

``path_counts`` counts, at trace time, the ``attend`` calls each path
took (``Trainer.counters["attention_path"]`` copies it); the paths run
under the named scopes ``attn_kernel`` and ``attn_chunked``.
"""
from __future__ import annotations

import collections
from typing import Optional

import jax
import numpy as np
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro import sharding_ctx
from repro.config import ModelConfig
from repro.models import layers as L
from repro.scopes import scoped
from repro.sharding_ctx import shard_activation

KV_BLOCK = 1024
# the fused kernel's q and kv block (forward and fused backward), and the
# head sizes it is taken for
KERNEL_BLOCK = 512
KERNEL_HEAD_DIMS = (64, 128, 192)
# DeepSeek's RMSNorm default eps, which MLA's latent norm takes (the
# block norms take the configuration's norm_eps)
MLA_NORM_EPS = 1e-6
HEAD_PAD = 16  # pad head counts to the model-axis width for clean TP

# trace-time count of ``attend`` calls by path: "kernel" or "chunked"
path_counts: collections.Counter = collections.Counter()


def padded_heads(cfg: ModelConfig):
    """(H_padded, KV_padded).  Heads pad up to a multiple of HEAD_PAD with
    exactly-zero parameters: zero heads produce zero outputs AND zero
    gradients, and Newton-Schulz polar (Muon) preserves zero columns, so
    padding is mathematically inert while making 40/24/10/56-head archs
    16-way tensor-shardable (Megatron pads the same way)."""
    H, KV = cfg.num_heads, cfg.num_kv_heads
    Hp = -(-H // HEAD_PAD) * HEAD_PAD
    kvp = Hp if KV == H else KV  # MHA pads kv with q; GQA keeps kv
    assert Hp % kvp == 0, (Hp, kvp)
    return Hp, kvp


def init_attention(key, cfg: ModelConfig):
    if cfg.mla:
        return init_mla(key, cfg)
    dtype = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    nh_t, nkv_t = cfg.num_heads, cfg.num_kv_heads
    nh, nkv = padded_heads(cfg)
    ks = jax.random.split(key, 4)

    def zero_pad(w, axis, true_n):
        if w.shape[axis] == true_n:
            return w
        idx = [slice(None)] * w.ndim
        idx[axis] = slice(true_n, None)
        return w.at[tuple(idx)].set(0)

    p = {
        "wq": zero_pad(L.dense_init(ks[0], (d, nh, hd), -3, dtype), 1, nh_t),
        "wk": zero_pad(L.dense_init(ks[1], (d, nkv, hd), -3, dtype), 1,
                       nkv_t),
        "wv": zero_pad(L.dense_init(ks[2], (d, nkv, hd), -3, dtype), 1,
                       nkv_t),
        "wo": zero_pad(L.dense_init(ks[3], (nh, hd, d), -3, dtype), 0, nh_t),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nh, hd), dtype)
        p["bk"] = jnp.zeros((nkv, hd), dtype)
        p["bv"] = jnp.zeros((nkv, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = L.init_rms_norm(hd)
        p["k_norm"] = L.init_rms_norm(hd)
    return p


def attention_axes(cfg: ModelConfig):
    if cfg.mla:
        return mla_axes(cfg)
    p = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.qkv_bias:
        p.update(bq=("heads", "head_dim"), bk=("kv_heads", "head_dim"),
                 bv=("kv_heads", "head_dim"))
    if cfg.qk_norm:
        p.update(q_norm=("head_dim",), k_norm=("head_dim",))
    return p


def _project_qkv(params, x, positions, cfg: ModelConfig):
    """x [B, S, D] -> q [B, S, H, Hd], k/v [B, S, KV, Hd] (RoPE applied)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    if cfg.qk_norm:
        q = L.rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, params["k_norm"], cfg.norm_eps)
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    q = shard_activation(q, ("batch", "seq", "heads", "head_dim"))
    k = shard_activation(k, ("batch", "seq", "kv_heads", "head_dim"))
    v = shard_activation(v, ("batch", "seq", "kv_heads", "head_dim"))
    return q, k, v


def init_mla(key, cfg: ModelConfig):
    """MLA without q-LoRA: wq [d, H, dn + dr], wkv_a [d, r + dr] (the
    latent and the shared rotary key), kv_norm [r], wkv_b [r, H, dn + dv]
    (each head's nope key and value), wo [H, dv, d]."""
    dtype = jnp.dtype(cfg.dtype)
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {"wq": L.dense_init(ks[0], (d, H, dn + dr), -3, dtype),
            "wkv_a": L.dense_init(ks[1], (d, r + dr), -2, dtype),
            "kv_norm": L.init_rms_norm(r),
            "wkv_b": L.dense_init(ks[2], (r, H, dn + dv), -3, dtype),
            "wo": L.dense_init(ks[3], (H, dv, d), -3, dtype)}


def mla_axes(cfg: ModelConfig):
    """The latent axis marks wkv_b's rows, as ``embed`` marks the other
    projections' (optim/base.py: Muon's matrix views)."""
    return {"wq": ("embed", "heads", "head_dim"),
            "wkv_a": ("embed", "kv_lora"),
            "kv_norm": ("kv_lora",),
            "wkv_b": ("kv_lora", "heads", "head_dim"),
            "wo": ("heads", "head_dim", "embed")}


@scoped("mla")
def _project_mla(params, x, positions, cfg: ModelConfig):
    """x [B, S, D] -> q, k [B, S, H, dn + dr], v [B, S, H, dv]: q and
    the latent from x, the latent normed and projected up to each head's
    nope key and value, rotary positions on q's last dr dims and on the
    one rotary key every head shares."""
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    kv_a = x @ params["wkv_a"]
    c = L.rms_norm(kv_a[..., :r], params["kv_norm"], MLA_NORM_EPS)
    kv = jnp.einsum("bsr,rhk->bshk", c, params["wkv_b"])
    k_rope = L.rope(kv_a[..., None, r:], positions, cfg.rope_theta)
    q = jnp.concatenate(
        [q[..., :dn], L.rope(q[..., dn:], positions, cfg.rope_theta)], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, kv.shape[:3]
                                        + k_rope.shape[-1:])], -1)
    return q, k, kv[..., dn:]


def _expand_kv(k, groups: int):
    """[B, S, KV, Hd] -> [B, S, H, Hd] by repeating each kv head.

    Sharding note: reshaping a model-sharded H dim into (KV, groups)
    de-shards attention under GSPMD (verified on the dry-run — attention
    compute replicated across the model axis).  Repeating KV up to H keeps
    the head dim intact and model-sharded; per chip the repeat gathers
    only the kv heads its q-heads need.
    """
    if groups == 1:
        return k
    return jnp.repeat(k, groups, axis=2)


def _causal_kernel(H: int, S: int):
    """The shipped splash-attention kernel for H heads of causal [S, S]
    masks at the fixed block sizes.  Made anew in every trace: it holds
    its mask tables as arrays of the trace that made it."""
    b = min(KERNEL_BLOCK, S)
    mask = splash.MultiHeadMask([splash.CausalMask((S, S))] * H)
    blocks = splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        use_fused_bwd_kernel=True)
    return splash.make_splash_mha(mask, block_sizes=blocks, head_shards=1,
                                  q_seq_shards=1)


@scoped("attn_kernel")
def _kernel_attend(q, k, v):
    """Causal attention as one fused Pallas kernel, forward and backward;
    q/k [B,S,H|KV,Hd], v [B,S,KV,Hv] -> [B,S,H,Hv], scaled by
    1/sqrt(Hd).  q is scaled in float32 and cast back as
    ``_flash_attend`` does; q, k and v stay bf16, the scores, softmax
    and accumulators float32."""
    B, S, H, Hd = q.shape
    k = _expand_kv(k, H // k.shape[2])
    v = _expand_kv(v, H // v.shape[2])
    scale = 1.0 / jnp.sqrt(Hd).astype(jnp.float32)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    heads_first = lambda x: x.transpose(0, 2, 1, 3)  # [B, H, S, Hd]
    out = jax.vmap(_causal_kernel(H, S))(
        heads_first(qs), heads_first(k), heads_first(v))
    return heads_first(out)


@scoped("attn_chunked")
def _flash_attend(q, k, v, q_pos, k_pos, window: int, kv_block: int):
    """Online-softmax attention; q/k [B,S|Sk,H|KV,Hd], v [B,Sk,KV,Hv]
    -> [B,S,H,Hv], scaled by 1/sqrt(Hd)."""
    B, S, H, Hd = q.shape
    Sk, KV, Hv = k.shape[1], k.shape[2], v.shape[3]
    k = shard_activation(_expand_kv(k, H // KV),
                         ("batch", "seq", "heads", "head_dim"))
    v = shard_activation(_expand_kv(v, H // KV),
                         ("batch", "seq", "heads", "head_dim"))
    scale = 1.0 / jnp.sqrt(Hd).astype(jnp.float32)
    qf = q.astype(jnp.float32) * scale
    nb = -(-Sk // kv_block)
    pad = nb * kv_block - Sk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kpos = jnp.pad(k_pos, ((0, 0), (0, pad)),
                   constant_values=jnp.iinfo(jnp.int32).max)
    kb = kp.reshape(B, nb, kv_block, H, Hd).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(B, nb, kv_block, H, Hv).transpose(1, 0, 2, 3, 4)
    pb = kpos.reshape(B, nb, kv_block).transpose(1, 0, 2)

    def body(carry, blk):
        m, den, acc = carry
        kc, vc, pc = blk  # [B, kb, H, Hd], [B, kb]
        # bf16 operands, fp32 MXU accumulation (halves attention HBM reads)
        s = jnp.einsum("bshk,bthk->bsht", qf.astype(q.dtype), kc,
                       preferred_element_type=jnp.float32)
        bias = L.causal_mask_bias(q_pos, pc, window)  # [B, S, kb]
        s = s + bias[:, :, None, :]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        den = den * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bsht,bthk->bshk", p.astype(q.dtype), vc,
            preferred_element_type=jnp.float32)
        return (m_new, den, acc), None

    # constrain scan carries: without this GSPMD has no preference for the
    # zero-init carries and unifies the loop on a replicated-head layout
    m0 = shard_activation(jnp.full((B, S, H), L.NEG_INF, jnp.float32),
                          ("batch", "seq", "heads"))
    d0 = shard_activation(jnp.zeros((B, S, H), jnp.float32),
                          ("batch", "seq", "heads"))
    a0 = shard_activation(jnp.zeros((B, S, H, Hv), jnp.float32),
                          ("batch", "seq", "heads", "head_dim"))
    (m, den, acc), _ = jax.lax.scan(body, (m0, d0, a0), (kb, vb, pb))
    out = acc / jnp.maximum(den, 1e-30)[..., None]
    return out.astype(q.dtype)


def _one_device() -> bool:
    """No activation-sharding context and no mesh (a shard_map body's
    included) spans more than one device: a ``pallas_call`` is not
    partitioned under GSPMD."""
    ctx = sharding_ctx.current()
    mesh = jax.sharding.get_abstract_mesh()
    return ((ctx is None or ctx[0].size == 1)
            and (mesh.empty or mesh.size == 1))


def _use_kernel(S: int, Sk: int, Hd: int, window: int,
                Hv: Optional[int] = None) -> bool:
    """Whether ``attend`` takes the fused kernel for these shapes here
    (q/k heads of Hd dims, v heads of Hv, Hd if None)."""
    return (jax.default_backend() == "tpu"
            and (not window or window >= S)
            and S == Sk and S % KERNEL_BLOCK == 0
            and Hd in KERNEL_HEAD_DIMS and (Hv or Hd) in KERNEL_HEAD_DIMS
            and _one_device())


def attend(params, x, positions, cfg: ModelConfig, window: int | None = None,
           kv_block: int = KV_BLOCK):
    """Self-attention over x [B, S, D] (train / prefill).

    ``positions`` must be ``arange(S)`` on every row, as every caller
    builds it: the fused kernel masks causally by index, not by
    position (the jnp loop reads the positions)."""
    if cfg.mla:
        q, k, v = _project_mla(params, x, positions, cfg)
    else:
        q, k, v = _project_qkv(params, x, positions, cfg)
    w = cfg.sliding_window if window is None else window
    if _use_kernel(q.shape[1], k.shape[1], q.shape[3], w, v.shape[3]):
        path_counts["kernel"] += 1
        out = _kernel_attend(q, k, v)
    else:
        path_counts["chunked"] += 1
        out = _flash_attend(q, k, v, positions, positions, w, kv_block)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), (k, v)


def decode_attend(params, x, position, cache_k, cache_v, cache_pos, slot,
                  cfg: ModelConfig, window: int | None = None,
                  active: Optional[jax.Array] = None):
    """One-token decode: x [B, 1, D], cache_k/v [B, Sc, KV, Hd].

    cache_pos [B, Sc] holds the absolute position of each cache slot
    (already updated for the CURRENT token at ``slot``; rolling buffers
    for SWA archs reuse slots; empty slots hold a sentinel > pos and are
    masked by causality).  The new k/v are scattered into the cache at
    ``slot`` BEFORE attending — concatenating one token onto a
    kv_seq-sharded cache forces GSPMD to fully rematerialize (all-gather)
    the cache slice per layer (measured 40 GB/token on qwen3 decode_32k);
    the in-place write touches one shard and attention runs flash-decode
    style with the softmax reducing over the sharded seq axis.

    ``active`` [B] bool (serving slot mask, DESIGN.md §16): lanes with
    active=False keep their cache rows bitwise-frozen — a retired slot
    in a continuous-batching step never scribbles its KV state, so its
    cache stays exactly what its request left behind until the slot is
    re-admitted.  None means every lane is live (the training-era path,
    bit-identical to pre-§16 behavior).

    Returns (out [B, 1, D], new cache_k, new cache_v).
    """
    if cfg.mla:
        raise NotImplementedError("latent attention has no decode cache")
    q, k, v = _project_qkv(params, x, position, cfg)
    w = cfg.sliding_window if window is None else window
    B, _, H, Hd = q.shape
    KV = k.shape[2]
    groups = H // KV
    old_k, old_v = cache_k, cache_v
    cache_k = jax.vmap(lambda c, s, kn: c.at[s].set(kn[0]))(cache_k, slot, k)
    cache_v = jax.vmap(lambda c, s, vn: c.at[s].set(vn[0]))(cache_v, slot, v)
    if active is not None:
        gate = active.reshape(B, 1, 1, 1)
        cache_k = jnp.where(gate, cache_k, old_k)
        cache_v = jnp.where(gate, cache_v, old_v)
    keys = shard_activation(cache_k, ("batch", "kv_seq", None, None))
    vals = shard_activation(cache_v, ("batch", "kv_seq", None, None))
    scale = jnp.asarray(1.0 / np.sqrt(Hd), q.dtype)
    qg = (q * scale).reshape(B, 1, KV, groups, Hd)  # local reshape (tiny)
    s = jnp.einsum("bsghk,btgk->bsght", qg, keys,
                   preferred_element_type=jnp.float32)
    s = shard_activation(s, ("batch", None, None, None, "kv_seq"))
    bias = L.causal_mask_bias(position, cache_pos, w)  # [B, 1, Sc]
    s = s + bias[:, :, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bsght,btgk->bsghk", p.astype(q.dtype), vals,
                     preferred_element_type=jnp.float32)
    out = out.reshape(B, 1, H, Hd).astype(x.dtype)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), keys, vals
