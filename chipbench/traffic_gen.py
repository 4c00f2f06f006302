"""Token batches for training cells, made on the device from the seed.

A low-rank bigram (Markov) source: the first token of each row is
uniform over the vocabulary, and each next token is drawn from
``softmax(U[x] V / sqrt(rank))`` with U [vocab, rank] and V [rank, vocab]
Gaussian factors of scale 1.5.  The same construction as the program's
synthetic stream, so the loss can fall; kept here so that the traffic
is the benchmark's and a change to the program cannot change it.

A traffic file gives ``seq``, ``batch``, ``distinct_batches`` and
``markov_rank``.  Every seed gets the same shapes and the same amount of
work; only the token values change.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp


def _bigram(key, vocab: int, rank: int, rows: int, seq: int):
    ku, kv, k0, ks = jax.random.split(key, 4)
    U = jax.random.normal(ku, (vocab, rank)) * 1.5
    V = jax.random.normal(kv, (rank, vocab)) * 1.5
    x0 = jax.random.randint(k0, (rows,), 0, vocab, jnp.int32)

    def step(x, k):
        nxt = jax.random.categorical(k, (U[x] @ V) / jnp.sqrt(rank))
        nxt = nxt.astype(jnp.int32)
        return nxt, nxt

    _, rest = jax.lax.scan(step, x0, jax.random.split(ks, seq - 1))
    return jnp.concatenate([x0[None], rest], axis=0).T  # [rows, seq]


def batches(key, traffic: Dict, vocab: int) -> List[jax.Array]:
    """``distinct_batches`` token arrays of [batch, seq], int32, made in
    one jitted call; the trainer cycles through them."""
    n, b, s = traffic["distinct_batches"], traffic["batch"], traffic["seq"]
    make = jax.jit(_bigram, static_argnums=(1, 2, 3, 4))
    toks = make(key, vocab, traffic["markov_rank"], n * b, s)
    return [toks[i * b:(i + 1) * b] for i in range(n)]
