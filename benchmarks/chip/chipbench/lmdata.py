"""Training traffic: token sequences from a fixed random Markov chain,
drawn on the device from ``--seed`` in one jitted call.

The chain runs over ``states`` hidden states, each mapped to a distinct
token id spread over the whole vocabulary, so the embedding and the head
see rows from all of it. Every sequence is drawn independently, so rows
differ from each other.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.weights import seed_key


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(key, n_rows: int, seq: int, vocab: int, states: int,
          temperature: float = 0.5):
    k_mat, k_ids, k_first, k_seq = jax.random.split(key, 4)
    logits = jax.random.normal(k_mat, (states, states)) / temperature
    ids = jax.random.permutation(k_ids, vocab)[:states].astype(jnp.int32)
    first = jax.random.randint(k_first, (n_rows,), 0, states)

    def step(s, k):
        nxt = jax.random.categorical(k, logits[s], axis=-1)
        return nxt, nxt

    _, rest = jax.lax.scan(step, first, jax.random.split(k_seq, seq))
    chain = jnp.concatenate([first[:, None], rest.T], axis=1)   # (n, seq+1)
    toks = ids[chain]
    return toks[:, :-1], toks[:, 1:]


def markov_rows(seed: int, n_rows: int, seq: int, vocab: int,
                states: int) -> dict:
    """``{"tokens", "labels"}`` of shape (n_rows, seq), int32 on device."""
    tokens, labels = _draw(jax.random.fold_in(seed_key(seed), 1), n_rows,
                           seq, vocab, min(states, vocab))
    return {"tokens": tokens, "labels": labels}
