"""Plain regeneration of the synthetic token stream the cells train on.

The cell's feed is the program's own input pipeline, a layer under
measurement, so the reference does not take its rows on trust.  The stream is
the one the traffic file's ``data`` names: a Markov walk over the vocabulary
whose transition logits are ``E[tok] @ F.T`` (rank 64, ``E`` and ``F``
standard normal from the key ``data_seed + 7919``, ``E`` scaled by
``1 / (concentration * 8)``).  Row ``r`` of node ``i`` at stream step ``s``
starts at ``randint`` of the first of three splits of
``fold_in(fold_in(key(data_seed), s), i)`` and takes its ``t``-th next token
by ``categorical`` with the ``t``-th of ``seq_len`` splits of the second.

Each id a row holds (every token and every label) is regenerated from the
row's own previous token, so one altered id counts once.  The share of ids
that differ is ``feed_mismatch``.  The reference trains on the row's tokens
with labels of its own: each token's regenerated successor.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MARKOV_RANK = 64
_CHUNK = 128            # positions regenerated per pass


def stream_step(data: Dict[str, Any], seed: int, t: int) -> int:
    """The stream step that step ``t`` of a run with ``seed`` reads."""
    return seed % data["step_span"] + t


def _factors(vocab: int, data: Dict[str, Any]):
    ke, kf = jax.random.split(jax.random.key(data["seed"] + 7919))
    e = jax.random.normal(ke, (vocab, MARKOV_RANK))
    f = jax.random.normal(kf, (vocab, MARKOV_RANK))
    return e / (data["concentration"] * MARKOV_RANK ** 0.5), f


class Feed:
    """Checks a step's rows against the stream and gives the labels."""

    def __init__(self, vocab: int, data: Dict[str, Any]):
        self.data = data

        def node(step, i, tokens):
            rows, length = tokens.shape
            key = jax.random.fold_in(jax.random.fold_in(jax.random.key(data["seed"]), step), i)
            k_init, k_walk, _ = jax.random.split(key, 3)
            first = jax.random.randint(k_init, (rows,), 0, vocab)
            c = math.gcd(length, _CHUNK)
            keys = jax.random.split(k_walk, length).reshape(-1, c)
            e, f = _factors(vocab, data)

            def chunk(a):
                ks, toks = a                            # (C,), (C, rows)
                return jax.vmap(lambda k, tok: jax.random.categorical(k, e[tok] @ f.T))(
                    ks, toks)

            cols = tokens.T.reshape(-1, c, rows)
            nxt = jax.lax.map(chunk, (keys, cols)).reshape(length, rows).T
            return first, nxt                           # (rows,), (rows, S)

        self._nodes = jax.jit(jax.vmap(node, in_axes=(None, 0, 0)))

    def __call__(self, seed: int, t: int, tokens: np.ndarray, labels: np.ndarray
                 ) -> Tuple[int, int, np.ndarray]:
        """(ids that differ, ids checked, labels): ``tokens``/``labels``
        (nodes, rows, S) as the feed gave them for step ``t``."""
        n = tokens.shape[0]
        first, nxt = jax.device_get(self._nodes(
            jnp.int32(stream_step(self.data, seed, t)), jnp.arange(n, dtype=jnp.int32),
            jnp.asarray(tokens, jnp.int32)))
        want_tokens = np.concatenate([first[..., None], nxt[..., :-1]], axis=-1)
        differ = int(np.sum(tokens != want_tokens) + np.sum(labels != nxt))
        return differ, tokens.size + labels.size, np.asarray(nxt, np.int32)
