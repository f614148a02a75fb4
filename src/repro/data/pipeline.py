"""Deterministic synthetic data pipeline with per-node sharding.

The paper's setting has node-local distributions D_i (Assumption: data is
partitioned across nodes; zeta² measures their disagreement).  This pipeline
gives every node a *different, deterministic* token stream:

* the global stream is a PRNG-derived Markovian token source (so there is real
  learnable structure: next-token depends on the current token);
* node ``i`` of ``n`` reads shard ``i`` — disjoint slices of the step's global
  batch, exactly like a production loader sharding by host;
* fully deterministic in (seed, step, node) — restart-safe for checkpoint resume,
  and the same batch is reproducible on any topology.

The transition logits are low rank, ``E[tok] @ F.T``, computed one row per
walker and step, so no vocab x vocab table exists at a published vocabulary
(50,280 entries would make it 10 GB).  A step's batch for every node comes
out of one jitted call.

For VLM/audio archs the pipeline also emits synthetic frontend embeddings
(the modality encoders are stubs per the assignment).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig

# Rank of the synthetic language's transition logits.
_MARKOV_RANK = 64


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab: int
    seq_len: int
    global_batch: int
    n_shards: int = 1
    seed: int = 0
    markov_concentration: float = 0.3   # smaller = more structure (lower entropy)


def _markov_factors(cfg: DataConfig):
    """Fixed random factors of the transition logits, scaled so each logit is
    ~ N(0, 1/concentration²) like a dense Gaussian table's."""
    ke, kf = jax.random.split(jax.random.key(cfg.seed + 7919))
    e = jax.random.normal(ke, (cfg.vocab, _MARKOV_RANK))
    f = jax.random.normal(kf, (cfg.vocab, _MARKOV_RANK))
    return e / (cfg.markov_concentration * _MARKOV_RANK ** 0.5), f


def _shard_batch(cfg: DataConfig, arch: Optional[ArchConfig], factors,
                 step: jax.Array, shard: jax.Array) -> Dict[str, jax.Array]:
    per_shard = cfg.global_batch // cfg.n_shards
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(cfg.seed), step), shard)
    k_init, k_walk, k_extra = jax.random.split(key, 3)
    e, f = factors
    s_text = cfg.seq_len
    if arch is not None and arch.frontend is not None and arch.frontend.kind == "vision":
        s_text = cfg.seq_len - arch.frontend.n_tokens

    x0 = jax.random.randint(k_init, (per_shard,), 0, cfg.vocab)

    def walk(tok, k):
        nxt = jax.random.categorical(k, e[tok] @ f.T)
        return nxt, nxt

    keys = jax.random.split(k_walk, s_text)
    _, seq = jax.lax.scan(walk, x0, keys)
    seq = jnp.concatenate([x0[None], seq], axis=0).T               # (B, s_text+1)
    batch = {"tokens": seq[:, :-1], "labels": seq[:, 1:]}
    if arch is not None and arch.frontend is not None:
        batch["extra_embeds"] = jax.random.normal(
            k_extra, (per_shard, arch.frontend.n_tokens, arch.frontend.dim))
    return batch


@functools.partial(jax.jit, static_argnums=(0, 1))
def _node_batches(cfg: DataConfig, arch: Optional[ArchConfig], step: jax.Array,
                  shards: jax.Array) -> Dict[str, jax.Array]:
    factors = _markov_factors(cfg)
    return jax.vmap(lambda s: _shard_batch(cfg, arch, factors, step, s))(shards)


def sample_batch(cfg: DataConfig, step: int, shard: int, arch: Optional[ArchConfig] = None
                 ) -> Dict[str, jax.Array]:
    """Deterministic batch for (step, shard): tokens, labels (next-token), extras."""
    assert 0 <= shard < cfg.n_shards
    batch = _node_batches(cfg, arch, jnp.asarray(step, jnp.int32),
                          jnp.asarray([shard], jnp.int32))
    return jax.tree.map(lambda l: l[0], batch)


def stacked_node_batches(cfg: DataConfig, step: int, arch: Optional[ArchConfig] = None
                         ) -> Dict[str, jax.Array]:
    """All shards stacked on a leading node axis, from one jitted call."""
    return _node_batches(cfg, arch, jnp.asarray(step, jnp.int32),
                         jnp.arange(cfg.n_shards, dtype=jnp.int32))
