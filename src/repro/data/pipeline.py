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
walker and position, so no vocab x vocab table exists at a published
vocabulary (50,280 entries would make it 10 GB).

The walk is sequential in position, and each position reads all of ``F``
(vocab x 64) for only the step's ``global_batch`` walkers.
So ``stacked_node_batches`` walks K consecutive steps at once, staggered:
each call advances steps t..t+K-1 by one segment of ``s_text / K``
positions in one scan (step t+j at position ``(K-1-j) * s_text / K``),
returns step t's finished batch, and keeps the others for the calls that
follow; one read of ``F`` then serves K times the walkers.  K comes from the
shapes (``_pipeline_depth``); at K = 1 a call walks its one step whole.  A
call that is not the next step of the one before fills the pipeline first
(a cold start, under the host span ``input_prime``).  Every id is the one
that walking the step alone gives: the schedule changes, the stream does not.
``pipeline_stats`` counts the segment calls and cold starts.

For VLM/audio archs the pipeline also emits synthetic frontend embeddings
(the modality encoders are stubs per the assignment).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig

# Rank of the synthetic language's transition logits.
_MARKOV_RANK = 64
# Walkers that one position of the pipelined walk advances together, at
# most: K steps of ``global_batch`` walkers share each read of the factors.
_TARGET_WALKERS = 64


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


def _text_len(cfg: DataConfig, arch: Optional[ArchConfig]) -> int:
    if arch is not None and arch.frontend is not None and arch.frontend.kind == "vision":
        return cfg.seq_len - arch.frontend.n_tokens
    return cfg.seq_len


def _shard_keys(cfg: DataConfig, step: jax.Array, shard: jax.Array):
    """(k_init, k_walk, k_extra) of one (step, shard)."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(cfg.seed), step), shard)
    return jax.random.split(key, 3)


def _next_token(factors, tok: jax.Array, k: jax.Array) -> jax.Array:
    """``jax.random.categorical(k, e[tok] @ f.T)``, with its argmax written as
    the lowest index that reaches the maximum.  Compiled for a TPU, the
    fused ``jnp.argmax`` keeps its running maximum in bfloat16 between tiles
    of the vocabulary (its reduce's value output), and the tiles follow the
    number of walkers in the program, so its pick would depend on how many
    walkers share a call; this form's does not."""
    e, f = factors
    v = jax.random.gumbel(k, (tok.shape[0], f.shape[0])) + e[tok] @ f.T
    hit = v == jnp.max(v, axis=-1, keepdims=True)
    return jnp.min(jnp.where(hit, jnp.arange(v.shape[-1]), v.shape[-1]), axis=-1)


def _extra_embeds(cfg: DataConfig, arch: ArchConfig, k_extra: jax.Array) -> jax.Array:
    return jax.random.normal(
        k_extra, (cfg.global_batch // cfg.n_shards, arch.frontend.n_tokens,
                  arch.frontend.dim))


def _shard_batch(cfg: DataConfig, arch: Optional[ArchConfig], factors,
                 step: jax.Array, shard: jax.Array) -> Dict[str, jax.Array]:
    per_shard = cfg.global_batch // cfg.n_shards
    k_init, k_walk, k_extra = _shard_keys(cfg, step, shard)
    x0 = jax.random.randint(k_init, (per_shard,), 0, cfg.vocab)

    def walk(tok, k):
        nxt = _next_token(factors, tok, k)
        return nxt, nxt

    keys = jax.random.split(k_walk, _text_len(cfg, arch))
    _, seq = jax.lax.scan(walk, x0, keys)
    seq = jnp.concatenate([x0[None], seq], axis=0).T               # (B, s_text+1)
    batch = {"tokens": seq[:, :-1], "labels": seq[:, 1:]}
    if arch is not None and arch.frontend is not None:
        batch["extra_embeds"] = _extra_embeds(cfg, arch, k_extra)
    return batch


def _segment(cfg: DataConfig, arch: Optional[ArchConfig], factors, step: jax.Array,
             shards: jax.Array, seqs: jax.Array) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Advance steps step..step+K-1 by one segment of L = s_text / K
    positions; return step's batch and the carry of step+1's call.

    The carry ``seqs`` (K-1, shards, rows, s_text+1) holds the sequences of
    steps step..step+K-2 (slot j is step+j, walked to position (K-1-j) * L,
    zeros past it).  Step step+K-1 starts here, at position 0."""
    k, s_text = seqs.shape[0] + 1, seqs.shape[-1] - 1
    seg = s_text // k
    starts = [(k - 1 - j) * seg for j in range(k)]                  # slot j's position
    keys = jax.vmap(lambda t: jax.vmap(lambda s: _shard_keys(cfg, t, s))(shards))(
        step + jnp.arange(k, dtype=step.dtype))                     # (K, shards, 3)
    x0 = jax.vmap(lambda ki: jax.random.randint(
        ki, (cfg.global_batch // cfg.n_shards,), 0, cfg.vocab))(keys[-1, :, 0])
    seqs = jnp.concatenate([seqs, jnp.zeros_like(seqs[:1]).at[0, ..., 0].set(x0)])
    walk_keys = jax.vmap(jax.vmap(lambda kw: jax.random.split(kw, s_text)))(keys[:, :, 1])
    seg_keys = jnp.stack([walk_keys[j, :, p:p + seg] for j, p in enumerate(starts)])
    tok = jnp.stack([seqs[j, ..., p] for j, p in enumerate(starts)])  # (K, shards, rows)

    def walk(tok, ks):
        nxt = jax.vmap(jax.vmap(functools.partial(_next_token, factors)))(tok, ks)
        return nxt, nxt

    _, walked = jax.lax.scan(walk, tok, seg_keys.transpose(2, 0, 1))  # (L, K, shards, rows)
    walked = walked.transpose(1, 2, 3, 0)
    for j, p in enumerate(starts):
        seqs = jax.lax.dynamic_update_slice(seqs, walked[j:j + 1], (j, 0, 0, p + 1))
    batch = {"tokens": seqs[0, ..., :-1], "labels": seqs[0, ..., 1:]}
    if arch is not None and arch.frontend is not None:
        batch["extra_embeds"] = jax.vmap(lambda ke: _extra_embeds(cfg, arch, ke))(
            keys[0, :, 2])
    return batch, seqs[1:]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _node_batches(cfg: DataConfig, arch: Optional[ArchConfig], step: jax.Array,
                  shards: jax.Array, factors=None, carry=None):
    """Every shard's batch of ``step``, walked whole; or, given the factors
    and a pipeline carry, one segment call (``_segment``).  Both are one
    program named ``_node_batches``, which the input's device time is read by."""
    if carry is not None:
        return _segment(cfg, arch, factors, step, shards, carry)
    factors = _markov_factors(cfg)
    return jax.vmap(lambda s: _shard_batch(cfg, arch, factors, step, s))(shards)


def _pipeline_depth(cfg: DataConfig, arch: Optional[ArchConfig]) -> int:
    """K: the largest power of two at most ``_TARGET_WALKERS // global_batch``
    that divides the walk's length, and at least 1."""
    s_text, k = _text_len(cfg, arch), 1
    while 2 * k <= _TARGET_WALKERS // cfg.global_batch and s_text % (2 * k) == 0:
        k *= 2
    return k


@dataclasses.dataclass
class _Pipeline:
    """The host side of one (DataConfig, arch)'s staggered walk."""
    factors: tuple
    carry: Optional[jax.Array] = None
    next_step: Optional[int] = None
    segment_calls: int = 0
    cold_starts: int = 0


_PIPELINES: Dict[Tuple[DataConfig, Optional[ArchConfig]], _Pipeline] = {}


def pipeline_stats(cfg: DataConfig, arch: Optional[ArchConfig] = None) -> Dict[str, int]:
    """``segment_calls`` (priming calls included), ``cold_starts`` and ``k``
    of ``stacked_node_batches`` for (cfg, arch) in this process."""
    p = _PIPELINES.get((cfg, arch))
    return {"segment_calls": p.segment_calls if p else 0,
            "cold_starts": p.cold_starts if p else 0, "k": _pipeline_depth(cfg, arch)}


def sample_batch(cfg: DataConfig, step: int, shard: int, arch: Optional[ArchConfig] = None
                 ) -> Dict[str, jax.Array]:
    """Deterministic batch for (step, shard): tokens, labels (next-token), extras."""
    assert 0 <= shard < cfg.n_shards
    batch = _node_batches(cfg, arch, jnp.asarray(step, jnp.int32),
                          jnp.asarray([shard], jnp.int32))
    return jax.tree.map(lambda l: l[0], batch)


def stacked_node_batches(cfg: DataConfig, step: int, arch: Optional[ArchConfig] = None
                         ) -> Dict[str, jax.Array]:
    """All shards of ``step`` stacked on a leading node axis: equal to each
    shard's ``sample_batch``.  Consecutive steps share the pipelined walk;
    under a trace (``jax.eval_shape``) the call walks its step whole and
    leaves the pipeline as it was."""
    shards = jnp.arange(cfg.n_shards, dtype=jnp.int32)
    t = jnp.asarray(step, jnp.int32)
    k = _pipeline_depth(cfg, arch)
    if k == 1 or isinstance(t, jax.core.Tracer):
        return _node_batches(cfg, arch, t, shards)
    step = int(step)
    p = _PIPELINES.get((cfg, arch))
    if p is None:
        factors = jax.jit(_markov_factors, static_argnums=0)(cfg)
        p = _PIPELINES[(cfg, arch)] = _Pipeline(factors)
    if step != p.next_step:
        with jax.profiler.TraceAnnotation("input_prime"):
            p.carry = jnp.zeros((k - 1, cfg.n_shards, cfg.global_batch // cfg.n_shards,
                                 _text_len(cfg, arch) + 1), jnp.int32)
            # steps step-K+1..step-1 are no real step's: their slots fill the
            # carry up to step's call and are thrown away
            for b in range(step - k + 1, step):
                _, p.carry = _node_batches(cfg, arch, jnp.asarray(b, jnp.int32), shards,
                                           p.factors, p.carry)
            p.segment_calls += k - 1
            p.cold_starts += 1
    batch, p.carry = _node_batches(cfg, arch, t, shards, p.factors, p.carry)
    p.segment_calls += 1
    p.next_step = step + 1
    return batch
