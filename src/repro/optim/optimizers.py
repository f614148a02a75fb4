"""Optimizers from scratch (no optax): SGD(+momentum), AdamW.

Functional style: ``opt.init(params) -> state``; ``opt.update(grads, state, params,
lr) -> (updates, state)``; apply with ``apply_updates``.  The paper's algorithms use
plain SGD (the gossip replaces the optimizer's averaging); AdamW is provided for the
LM examples and works with every decentralized algorithm (the gossip runs on the
*parameters*, which is exactly what DCD/ECD compress).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class OptState(NamedTuple):
    step: jax.Array
    m: Any = None       # momentum / first moment
    v: Any = None       # second moment (adam only)


@dataclasses.dataclass(frozen=True)
class Optimizer:
    name: str
    init: Callable[[Any], OptState]
    update: Callable[[Any, OptState, Any, jax.Array], Tuple[Any, OptState]]


def sgd(momentum: float = 0.0, weight_decay: float = 0.0, nesterov: bool = False) -> Optimizer:
    def init(params):
        m = jax.tree.map(jnp.zeros_like, params) if momentum else None
        return OptState(step=jnp.zeros((), jnp.int32), m=m)

    def update(grads, state, params, lr):
        with jax.named_scope("optimizer"):
            if weight_decay:
                grads = jax.tree.map(lambda g, p: g + weight_decay * p, grads, params)
            if momentum:
                m = jax.tree.map(lambda mm, g: momentum * mm + g, state.m, grads)
                eff = jax.tree.map(lambda mm, g: g + momentum * mm, m, grads) if nesterov else m
                upd = jax.tree.map(lambda u: -lr * u, eff)
                return upd, OptState(step=state.step + 1, m=m)
            upd = jax.tree.map(lambda g: -lr * g, grads)
            return upd, OptState(step=state.step + 1)

    return Optimizer("sgd", init, update)


def adamw(b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
          weight_decay: float = 0.1) -> Optimizer:
    def init(params):
        z = jax.tree.map(jnp.zeros_like, params)
        return OptState(step=jnp.zeros((), jnp.int32), m=z, v=jax.tree.map(jnp.zeros_like, params))

    def update(grads, state, params, lr):
        with jax.named_scope("optimizer"):
            t = state.step + 1
            m = jax.tree.map(lambda mm, g: b1 * mm + (1 - b1) * g, state.m, grads)
            v = jax.tree.map(lambda vv, g: b2 * vv + (1 - b2) * g * g, state.v, grads)
            bc1 = 1 - b1 ** t.astype(jnp.float32)
            bc2 = 1 - b2 ** t.astype(jnp.float32)
            upd = jax.tree.map(
                lambda mm, vv, p: -lr * ((mm / bc1) / (jnp.sqrt(vv / bc2) + eps)
                                         + weight_decay * p),
                m, v, params)
            return upd, OptState(step=t, m=m, v=v)

    return Optimizer("adamw", init, update)


def apply_updates(params, updates):
    # the train step's optimizer phase (distributed/decentralized.py PHASES)
    with jax.named_scope("optimizer"):
        return jax.tree.map(lambda p, u: (p + u).astype(p.dtype), params, updates)


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm: float):
    n = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (n + 1e-9))
    return jax.tree.map(lambda g: g * scale, grads), n


def make_optimizer(name: str, **kw) -> Optimizer:
    return {"sgd": sgd, "adamw": adamw}[name](**kw)
