"""Plain reference of the first training steps of a decentralized cell.

Every node keeps its own model ``X_i`` and AdamW moments.  One step of DCD
(Tang et al., NeurIPS 2018, Algorithm 1), with AdamW's update in place of the
plain gradient step, is

    g_i      = grad of node i's mean token loss at X_i on its own rows
    u_i      = AdamW(g_i, X_i)                      (decoupled weight decay)
    X_half_i = sum_j W_ij X_j + u_i                 (W: the gossip matrix)
    X_i     <- X_i + Q(X_half_i - X_i)

where ``Q`` is the unbiased stochastic ``bits``-bit quantizer of the wire
(per block of the last dimension: scale = max |z|, levels 2^(bits-1) - 1,
stochastic rounding).  The paper's replicas of the neighbours' models are
exact copies under DCD (both ends apply the same decoded delta), so the
reference mixes the neighbours' models themselves.  Its rounding draws come
from ``jax.random`` and not from the program's hash: the numbers compared are
norms, which the two draws give alike.

Nothing here imports the program.  Inputs are the token rows the cell's own
feed produced for the first steps, checked against a plain regeneration of
the stream (``data.Feed``), with labels the reference derives itself; the
weights are made from the seed by the program's recipe
(``model.init_params``).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.reference.data import Feed
from bench.reference.model import HIGHEST, Numerics, init_params, sequence_loss
from bench.wire import block_len


def gossip_matrix(topology: str, n: int) -> np.ndarray:
    """The mixing matrix W of the paper's ring: each node averages itself and
    its distinct ring neighbours with equal weights."""
    if topology != "ring":
        raise ValueError(f"the reference knows the ring only, not {topology!r}")
    w = np.zeros((n, n))
    for i in range(n):
        for j in {i, (i + 1) % n, (i - 1) % n}:
            w[i, j] = 1.0
    return w / w.sum(axis=1, keepdims=True)


def lr_at(traffic: Dict[str, Any], step: int) -> float:
    """Linear warm-up to ``lr`` over ``warmup`` steps, then cosine decay to a
    tenth over the rest of ``horizon``."""
    peak, warm, horizon = traffic["lr"], traffic["warmup"], traffic["horizon"]
    if step < warm:
        return peak * step / max(warm, 1)
    total = max(horizon - warm, 1)
    t = min(step - warm, total) / total
    return peak * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * t)))


def quantize(z: jax.Array, key: jax.Array, bits: int) -> jax.Array:
    """Unbiased stochastic quantization of ``z`` and its decoded value."""
    levels = 2 ** (bits - 1) - 1
    last = z.shape[-1]
    block = block_len(last, bits)
    pad = (-last) % block
    zb = jnp.pad(z, [(0, 0)] * (z.ndim - 1) + [(0, pad)])
    zb = zb.reshape(*z.shape[:-1], (last + pad) // block, block)
    scale = jnp.max(jnp.abs(zb), axis=-1, keepdims=True)
    v = zb * (levels / jnp.where(scale > 0, scale, 1.0))
    lo = jnp.floor(v)
    q = lo + (jax.random.uniform(key, v.shape) < v - lo)
    q = jnp.clip(q, -levels, levels)
    out = q * (scale * (1.0 / levels))
    return out.reshape(*z.shape[:-1], last + pad)[..., :last]


@jax.jit
def _node_norms(leaves):
    return [jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32)), axis=tuple(range(1, l.ndim))))
            for l in leaves]


def leaf_norms(tree) -> Dict[str, np.ndarray]:
    """{leaf path: per-node L2 norms (n,)} of a node-stacked tree."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = _node_norms([l for _, l in flat])
    return {jax.tree_util.keystr(p): np.asarray(v, np.float64)
            for (p, _), v in zip(flat, norms)}


def half_batch(rows: np.ndarray) -> np.ndarray:
    """The first half of each node's rows; of its positions where it has one
    row.  Dropping the rest, the mean runs over what is left."""
    if rows.shape[1] >= 2:
        return rows[:, :rows.shape[1] // 2]
    return rows[:, :, :rows.shape[2] // 2]


class ReferenceRun:
    """The plain reference of a cell, on the cell's own devices.

    ``devices``: one device (every node stacked there) or one per node.
    ``fault``: None, or ``"half_batch"`` (each node's gradient from the first
    half of its rows) or ``"no_exchange"`` (W replaced by the identity) — the
    faults the benchmark must catch, planted here for its calibration."""

    def __init__(self, cfg: Dict[str, Any], traffic: Dict[str, Any], devices,
                 numerics: Numerics = Numerics(), fault: Optional[str] = None):
        self.cfg, self.traffic, self.nx, self.fault = cfg, traffic, numerics, fault
        self.n = traffic["nodes"]
        self.mesh = Mesh(np.asarray(devices), ("node",))
        self.node_sh = NamedSharding(self.mesh, P("node"))
        w = np.eye(self.n) if fault == "no_exchange" else \
            gossip_matrix(traffic["topology"], self.n)
        self.w = jnp.asarray(w, jnp.float32)
        self.feed = Feed(cfg["vocab"], traffic["data"])
        self._grad = self._make_grad()
        self._update = self._make_update()
        n = self.n
        self._stack = jax.jit(lambda k: jax.tree.map(
            lambda l: jnp.broadcast_to(l, (n,) + l.shape), init_params(cfg, k)),
            out_shardings=self.node_sh)
        self._zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                              out_shardings=self.node_sh)
        self._minus = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b),
                              donate_argnums=0)

    # ---- gradients: each device maps over the nodes it holds, row by row
    def _make_grad(self):
        cfg, nx = self.cfg, self.nx

        def node_grad(x, tokens, labels):
            def row(acc, tl):
                loss, g = jax.value_and_grad(
                    lambda p: sequence_loss(cfg, p, tl[0], tl[1], nx))(x)
                return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

            zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, x))
            (loss, g), _ = jax.lax.scan(row, zero, (tokens, labels))
            rows = tokens.shape[0]
            return loss / rows, jax.tree.map(lambda a: a / rows, g)

        def local(xs, tokens, labels):
            return jax.lax.map(lambda a: node_grad(*a), (xs, tokens, labels))

        sharded = jax.shard_map(local, mesh=self.mesh,
                                in_specs=(P("node"), P("node"), P("node")),
                                out_specs=(P("node"), P("node")), check_vma=False)
        return jax.jit(sharded)

    # ---- AdamW, the gossip mix and the compressed DCD update, leaf by leaf
    def _make_update(self):
        t_ = self.traffic
        b1, b2, eps, wd, bits = t_["b1"], t_["b2"], t_["eps"], t_["weight_decay"], \
            t_["wire_bits"]

        def update(xs, ms, vs, grads, lr, count, key):
            flat_x, tdef = jax.tree.flatten(xs)
            out_x, out_m, out_v = [], [], []
            for i, (x, m, v, g) in enumerate(zip(
                    flat_x, jax.tree.leaves(ms), jax.tree.leaves(vs),
                    jax.tree.leaves(grads))):
                m = b1 * m + (1 - b1) * g
                v = b2 * v + (1 - b2) * g * g
                mh = m / (1 - b1 ** count)
                vh = v / (1 - b2 ** count)
                u = -lr * (mh / (jnp.sqrt(vh) + eps) + wd * x)
                mixed = jnp.einsum("ij,j...->i...", self.w, x, precision=HIGHEST)
                z = mixed + u - x
                x = x + quantize(z, jax.random.fold_in(key, i), bits)
                out_x.append(x), out_m.append(m), out_v.append(v)
            return (jax.tree.unflatten(tdef, out_x), jax.tree.unflatten(tdef, out_m),
                    jax.tree.unflatten(tdef, out_v))

        return jax.jit(update, donate_argnums=(0, 1, 2))

    def run(self, seed: int, rows: List[Dict[str, np.ndarray]]) -> Dict[str, Any]:
        """Follow the cell through ``len(rows)`` steps from ``seed``.

        ``rows[t]``: {"tokens", "labels"}: (nodes, rows per node, S) int32, the
        step's input as the feed gave it.  Returns the mean loss of each step,
        the per-leaf per-node norms of the first gradient, those of the change
        of every node's model over all the steps, and the share of the rows'
        ids that the stream does not give (``feed_mismatch``)."""
        key = jax.random.key(seed)
        xs = self._stack(key)
        ms, vs = self._zeros(xs), self._zeros(xs)
        losses, grad_norms = [], None
        noise = jax.random.fold_in(key, 0x5EED)
        differ = checked = 0
        for t, batch in enumerate(rows):
            tok = batch["tokens"]
            d, c, lab = self.feed(seed, t, tok, batch["labels"])
            differ, checked = differ + d, checked + c
            if self.fault == "half_batch":
                tok, lab = half_batch(tok), half_batch(lab)
            tok = jax.device_put(jnp.asarray(tok), self.node_sh)
            lab = jax.device_put(jnp.asarray(lab), self.node_sh)
            loss, grads = self._grad(xs, tok, lab)
            losses.append(float(jnp.mean(loss)))
            if t == 0:
                grad_norms = leaf_norms(grads)
            xs, ms, vs = self._update(xs, ms, vs, grads,
                                      jnp.float32(lr_at(self.traffic, t)),
                                      jnp.float32(t + 1), jax.random.fold_in(noise, t))
        del ms, vs, grads
        change = leaf_norms(self._minus(xs, self._stack(key)))
        return {"losses": losses, "grad_norms": grad_norms, "change_norms": change,
                "feed_mismatch": differ / checked}
