"""A Llama-style decoder (Granite-3.0), the ``dense`` family of the plain
reference: RMSNorm, grouped-query attention with rotate-half RoPE and a
causal softmax, SwiGLU MLP.
"""
import math

import jax
import jax.numpy as jnp

from bench.reference.model import Numerics, normal, rmsnorm


def block_init(cfg, key):
    d, h, kv, ff = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], cfg["d_ff"]
    hd = cfg["head_dim"]
    ka, kf = jax.random.split(key)
    kq, kk, kvv, ko = jax.random.split(ka, 4)
    k1, k2, k3 = jax.random.split(kf, 3)
    return {
        "ln1": jnp.ones((d,), jnp.float32),
        "attn": {"wq": normal(kq, (d, h * hd), 1 / math.sqrt(d)),
                 "wk": normal(kk, (d, kv * hd), 1 / math.sqrt(d)),
                 "wv": normal(kvv, (d, kv * hd), 1 / math.sqrt(d)),
                 "wo": normal(ko, (h * hd, d), 1 / math.sqrt(h * hd))},
        "ln2": jnp.ones((d,), jnp.float32),
        "ffn": {"wi": normal(k1, (d, ff), 1 / math.sqrt(d)),
                "wg": normal(k2, (d, ff), 1 / math.sqrt(d)),
                "wo": normal(k3, (ff, d), 1 / math.sqrt(ff))},
    }


def rope(x, theta):
    """Rotate-half RoPE over the last dim of x (S, heads, hd)."""
    S, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs        # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def block(cfg, h, p, nx: Numerics):
    a, f = p["attn"], p["ffn"]
    heads, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    S = h.shape[0]
    u = rmsnorm(h, p["ln1"])
    q = rope(nx.mm("sd,de->se", u, a["wq"]).reshape(S, heads, hd), cfg["rope_theta"])
    k = rope(nx.mm("sd,de->se", u, a["wk"]).reshape(S, kv, hd), cfg["rope_theta"])
    v = nx.mm("sd,de->se", u, a["wv"]).reshape(S, kv, hd)
    # query head j attends with key/value head j // (heads // kv)
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)
    scores = nx.mm("thd,shd->hts", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = nx.mm("hts,shd->thd", probs, v).reshape(S, heads * hd)
    h = h + nx.mm("se,ed->sd", att, a["wo"])
    u = rmsnorm(h, p["ln2"])
    mlp = jax.nn.silu(nx.mm("sd,df->sf", u, f["wg"])) * nx.mm("sd,df->sf", u, f["wi"])
    return h + nx.mm("sf,fd->sd", mlp, f["wo"])
