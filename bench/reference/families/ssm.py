"""Mamba-2 (arXiv:2405.21060), the ``ssm`` family of the plain reference.

Each block is pre-norm RMSNorm, the z / x / BC / dt projections, a causal
depthwise convolution of width ``d_conv`` with SiLU, the SSD map in its
quadratic ("attention") form
``y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s + D x_t``,
a gated RMSNorm (norm before the SiLU(z) gate) and the output projection.
"""
import math

import jax
import jax.numpy as jnp

from bench.reference.model import Numerics, normal, rmsnorm


def block_init(cfg, key):
    d, di, n, h = cfg["d_model"], cfg["d_inner"], cfg["d_state"], cfg["n_heads"]
    dbc = 2 * cfg["n_groups"] * n
    ks = jax.random.split(key, 6)
    return {"ln": jnp.ones((d,), jnp.float32), "mixer": {
        "wz": normal(ks[0], (d, di), 1 / math.sqrt(d)),
        "wx": normal(ks[1], (d, di), 1 / math.sqrt(d)),
        "wbc": normal(ks[2], (d, dbc), 1 / math.sqrt(d)),
        "wdt": normal(ks[3], (d, h), 1 / math.sqrt(d)),
        "conv_w": normal(ks[4], (cfg["d_conv"], di + dbc), 0.1),
        "conv_b": jnp.zeros((di + dbc,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(0.01)) * jnp.ones((h,), jnp.float32),
        "norm_g": jnp.ones((di,), jnp.float32),
        "out_proj": normal(ks[5], (di, d), 1 / math.sqrt(di)),
    }}


def ssd(x, dt, a_log, b, c, d_skip, nx: Numerics):
    """Quadratic form of the SSD map for one sequence.

    x (S, H, P), dt (S, H), b/c (S, G, N), a_log/d_skip (H,) -> y (S, H, P)."""
    S, H, _ = x.shape
    rep = H // b.shape[1]
    bh = jnp.repeat(b, rep, axis=1)                                # (S, H, N)
    ch = jnp.repeat(c, rep, axis=1)
    x, dt, bh, ch = nx.op(x), nx.op(dt), nx.op(bh), nx.op(ch)
    la = dt * (-jnp.exp(a_log))                                    # (S, H) log decay
    cs = jnp.cumsum(la, axis=0)
    seg = cs[:, None, :] - cs[None, :, :]                          # (t, s, H)
    causal = jnp.tril(jnp.ones((S, S), bool))[:, :, None]
    decay = nx.op(jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0))
    cb = nx.mm("thn,shn->tsh", ch, bh)
    y = nx.mm("tsh,shp->thp", cb * decay, x * dt[:, :, None])
    return y + x * d_skip[None, :, None]


def block(cfg, h, p, nx: Numerics):
    m = p["mixer"]
    di, n, heads, g = cfg["d_inner"], cfg["d_state"], cfg["n_heads"], cfg["n_groups"]
    S = h.shape[0]
    u = rmsnorm(h, p["ln"])
    z = nx.mm("sd,de->se", u, m["wz"])
    xbc = jnp.concatenate([nx.mm("sd,de->se", u, m["wx"]),
                           nx.mm("sd,de->se", u, m["wbc"])], axis=-1)
    dt_raw = nx.mm("sd,de->se", u, m["wdt"])
    k = m["conv_w"].shape[0]
    pad = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = sum(pad[i:i + S] * m["conv_w"][i] for i in range(k)) + m["conv_b"]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :di].reshape(S, heads, di // heads)
    b = xbc[:, di:di + g * n].reshape(S, g, n)
    c = xbc[:, di + g * n:].reshape(S, g, n)
    dt = jax.nn.softplus(dt_raw + m["dt_bias"])
    y = ssd(x, dt, m["A_log"], b, c, m["D"], nx).reshape(S, di)
    y = rmsnorm(y, m["norm_g"]) * jax.nn.silu(z)
    return h + nx.mm("se,ed->sd", y, m["out_proj"])
