"""Attention variants: GQA (full / sliding-window), MLA, cross-attention.

All functions are pure; KV caches are NamedTuple pytrees so they thread through
``jax.lax.scan`` over layers.  Decode caches come in two flavours:

* full cache      — capacity = max sequence length (decode_32k shapes);
* ring buffer     — capacity = sliding window; position ``p`` writes slot
                    ``p % window`` (long_500k shapes: O(window) memory at 524k ctx).

Keys are stored *already roped at absolute positions*; RoPE's relative property
makes ring-buffer overwrites safe.

Training / prefill self-attention (``gqa_forward``) picks its core by
``core_path``: on a TPU the Pallas flash kernel (``kernels/attention.py``),
elsewhere the dense ``_sdpa`` or, for long sequences, ``_sdpa_chunked``.
``_sdpa`` is also the plain reference the kernel is tested against.
"""
from __future__ import annotations

import collections
import dataclasses
from functools import partial
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import attention as flash
from repro.kernels import ops as kops
from repro.models.layers import COMPUTE_DTYPE, Params, apply_rope, dense, dense_init

NEG_INF = -1e9


@partial(jax.tree_util.register_dataclass, data_fields=("k", "v", "pos"), meta_fields=("window",))
@dataclasses.dataclass
class KVCache:
    k: jax.Array          # (B, C, KV, D) — roped keys
    v: jax.Array          # (B, C, KV, D)
    pos: jax.Array        # scalar int32: #tokens already in context
    window: Optional[int] = None  # STATIC: ring-buffer capacity if sliding


class MLACache(NamedTuple):
    c_kv: jax.Array       # (B, C, R)  compressed latent
    k_rope: jax.Array     # (B, C, Dr) shared roped key part
    pos: jax.Array


# ------------------------------------------------------------------ GQA

def gqa_init(key, d: int, n_heads: int, n_kv: int, head_dim: int) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, d, n_heads * head_dim),
        "wk": dense_init(kk, d, n_kv * head_dim),
        "wv": dense_init(kv, d, n_kv * head_dim),
        "wo": dense_init(ko, n_heads * head_dim, d),
    }


def _split_heads(x, n):
    B, S, _ = x.shape
    return x.reshape(B, S, n, -1)


def _sdpa(q, k, v, mask):
    """q (B,S,H,D), k/v (B,T,KV,D); GQA by head-group reshape; mask (B,1,S,T) or (S,T)."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, S, KV, G, D)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32) / jnp.sqrt(D)
    if mask.ndim == 2:
        mask = mask[None, None, None]
    else:  # (B, S, T) -> (B, 1, 1, S, T)
        mask = mask[:, None, None]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, H, v.shape[-1])


def causal_mask(S: int, window: Optional[int] = None) -> jax.Array:
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    m = j <= i
    if window is not None:
        m &= j > i - window
    return m


# At or above this many query positions, self-attention runs in the chunked
# (online-softmax / "flash"-style) formulation: O(S * CHUNK) live memory instead
# of the O(S^2) score tensor — required for the 32k prefill shapes, where the
# materialized scores would be ~17 GB/chip/layer.
FLASH_THRESHOLD = 4096
FLASH_CHUNK = 1024


def _sdpa_chunked(q, k, v, *, window: Optional[int] = None,
                  chunk: int = FLASH_CHUNK):
    """Causal self-attention with online softmax over KV chunks.

    q (B,S,H,D), k/v (B,S,KV,D), S == T (self-attention).  Scans KV chunks
    carrying (running max, running denominator, weighted accumulator); each
    chunk's contribution is masked causally (and by the sliding window if set).
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    Dv = v.shape[-1]
    G = H // KV
    scale = 1.0 / jnp.sqrt(D)
    qr = q.reshape(B, S, KV, G, D)
    pad = (-S) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = k.shape[1] // chunk
    kc = k.reshape(B, n_chunks, chunk, KV, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, KV, Dv).transpose(1, 0, 2, 3, 4)
    qpos = jnp.arange(S)

    def body(carry, inp):
        m, l, acc = carry                              # (B,KV,G,S), ..., (B,KV,G,S,D)
        kj, vj, cidx = inp
        kpos = cidx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bskgd,btkd->bkgst", qr, kj).astype(jnp.float32) * scale
        valid = kpos[None, :] <= qpos[:, None]
        if window is not None:
            valid &= kpos[None, :] > qpos[:, None] - window
        valid &= (kpos < S)[None, :]
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p.astype(q.dtype), vj).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, S), jnp.float32)
    a0 = jnp.zeros((B, KV, G, S, Dv), jnp.float32)
    # flash-style backward: recompute each chunk's probabilities instead of
    # storing the (S x chunk) residuals — without this, scan-AD materializes the
    # full attention matrix (defeating the whole point of chunking)
    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (kc, vc, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, Dv).astype(q.dtype)


# attention cores traced by ``gqa_forward``, by path (``attention_stats``)
_CORES: collections.Counter = collections.Counter()


def core_path(seq_len: int, groups: int, head_dim: int, window: Optional[int]) -> str:
    """Which implementation ``gqa_forward`` traces for its core: ``chunked``
    (``_sdpa_chunked``) from ``FLASH_THRESHOLD`` up; ``kernel`` (the Pallas
    flash kernel, ``kernels/attention.py``) for an unwindowed causal core
    whose length, query heads per key/value head and head size the kernel
    takes, where the kernels compile with Mosaic (a TPU, or a described TPU
    with ``interpret_kernels`` patched to False); else ``dense`` (``_sdpa``),
    on the CPU and on every other backend too.  All three compute the same
    attention."""
    if seq_len >= FLASH_THRESHOLD:
        return "chunked"
    if (window is None and flash.supports(seq_len, groups, head_dim)
            and jax.default_backend() in ("cpu", "tpu") and not kops.interpret_kernels()):
        return "kernel"
    return "dense"


def attention_stats() -> Dict[str, int]:
    """How many attention cores ``gqa_forward`` has traced in this process,
    by path.  A core traced inside a layer scan counts once, however many
    layers or nodes run it."""
    return {path: _CORES[path] for path in ("kernel", "dense", "chunked")}


def gqa_forward(x: jax.Array, p: Params, *, n_heads: int, n_kv: int, head_dim: int,
                theta: float, window: Optional[int] = None,
                positions: Optional[jax.Array] = None) -> jax.Array:
    """Training / prefill self-attention (causal, optionally sliding-window)."""
    B, S, _ = x.shape
    pos = positions if positions is not None else jnp.arange(S)
    q = apply_rope(_split_heads(dense(x, p["wq"]), n_heads), pos, theta)
    k = apply_rope(_split_heads(dense(x, p["wk"]), n_kv), pos, theta)
    v = _split_heads(dense(x, p["wv"]), n_kv)
    path = core_path(S, n_heads // n_kv, head_dim, window)
    _CORES[path] += 1
    with jax.named_scope("attention"):
        if path == "kernel":
            out = flash.causal_gqa(q, k, v, interpret=False)
        elif path == "chunked":
            out = _sdpa_chunked(q, k, v, window=window)
        else:
            out = _sdpa(q, k, v, causal_mask(S, window))
    return dense(out.reshape(B, S, -1), p["wo"])


def gqa_init_cache(B: int, capacity: int, n_kv: int, head_dim: int,
                   window: Optional[int] = None, dtype=COMPUTE_DTYPE) -> KVCache:
    cap = min(capacity, window) if window else capacity
    z = jnp.zeros((B, cap, n_kv, head_dim), dtype)
    return KVCache(k=z, v=z, pos=jnp.zeros((), jnp.int32), window=window)


def gqa_decode(x: jax.Array, cache: KVCache, p: Params, *, n_heads: int, n_kv: int,
               head_dim: int, theta: float) -> tuple[jax.Array, KVCache]:
    """One-token decode: x (B, 1, d)."""
    B = x.shape[0]
    t = cache.pos
    q = apply_rope(_split_heads(dense(x, p["wq"]), n_heads), t[None], theta)
    k_new = apply_rope(_split_heads(dense(x, p["wk"]), n_kv), t[None], theta)
    v_new = _split_heads(dense(x, p["wv"]), n_kv)
    cap = cache.k.shape[1]
    slot = (t % cap) if cache.window else jnp.minimum(t, cap - 1)
    k = jax.lax.dynamic_update_slice(cache.k, k_new.astype(cache.k.dtype), (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache.v, v_new.astype(cache.v.dtype), (0, slot, 0, 0))
    # valid slots: ring buffer -> everything written so far (all < window back);
    # full cache -> positions <= t.
    j = jnp.arange(cap)
    valid = (j <= jnp.minimum(t, cap - 1)) if not cache.window else (
        (j <= t) | (t >= cap))
    out = _sdpa(q, k, v, valid[None, None, :].repeat(B, 0))
    y = dense(out.reshape(B, 1, -1), p["wo"])
    return y, KVCache(k=k, v=v, pos=t + 1, window=cache.window)


# ------------------------------------------------------------------ MLA (DeepSeek-V2)

def mla_init(key, d: int, n_heads: int, *, kv_lora: int, qk_nope: int, qk_rope: int,
             v_head: int) -> Params:
    ks = jax.random.split(key, 6)
    return {
        "wq": dense_init(ks[0], d, n_heads * (qk_nope + qk_rope)),
        "wdkv": dense_init(ks[1], d, kv_lora),
        "wuk": dense_init(ks[2], kv_lora, n_heads * qk_nope),
        "wuv": dense_init(ks[3], kv_lora, n_heads * v_head),
        "wkr": dense_init(ks[4], d, qk_rope),
        "wo": dense_init(ks[5], n_heads * v_head, d),
    }


def mla_forward(x: jax.Array, p: Params, *, n_heads: int, kv_lora: int, qk_nope: int,
                qk_rope: int, v_head: int, theta: float) -> jax.Array:
    """Training/prefill MLA (uncompressed path)."""
    B, S, _ = x.shape
    pos = jnp.arange(S)
    q = dense(x, p["wq"]).reshape(B, S, n_heads, qk_nope + qk_rope)
    q_nope, q_rope = q[..., :qk_nope], q[..., qk_nope:]
    q_rope = apply_rope(q_rope, pos, theta)
    c_kv = dense(x, p["wdkv"])                                     # (B,S,R)
    k_nope = dense(c_kv, p["wuk"]).reshape(B, S, n_heads, qk_nope)
    v = dense(c_kv, p["wuv"]).reshape(B, S, n_heads, v_head)
    k_rope = apply_rope(dense(x, p["wkr"])[:, :, None, :], pos, theta)  # (B,S,1,Dr)

    if S >= FLASH_THRESHOLD:
        # chunked path: fold the shared rope key into per-head effective K
        q_eff = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_eff = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, S, n_heads, qk_rope))], axis=-1)
        with jax.named_scope("attention"):
            out = _sdpa_chunked(q_eff, k_eff, v)
        return dense(out.reshape(B, S, -1), p["wo"])

    with jax.named_scope("attention"):
        scale = 1.0 / jnp.sqrt(qk_nope + qk_rope)
        s1 = jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
        s2 = jnp.einsum("bshd,btxd->bhst", q_rope, k_rope)
        scores = (s1 + s2).astype(jnp.float32) * scale
        scores = jnp.where(causal_mask(S)[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhst,bthd->bshd", probs, v)
    return dense(out.reshape(B, S, -1), p["wo"])


def mla_init_cache(B: int, capacity: int, kv_lora: int, qk_rope: int,
                   dtype=COMPUTE_DTYPE) -> MLACache:
    return MLACache(
        c_kv=jnp.zeros((B, capacity, kv_lora), dtype),
        k_rope=jnp.zeros((B, capacity, qk_rope), dtype),
        pos=jnp.zeros((), jnp.int32),
    )


def mla_decode(x: jax.Array, cache: MLACache, p: Params, *, n_heads: int, kv_lora: int,
               qk_nope: int, qk_rope: int, v_head: int, theta: float
               ) -> tuple[jax.Array, MLACache]:
    """Absorbed-matrix decode: scores/values computed in the 512-dim latent space,
    so the per-step cost is O(S * (kv_lora + qk_rope)) per head — the whole point
    of MLA's compressed KV cache."""
    B = x.shape[0]
    t = cache.pos
    q = dense(x, p["wq"]).reshape(B, 1, n_heads, qk_nope + qk_rope)
    q_nope, q_rope = q[..., :qk_nope], q[..., qk_nope:]
    q_rope = apply_rope(q_rope, t[None], theta)

    c_new = dense(x, p["wdkv"])                                    # (B,1,R)
    kr_new = apply_rope(dense(x, p["wkr"])[:, :, None, :], t[None], theta)[:, :, 0]
    c_kv = jax.lax.dynamic_update_slice(cache.c_kv, c_new.astype(cache.c_kv.dtype), (0, t, 0))
    k_rope = jax.lax.dynamic_update_slice(cache.k_rope, kr_new.astype(cache.k_rope.dtype), (0, t, 0))

    # absorb W_uk into q: q_lat (B,H,R)
    wuk = p["wuk"].reshape(kv_lora, n_heads, qk_nope).astype(x.dtype)
    q_lat = jnp.einsum("bxhd,rhd->bhr", q_nope, wuk)
    scale = 1.0 / jnp.sqrt(qk_nope + qk_rope)
    s1 = jnp.einsum("bhr,btr->bht", q_lat, c_kv)
    s2 = jnp.einsum("bxhd,btd->bht", q_rope, k_rope)
    scores = (s1 + s2).astype(jnp.float32) * scale
    valid = jnp.arange(c_kv.shape[1]) <= t
    scores = jnp.where(valid[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bht,btr->bhr", probs, c_kv)                # (B,H,R)
    wuv = p["wuv"].reshape(kv_lora, n_heads, v_head).astype(x.dtype)
    out = jnp.einsum("bhr,rhd->bhd", o_lat, wuv).reshape(B, 1, -1)
    return dense(out, p["wo"]), MLACache(c_kv=c_kv, k_rope=k_rope, pos=t + 1)


# ------------------------------------------------------------------ cross-attention

def cross_init(key, d: int, n_heads: int, head_dim: int) -> Params:
    return gqa_init(key, d, n_heads, n_heads, head_dim)


def cross_forward(x: jax.Array, enc: jax.Array, p: Params, *, n_heads: int,
                  head_dim: int) -> jax.Array:
    """Decoder->encoder attention; no mask (encoder fully visible), no RoPE."""
    B, S, _ = x.shape
    T = enc.shape[1]
    q = _split_heads(dense(x, p["wq"]), n_heads)
    k = _split_heads(dense(enc.astype(x.dtype), p["wk"]), n_heads)
    v = _split_heads(dense(enc.astype(x.dtype), p["wv"]), n_heads)
    out = _sdpa(q, k, v, jnp.ones((S, T), bool))
    return dense(out.reshape(B, S, -1), p["wo"])
