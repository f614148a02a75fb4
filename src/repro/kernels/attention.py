"""Pallas TPU kernel: causal GQA self-attention, flash-style, forward and backward.

``causal_gqa(q, k, v)`` computes what ``models.attention._sdpa`` computes for a
causal mask, for q ``(B, S, H, D)`` and k, v ``(B, S, KV, D)`` in bfloat16 with
``H = KV * G``, without ever writing the ``S x S`` scores or probabilities to
HBM.  The sequence is cut into square ``(b, b)`` tiles (``b = block_size(...)``).
A grid step takes one key/value head, one query tile and one key tile, and
the ``G`` query heads that share that key/value head, so each key and value
tile is read once for the whole group and never copied per head.

* Only the tiles on or below the diagonal are visited: the grid's last axis
  walks a table of the ``n (n + 1) / 2`` causal (query tile, key tile) pairs,
  prefetched into SMEM, so a tile wholly above the diagonal costs neither a
  step nor a DMA.  Only the diagonal tile is masked.
* The forward keeps each tile's scores in VMEM in float32 and folds them into
  a running max and sum (online softmax); the unnormalized probabilities are
  cast to bfloat16 for the product with V, as ``_sdpa`` casts its
  probabilities.  It saves the rows' log-sum-exp, one float32 per query.
* The backward recomputes each tile's probabilities from that log-sum-exp:
  one kernel walks key tiles outer and accumulates dK and dV over the query
  tiles below and the group's heads; another walks query tiles outer and
  accumulates dQ.  ``rowsum(dO * O)`` comes from XLA beforehand.

The scale ``1 / sqrt(D)`` goes into q where it is a power of two (D = 64:
the bfloat16 product is exact), else into the float32 scores inside the
kernels.  The tile side is the largest of ``BLOCKS`` whose buffers, counted by
``vmem_bytes`` from the query heads per group, D and the tile, fit Mosaic's
scoped VMEM; shapes no tile fits (``supports``) stay on the jnp paths of
``models/attention.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# tile sides, in order of preference: the first that divides S and fits
# ``VMEM_BUDGET`` is used
BLOCKS = (512, 256, 128)
HEAD_DIMS = (64, 128)
# the largest group of query heads per key/value head checked against Mosaic
# (every group up to it compiles at the tile ``block_size`` picks); at 32 the
# unrolled loop over the group takes more than ``vmem_bytes`` counts
MAX_GROUPS = 16
# Mosaic's default scoped-VMEM limit on a v5e; the kernels ask for no more
VMEM_BUDGET = 16 * 2**20
MASK_VALUE = -1e9    # as models.attention.NEG_INF: every row keeps its diagonal


def vmem_bytes(groups: int, head_dim: int, block: int) -> int:
    """Scoped VMEM that the largest of the three kernels takes, as counted
    here: every block of q, o, dO, dQ (G, b, D) and of k, v, dK, dV (b, D)
    in bfloat16 and double-buffered, D padded to the 128 lanes; two (G, b)
    float32 row blocks (log-sum-exp, rowsum(dO * O)), G padded to 8
    sublanes; each kernel's scratch; and two float32 (b, b) score tiles.
    For tiles up to 512 and up to ``MAX_GROUPS`` heads a group this reads at
    least what Mosaic asks for, to within 1/8 MiB; at 1024 it reads less,
    which is one reason ``BLOCKS`` stops at 512."""
    d = -(-head_dim // LANES) * LANES
    group = 2 * groups * block * d * 2
    pair = 2 * 2 * block * d * 2
    rows = 2 * 2 * (-(-groups // SUBLANES) * SUBLANES) * block * 4
    tiles = 2 * block * block * 4
    fwd = 2 * group + pair + groups * block * (2 * LANES + d) * 4   # m, l, acc
    dkv = 2 * group + 2 * pair + 2 * block * d * 4                  # dK, dV acc
    dq = 3 * group + pair + groups * block * d * 4                  # dQ acc
    return max(fwd, dkv, dq) + rows + tiles


def block_size(seq_len: int, groups: int, head_dim: int) -> int | None:
    """The tile side for a causal self-attention of this length, query heads
    per key/value head and head size: the first of ``BLOCKS`` that divides S
    and whose kernels fit ``VMEM_BUDGET``; None where the kernel does not
    take the shape (D not in ``HEAD_DIMS``, more than ``MAX_GROUPS`` heads a
    group, or S off the 128-lane tiles)."""
    if head_dim not in HEAD_DIMS or groups > MAX_GROUPS:
        return None
    return next((b for b in BLOCKS if seq_len % b == 0
                 and vmem_bytes(groups, head_dim, b) <= VMEM_BUDGET), None)


def supports(seq_len: int, groups: int, head_dim: int) -> bool:
    return block_size(seq_len, groups, head_dim) is not None


def _split_scale(head_dim: int) -> tuple[float, float]:
    """(q's factor, the float32 scores' factor), whose product is 1/sqrt(D).
    q takes the scale where it is a power of two, which bfloat16 multiplies
    exactly (D = 64: 1/8); else the kernels scale the float32 scores and
    their gradients, so q is rounded no second time."""
    scale = 1.0 / math.sqrt(head_dim)
    return (scale, 1.0) if math.frexp(scale)[0] == 0.5 else (1.0, scale)


def _pairs(n: int, *, kv_major: bool) -> tuple[np.ndarray, np.ndarray]:
    """(query tile, key tile) of every causal pair of an n x n tiling: query
    tiles outer (each row's diagonal last), or key tiles outer (each
    column's last query tile last)."""
    if kv_major:
        pairs = [(i, j) for j in range(n) for i in range(j, n)]
    else:
        pairs = [(i, j) for i in range(n) for j in range(i + 1)]
    qi, kj = zip(*pairs)
    return np.asarray(qi, np.int32), np.asarray(kj, np.int32)


def _causal(s, *, q_rows: bool):
    """``s`` of the diagonal tile with the keys after each query masked;
    ``q_rows``: queries along rows (else along columns)."""
    r = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(c <= r if q_rows else r <= c, s, MASK_VALUE)


def _nt(a, b):
    """a @ b.T, accumulated in float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _scaled(x, scale: float):
    """x * scale in float32; nothing to trace where the scale went into q."""
    return x if scale == 1.0 else x * scale


def _nn(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _on_diagonal(step, qi_ref, kj_ref):
    """Run ``step(masked)`` for this grid step's pair, masked only on the
    diagonal tile."""
    p = pl.program_id(2)
    diagonal = qi_ref[p] == kj_ref[p]

    @pl.when(diagonal)
    def _():
        step(True)

    @pl.when(jnp.logical_not(diagonal))
    def _():
        step(False)


# ------------------------------------------------------------------ forward

def _fwd_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, groups: int, block: int, scale: float):
    p = pl.program_id(2)

    @pl.when(kj_ref[p] == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked):
        k, v = k_ref[...], v_ref[...]
        for g in range(groups):
            s = _scaled(_nt(q_ref[g], k), scale)                  # (b, b) f32
            if masked:
                s = _causal(s, q_rows=True)
            m_prev = m_scr[g]                                     # (b, 128)
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            e = jnp.exp(s - jnp.tile(m_next, (1, block // LANES)))
            alpha = jnp.exp(m_prev - m_next)
            l_scr[g] = alpha * l_scr[g] + jnp.sum(e, axis=1, keepdims=True)
            m_scr[g] = m_next
            acc_scr[g] = (alpha[:, :1] * acc_scr[g]
                          + _nn(e.astype(v.dtype), v))

    _on_diagonal(step, qi_ref, kj_ref)

    @pl.when(kj_ref[p] == qi_ref[p])
    def _():
        for g in range(groups):
            l = l_scr[g]
            o_ref[g] = (acc_scr[g] / l[:, :1]).astype(o_ref.dtype)
            # (b, 128) lane-replicated -> one lane-major row of b
            lse_ref[g:g + 1, :] = (m_scr[g] + jnp.log(l)).T[:1]


def _forward(q, k, v, *, block: int, scale: float, interpret: bool):
    """q (B, KV, G, S, D), k/v (B, KV, S, D) -> o like q, and the rows'
    log-sum-exp (B, KV, G, S) f32 of the scores times ``scale``."""
    B, KV, G, S, D = q.shape
    qi, kj = _pairs(S // block, kv_major=False)
    q_spec = pl.BlockSpec((None, None, G, block, D),
                          lambda b, h, p, qi, kj: (b, h, 0, qi[p], 0))
    kv_spec = pl.BlockSpec((None, None, block, D),
                           lambda b, h, p, qi, kj: (b, h, kj[p], 0))
    lse_spec = pl.BlockSpec((None, None, G, block),
                            lambda b, h, p, qi, kj: (b, h, 0, qi[p]))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, groups=G, block=block, scale=scale),
        name="flash_attention_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, KV, len(qi)),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[q_spec, lse_spec],
            scratch_shapes=[pltpu.VMEM((G, block, LANES), jnp.float32),
                            pltpu.VMEM((G, block, LANES), jnp.float32),
                            pltpu.VMEM((G, block, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, KV, G, S), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(qi), jnp.asarray(kj), q, k, v)


# ------------------------------------------------------------------ backward

def _dkv_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, groups: int, last: int,
                scale: float):
    p = pl.program_id(2)

    @pl.when(qi_ref[p] == kj_ref[p])
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked):
        k, v = k_ref[...], v_ref[...]
        for g in range(groups):
            q, do = q_ref[g], do_ref[g]
            s = _scaled(_nt(k, q), scale)                 # (keys, queries) f32
            if masked:
                s = _causal(s, q_rows=False)
            e = jnp.exp(s - lse_ref[g:g + 1, :])
            dv_scr[...] += _nn(e.astype(do.dtype), do)
            ds = e * (_nt(v, do) - di_ref[g:g + 1, :])
            dk_scr[...] += _nn(ds.astype(q.dtype), q)

    _on_diagonal(step, qi_ref, kj_ref)

    @pl.when(qi_ref[p] == last)
    def _():
        dk_ref[...] = _scaled(dk_scr[...], scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
               dq_ref, dq_scr, *, groups: int, scale: float):
    p = pl.program_id(2)

    @pl.when(kj_ref[p] == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def step(masked):
        k, v = k_ref[...], v_ref[...]
        for g in range(groups):
            s = _scaled(_nt(q_ref[g], k), scale)          # (queries, keys) f32
            if masked:
                s = _causal(s, q_rows=True)
            e = jnp.exp(s - lse_ref[g][:, None])
            ds = e * (_nt(do_ref[g], v) - di_ref[g][:, None])
            dq_scr[g] += _nn(ds.astype(k.dtype), k)

    _on_diagonal(step, qi_ref, kj_ref)

    @pl.when(kj_ref[p] == qi_ref[p])
    def _():
        dq_ref[...] = _scaled(dq_scr[...], scale).astype(dq_ref.dtype)


def _backward(q, k, v, o, lse, do, *, block: int, scale: float, interpret: bool):
    B, KV, G, S, D = q.shape
    n = S // block
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    q_spec = pl.BlockSpec((None, None, G, block, D),
                          lambda b, h, p, qi, kj: (b, h, 0, qi[p], 0))
    kv_spec = pl.BlockSpec((None, None, block, D),
                           lambda b, h, p, qi, kj: (b, h, kj[p], 0))
    row_spec = pl.BlockSpec((None, None, G, block),
                            lambda b, h, p, qi, kj: (b, h, 0, qi[p]))
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    qi, kj = _pairs(n, kv_major=True)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, groups=G, last=n - 1, scale=scale),
        name="flash_attention_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, KV, len(qi)), in_specs=in_specs,
            out_specs=[kv_spec, kv_spec],
            scratch_shapes=[pltpu.VMEM((block, D), jnp.float32)] * 2),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype)] * 2,
        compiler_params=params,
        interpret=interpret,
    )(jnp.asarray(qi), jnp.asarray(kj), q, k, v, do, lse, di)

    qi, kj = _pairs(n, kv_major=False)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, groups=G, scale=scale),
        name="flash_attention_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, KV, len(qi)), in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((G, block, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=params,
        interpret=interpret,
    )(jnp.asarray(qi), jnp.asarray(kj), q, k, v, do, lse, di)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, block, scale, interpret):
    return _forward(q, k, v, block=block, scale=scale, interpret=interpret)[0]


def _flash_fwd(q, k, v, block, scale, interpret):
    o, lse = _forward(q, k, v, block=block, scale=scale, interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(block, scale, interpret, res, do):
    return _backward(*res, do, block=block, scale=scale, interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def causal_gqa(q: jax.Array, k: jax.Array, v: jax.Array, *, interpret: bool) -> jax.Array:
    """Causal self-attention: q (B, S, H, D), k/v (B, S, KV, D) -> (B, S, H, D).

    Query head ``h`` reads key/value head ``h // (H // KV)``, as ``_sdpa``'s
    head-group reshape does.  The tile side is ``block_size(S, H // KV, D)``.
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    block = block_size(S, H // KV, D)
    assert block and k.shape == v.shape == (B, S, KV, D), (q.shape, k.shape)
    q_scale, s_scale = _split_scale(D)
    if q_scale != 1.0:
        q = (q * q_scale).astype(q.dtype)
    qg = q.reshape(B, S, KV, H // KV, D).transpose(0, 2, 3, 1, 4)
    kt, vt = (x.transpose(0, 2, 1, 3) for x in (k, v))
    o = _flash(qg, kt, vt, block, s_scale, interpret)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, S, H, D)
