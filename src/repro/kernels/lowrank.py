"""Pallas fused block-matmul kernels for the low-rank (PowerGossip) wire format.

Two kernels, one per wire direction:

* ``lowrank_project_2d`` — the encode "subtract-project-pack" matmul stage:
  ``P = M @ V`` for a (m, n) leaf view against the (n, r) warm/right factor.
  The subtraction (model difference) happens upstream in the round fn and the
  "pack" is free — the rank-r factors ARE the payload, already 32/r·(m+n)/(m·n)
  of the dense leaf, so no bit-packing stage follows.
* ``lowrank_axpy_2d`` — the decode "factor-matmul-accumulate" receive side:
  ``acc_weight * acc + weight * (P @ V^T)``, reconstructing the rank-r leaf
  and folding it into the mix accumulator in the same VMEM pass, so the dense
  fp32 reconstruction never round-trips through HBM.  Both weights ride the
  same (2,) scalar operand as the quantized/sparse/sign axpy kernels, so
  traced mixing weights drive this kernel too.

Bit-identity contract (vs kernels/ref.py): the grids tile only output rows
and, in the axpy kernel, output columns — the r- and n-contractions are never
split — and each tile issues a single
``dot_general`` with ``preferred_element_type=f32`` using the exact dimension
numbers of the oracle (``_factor_matmul`` is literally shared).  Every output
element therefore reduces over the same operands in the same order as the
oracle's one big dot, and the parity tests assert exact word equality, not
atol.  Padding rows (``_pad_rows``) adds all-zero rows whose outputs are
sliced off; a zero row's dot is exact zero, so padding cannot perturb the
kept rows.

One carve-out: at ``rank == 1`` the contraction is a single multiply, which
XLA rewrites to an elementwise op and then FMA-contracts into the axpy
epilogue when compiling the oracle — one rounding where the interpreted
kernel does two — so the last ulp can differ.  Word-equality is claimed (and
tested) for rank >= 2, where the dot lowers as a genuine reduction on both
paths; rank-1 still holds to 1 ulp, well inside the differential tier's
tolerance.

TPU note: the rank axis (r = 2..8 typically) is far below the 128-lane tile,
so Mosaic pads the (bm, r) and (bn, r) factor tiles to 128 lanes; the block
pickers count that padding against the VMEM budget.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.quant import _pad_rows, _padded_row_bytes, _pick_block_rows
from repro.kernels.ref import F32_MATMUL, _factor_matmul

# Widest output column tile of the axpy kernel.  The (bn, r) right factor
# pads to 128 lanes in VMEM, so a whole vocabulary-wide factor would not fit.
_MAX_BLOCK_COLS = 2048


def _pick_block_cols(n: int) -> int:
    """Largest multiple of 128 that divides ``n`` and is at most
    ``_MAX_BLOCK_COLS``; ``n`` itself when it is not lane-aligned (the
    interpret-mode tests' small leaves)."""
    if n % 128:
        return n
    return max(c for c in range(128, min(n, _MAX_BLOCK_COLS) + 1, 128)
               if n % c == 0)


def _lowrank_project_kernel(m_ref, v_ref, out_ref):
    # full (n, r) right factor in VMEM, (bm, n) leaf rows per grid step:
    # one dot per tile, contraction unsplit => oracle-exact.
    out_ref[...] = jax.lax.dot_general(
        m_ref[...], v_ref[...], (((1,), (0,)), ((), ())),
        precision=F32_MATMUL, preferred_element_type=jnp.float32)


def _lowrank_axpy_kernel(weights_ref, p_ref, v_ref, acc_ref, out_ref):
    # weights_ref = [acc_weight, weight], exactly like the quant/sparse/sign
    # axpy kernels; dot contracts the shared rank axis (P @ V^T) without
    # materializing the transpose — same dimension numbers as the oracle.
    aw = weights_ref[0]
    w = weights_ref[1]
    out_ref[...] = aw * acc_ref[...] + w * _factor_matmul(p_ref[...], v_ref[...])


def lowrank_project_2d(m: jax.Array, v: jax.Array, *,
                       interpret: bool = False) -> jax.Array:
    """Fused projection ``P = M @ V`` of a (rows, n) f32 leaf view onto the
    (n, r) right factor.  Returns (rows, r) f32, exactly equal to
    :func:`repro.kernels.ref.lowrank_project_2d_ref`."""
    rows, n = m.shape
    n2, r = v.shape
    assert n == n2, (m.shape, v.shape)
    bm = _pick_block_rows(rows, [(n, jnp.float32), (r, jnp.float32)],
                          temps=[r],
                          fixed_bytes=2 * n2 * _padded_row_bytes(r, jnp.float32))
    (m,), pad = _pad_rows([m], bm, rows)
    grid = ((rows + pad) // bm,)
    out = pl.pallas_call(
        _lowrank_project_kernel,
        name="lowrank_project",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((n2, r), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, r), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, r), jnp.float32),
        interpret=interpret,
    )(m.astype(jnp.float32), v.astype(jnp.float32))
    return out[:rows] if pad else out


def lowrank_axpy_2d(p: jax.Array, v: jax.Array, acc: jax.Array, *,
                    weight, acc_weight=1.0,
                    interpret: bool = False) -> jax.Array:
    """Fused factor-matmul + accumulate:
    ``acc_weight * acc + weight * (P @ V^T)``.

    The low-rank receive side of a gossip round: (rows, r) left factor x
    (n, r) right factor reconstruct the rank-r leaf directly into the (rows,
    n) mix accumulator — the dense fp32 reconstruction never exists in HBM.
    Exactly equal to :func:`repro.kernels.ref.lowrank_axpy_2d_ref`."""
    rows, r = p.shape
    n, r2 = v.shape
    assert r == r2, (p.shape, v.shape)
    assert acc.shape == (rows, n), (acc.shape, (rows, n))
    bn = _pick_block_cols(n)
    bm = _pick_block_rows(rows, [(r, jnp.float32), (bn, jnp.float32),
                                 (bn, jnp.float32)], temps=[bn],
                          fixed_bytes=2 * bn * _padded_row_bytes(r, jnp.float32))
    (p, acc), pad = _pad_rows([p, acc], bm, rows)
    grid = ((rows + pad) // bm, n // bn)
    weights = jnp.stack([jnp.asarray(acc_weight, jnp.float32).reshape(()),
                         jnp.asarray(weight, jnp.float32).reshape(())])
    out = pl.pallas_call(
        _lowrank_axpy_kernel,
        name="lowrank_axpy",
        grid=grid,
        in_specs=[
            pl.BlockSpec((2,), lambda i, j: (0,)),
            pl.BlockSpec((bm, r), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, r2), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, n), jnp.float32),
        interpret=interpret,
    )(weights, p.astype(jnp.float32), v.astype(jnp.float32),
      acc.astype(jnp.float32))
    return out[:rows] if pad else out
