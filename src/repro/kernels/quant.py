"""Pallas TPU kernels: fused per-block scaling + stochastic quantization + bit-pack.

This is the compute hot-spot the paper's technique adds to the training step: every
gossip round quantizes the full model-delta (up to tens of GB across the node).  Two
kernel families share one VMEM pass over the tensor:

* ``quantize_2d``      — scale = max|block| -> normalize -> stochastic round ->
  **int8** codes (the ``bits=8`` container).
* ``quantize_pack_2d`` — same pipeline, then **bit-packs** the codes into
  ``uint32`` words before they ever leave VMEM — any width 2..7, so the HBM
  write (and the wire payload built from it) is exactly ``bits``/32 of fp32 —
  the paper's compression ratio (including its 3-bit sweet spot) as actual
  bytes, not a formula.

Receive side mirrors it: ``unpack_dequant_2d`` (unpack -> dequantize) and
``unpack_dequant_axpy`` (unpack -> dequantize -> ``acc + w * value``), which
fuses the neighbor-mix accumulation so the reconstructed fp32 neighbor tensor is
never materialized in HBM before the gossip average.  It addresses a leaf
through its own row view ``(rows, last)``, one grid step per (row tile,
block), so the leaf is never copied into a ``(rows * nblk, block)`` view.
The axpy weight is a scalar *operand* (not a compile-time constant), so
traced mixing weights — ECD's 2/s blend — drive the same kernel.

Packed wire format v2 — bit-exact stream layout (shared with kernels/ref.py
and ``QuantWire`` in distributed/wire.py; all three produce identical
words, and it is bit-identical to the v1 planar format for bits in {2, 4}):

    cpg = lcm(bits, 32) // bits   # codes per group  (8 @4b, 16 @2/6b, 32 @3/5/7b)
    wpg = lcm(bits, 32) // 32     # words per group  (1 @2/4b, 3 @3/6b, 5, 7)
    G   = cols // cpg             # groups per row of ``cols`` codes
    u   = code + levels + 1       # bias signed [-L, L] -> unsigned [1, 2^bits - 1]

Group ``g`` packs the ``cpg`` codes ``{u[j*G + g] : j}`` as one contiguous
``cpg * bits``-bit little-endian stream filling its ``wpg`` words exactly —
code ``j`` occupies stream bits ``[j*bits, (j+1)*bits)``, **straddling a word
boundary** whenever ``32 % bits != 0``:

    w, off   = divmod(j * bits, 32)
    word[w]     |= u_j << off                 # low piece (high bits drop, u32)
    word[w + 1] |= u_j >> (32 - off)          # carry, iff off + bits > 32

so a row of ``cols`` codes ships ``cols * bits / 32 = ceil`` words — 3-bit
is 3.0 wire bits/element + scale, not an 8-bit container.  Rows are laid out
word-plane-major (``packed[:, w*G:(w+1)*G]`` is word ``w`` of every group):
both the group slices ``u[j*G:(j+1)*G]`` and the word planes are static
contiguous lane slices, so pack/unpack never needs a strided lane gather
(which the TPU VPU cannot do cheaply).  ``cols`` must be a multiple of
``cpg``; ``cols % 128 == 0`` (the lane-width contract below) guarantees it.
Tail handling lives one level up: callers pad the last dim to a whole block
(``aligned_block`` rounds the block to whole groups) and slice ``[:n]`` after
dequantize, so ragged tails never reach the kernels; ``unpack_dequant_axpy``
alone takes a ragged last block as a masked edge block.

TPU adaptation notes (vs. a CUDA quantizer):
* Blocks are *rows* of a (rows, block_size) view with block_size a multiple of 128
  (lane width); row tiles are multiples of 8 (sublane) — MXU/VPU aligned.
* Randomness is a counter-based PCG hash of (element index XOR seed) computed with
  VPU integer ops — stateless, reproducible, identical in interpret mode on CPU
  (``pltpu.prng_random_bits`` has no CPU lowering, and a counter-based generator
  vectorizes better than threading PRNG state through the grid anyway).
* The row-max reduction stays in VMEM registers; scales land in a (rows, 1) output.
* Pack/unpack is shift-and-OR over the biased codes — pure VPU integer ops on
  lane-aligned slices, fused into the same grid step as the quantize/dequantize.

A third kernel family ships the *sparse* wire format (fixed-capacity top-k /
rescaled random-k: ``k = ceil(p * cols)`` values + their block-local indices
packed to ``idx_bits_for(cols)`` bits via the same stream layout, raw unsigned
fields, no sign bias):

* ``sparse_select_pack_2d``   — selection (iterative first-occurrence argmax,
  ``k`` unrolled row reductions: descending key, ties to the smaller index —
  the exact order of the stable-argsort oracle in kernels/ref.py), gather, and
  index bit-pack in one VMEM pass; only ``k`` values + ``~k*idx_bits`` index
  bits leave the kernel.
* ``sparse_unpack_scatter_2d`` / ``sparse_scatter_axpy_2d`` — the receive
  side: unpack the index stream and scatter each value into its lane via
  ``k`` unrolled lane-compare selects (``out[lane] += where(lane == idx_i,
  w*val_i, 0)``) — a dense one-hot contraction, O(k*cols) VPU work, chosen
  over a real scatter because the TPU VPU has no cheap strided lane store;
  the axpy variant folds ``acc_weight * acc`` into the same pass exactly like
  ``unpack_dequant_axpy``.  Indices within a row are duplicate-free, so
  every lane receives at most one value and the accumulation order cannot
  change the result.

Validated against kernels/ref.py (pure jnp, same hash, same word layout) in
tests/test_kernels.py and tests/test_wire_format.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PACKABLE_BITS = (2, 3, 4, 5, 6, 7)

SPARSE_MODES = ("randk", "topk")

SIGN_SCALE_MODES = ("mean", "l2")


def stream_geometry(bits: int) -> tuple:
    """(codes per group, words per group) of the v2 stream layout — the single
    source of truth for the group geometry (kernels/ref.py re-exports it); see
    the module docstring."""
    l = math.lcm(bits, 32)
    return l // bits, l // 32


def idx_bits_for(block: int) -> int:
    """Bits needed to address one element of a ``block``-wide row (>= 1)."""
    return max(1, (block - 1).bit_length())


def sparse_geometry(block: int, p: float) -> tuple:
    """(k, idx_bits, kpad, words) of the fixed-capacity sparse wire format.

    ``k = ceil(p * block)`` values are kept per block; their block-local
    indices pack to ``idx_bits = ceil(log2(block))`` bits each via the stream
    layout above, padded to ``kpad`` (a whole number of stream groups,
    zero-filled tail) so the index container is ``words`` whole uint32 words.
    The payload is fixed-capacity — the same (k, words) for every input — so
    the codec is SPMD-friendly: no data-dependent shapes ever reach the
    compiled program.
    """
    k = min(block, max(1, math.ceil(p * block)))
    w = idx_bits_for(block)
    cpg, _ = stream_geometry(w)
    kpad = -(-k // cpg) * cpg
    return k, w, kpad, kpad * w // 32


def pcg_hash(x: jax.Array) -> jax.Array:
    """PCG-XSH-RR-style 32-bit mix; input/output uint32. Pure VPU integer ops."""
    x = x.astype(jnp.uint32)
    state = x * jnp.uint32(747796405) + jnp.uint32(2891336453)
    word = ((state >> ((state >> jnp.uint32(28)) + jnp.uint32(4))) ^ state) * jnp.uint32(277803737)
    return (word >> jnp.uint32(22)) ^ word


def uniform_from_hash(idx: jax.Array, seed: jax.Array) -> jax.Array:
    """Deterministic U[0,1) from a per-element counter and a scalar seed."""
    bits = pcg_hash(idx ^ seed.astype(jnp.uint32))
    # 24-bit value, exact in int32 and f32; Mosaic has no uint32 -> f32 cast
    return (bits >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32) \
        * jnp.float32(1.0 / (1 << 24))


def _stochastic_codes(x, seed_ref, pid, *, levels: int, block_rows: int, cols: int):
    """Shared head of both quantize kernels: scale, normalize, stochastic round.

    Returns (float codes in [-levels, levels], per-row scale).
    """
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    safe = jnp.where(scale > 0.0, scale, 1.0)
    v = x * (jnp.float32(levels) / safe)

    rows = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 0) + (pid * block_rows).astype(jnp.uint32)
    lanes = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 1)
    idx = rows * jnp.uint32(cols) + lanes
    u = uniform_from_hash(idx, seed_ref[0])

    floor = jnp.floor(v)
    q = floor + (u < (v - floor)).astype(jnp.float32)
    return jnp.clip(q, -levels, levels), scale


def _quant_kernel(seed_ref, x_ref, codes_ref, scale_ref, *, levels: int, block_rows: int, cols: int):
    x = x_ref[...].astype(jnp.float32)
    q, scale = _stochastic_codes(x, seed_ref, pl.program_id(0),
                                 levels=levels, block_rows=block_rows, cols=cols)
    codes_ref[...] = q.astype(jnp.int8)
    scale_ref[...] = scale


def _quant_pack_kernel(seed_ref, x_ref, packed_ref, scale_ref, *,
                       bits: int, levels: int, block_rows: int, cols: int):
    x = x_ref[...].astype(jnp.float32)
    q, scale = _stochastic_codes(x, seed_ref, pl.program_id(0),
                                 levels=levels, block_rows=block_rows, cols=cols)
    # biased, in [1, 2^bits-1]; via int32 (Mosaic has no f32 -> uint32 cast)
    u = (q + jnp.float32(levels + 1)).astype(jnp.int32).astype(jnp.uint32)
    cpg, wpg = stream_geometry(bits)
    g = cols // cpg
    words = [jnp.zeros(u.shape[:-1] + (g,), jnp.uint32) for _ in range(wpg)]
    for j in range(cpg):
        w, off = divmod(j * bits, 32)
        uj = u[:, j * g:(j + 1) * g]
        words[w] = words[w] | (uj << jnp.uint32(off))
        if off + bits > 32:
            words[w + 1] = words[w + 1] | (uj >> jnp.uint32(32 - off))
    for w in range(wpg):
        packed_ref[:, w * g:(w + 1) * g] = words[w]
    scale_ref[...] = scale


def _dequant_kernel(codes_ref, scale_ref, out_ref, *, levels: int):
    q = codes_ref[...].astype(jnp.float32)
    # multiply by the precomputed reciprocal: XLA rewrites div-by-constant to a
    # reciprocal multiply anyway, so this IS the canonical dequant semantics —
    # kernels/ref.py and both codecs use the identical formulation (bit-exact)
    out_ref[...] = q * (scale_ref[...] * jnp.float32(1.0 / levels))


def _unpacked_planes(word, *, bits: int, levels: int):
    """Yield (code plane index j, signed int32 codes) for a packed word array."""
    cpg, wpg = stream_geometry(bits)
    g = word.shape[-1] // wpg
    mask = jnp.uint32((1 << bits) - 1)
    planes = [word[:, w * g:(w + 1) * g] for w in range(wpg)]
    for j in range(cpg):
        w, off = divmod(j * bits, 32)
        v = planes[w] >> jnp.uint32(off)
        if off + bits > 32:
            v = v | (planes[w + 1] << jnp.uint32(32 - off))
        yield j, (v & mask).astype(jnp.int32) - (levels + 1)


def _unpack_dequant_kernel(packed_ref, scale_ref, out_ref, *, bits: int, levels: int):
    word = packed_ref[...]
    inv = scale_ref[...] * jnp.float32(1.0 / levels)
    cpg, wpg = stream_geometry(bits)
    g = word.shape[-1] // wpg
    for j, u in _unpacked_planes(word, bits=bits, levels=levels):
        out_ref[:, j * g:(j + 1) * g] = u.astype(jnp.float32) * inv


def _unpack_dequant_axpy_kernel(weights_ref, packed_ref, scale_ref, acc_ref, out_ref, *,
                                bits: int, levels: int):
    # weights_ref = [acc_weight, weight]: out = acc_weight*acc + weight*dequant.
    # Scaling the accumulator here (rather than pre-scaling it in HBM) keeps
    # ECD's (1-2/s)*tilde + (2/s)*decode update a genuine single VMEM pass.
    # Grid (row tile, block b): the row tile's codes hold a group of up to 8
    # blocks (one sublane tile) and its scales every block, so block b's
    # words are a strided sublane load and its scale the one lane of b (a
    # max over -inf elsewhere is exact).
    scale = scale_ref[...]
    if packed_ref.ndim == 3:
        b = pl.program_id(1)
        word = packed_ref[:, b % packed_ref.shape[1], :]
        lane = jax.lax.broadcasted_iota(jnp.int32, scale.shape, 1)
        scale = jnp.max(jnp.where(lane == b, scale, -jnp.inf), axis=1, keepdims=True)
    else:
        word = packed_ref[...]
    aw = weights_ref[0]
    inv = scale * (weights_ref[1] * jnp.float32(1.0 / levels))
    cpg, wpg = stream_geometry(bits)
    g = word.shape[-1] // wpg
    for j, u in _unpacked_planes(word, bits=bits, levels=levels):
        out_ref[:, j * g:(j + 1) * g] = (
            aw * acc_ref[:, j * g:(j + 1) * g] + u.astype(jnp.float32) * inv)


# v5e's default scoped-VMEM limit is 16 MiB per kernel.  Half of it goes to
# the pipelined tiles and the body's temporaries as counted below; the rest is
# headroom for what Mosaic allocates on its own (spilled vregs, masks).
VMEM_TILE_BUDGET = 8 << 20


def _padded_row_bytes(cols: int, dtype) -> int:
    """VMEM bytes of one row of a tile: lanes pad to a multiple of 128."""
    return -(-cols // 128) * 128 * jnp.dtype(dtype).itemsize


def _pick_block_rows(rows: int, tiles, *, temps=(), fixed_bytes: int = 0,
                     budget: int = VMEM_TILE_BUDGET) -> int:
    """Row tile ``bm`` (a multiple of 8) whose VMEM footprint fits ``budget``.

    ``tiles``: ``(cols, dtype)`` of every row-tiled operand, inputs and
    outputs; each is double-buffered by the pipeline and padded to 128 lanes,
    so a ``(bm, 1)`` scale costs as much as a ``(bm, 128)`` one.  ``temps``:
    the ``cols`` of each f32 ``(bm, cols)`` value the kernel body keeps live
    beside them.  ``fixed_bytes``: untiled operands (whole factors).  Among
    the fitting sizes a divisor of ``rows`` is preferred when one is at least
    half the largest, so the caller need not pad (copy) its operands.  The
    codes do not depend on ``bm``: every PCG counter is global.
    """
    if rows < 8:
        return rows
    per_row = 2 * sum(_padded_row_bytes(c, d) for c, d in tiles) \
        + sum(_padded_row_bytes(c, jnp.float32) for c in temps)
    bm = max(8, (budget - fixed_bytes) // per_row // 8 * 8)
    bm = min(bm, rows // 8 * 8)
    for cand in range(bm, bm // 2 - 1, -8):
        if rows % cand == 0:
            return cand
    return bm


def _pad_rows(arrs, bm: int, rows: int):
    pad = (-rows) % bm
    if pad:
        arrs = [jnp.pad(a, ((0, pad), (0, 0))) for a in arrs]
    return arrs, pad


def quantize_2d(x: jax.Array, seed: jax.Array, *, bits: int, interpret: bool = False):
    """Quantize a (rows, cols) f32 array, one scale per row. cols % 128 == 0."""
    rows, cols = x.shape
    assert cols % 128 == 0, f"block_size must be a multiple of 128, got {cols}"
    levels = 2 ** (bits - 1) - 1
    bm = _pick_block_rows(rows, [(cols, jnp.float32), (cols, jnp.int8),
                                 (1, jnp.float32)], temps=[cols] * 4)
    (x,), pad = _pad_rows([x], bm, rows)
    grid = ((rows + pad) // bm,)
    kernel = functools.partial(_quant_kernel, levels=levels, block_rows=bm, cols=cols)
    codes, scale = pl.pallas_call(
        kernel,
        name="quantize",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),  # scalar seed, broadcast to all programs
            pl.BlockSpec((bm, cols), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, cols), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows + pad, cols), jnp.int8),
            jax.ShapeDtypeStruct((rows + pad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(seed.reshape(1).astype(jnp.uint32), x.astype(jnp.float32))
    if pad:
        codes, scale = codes[:rows], scale[:rows]
    return codes, scale


def quantize_pack_2d(x: jax.Array, seed: jax.Array, *, bits: int, interpret: bool = False):
    """Fused quantize + bit-pack of a (rows, cols) f32 array.

    Returns (packed uint32 (rows, cols*bits/32), scale f32 (rows, 1)).  The codes
    are identical to ``quantize_2d`` for the same seed — packing is lossless —
    but only ``bits`` per element ever leave the kernel.
    """
    rows, cols = x.shape
    assert bits in PACKABLE_BITS, f"packable bits are {PACKABLE_BITS}, got {bits}"
    assert cols % 128 == 0, f"block_size must be a multiple of 128, got {cols}"
    levels = 2 ** (bits - 1) - 1
    w = cols * bits // 32
    bm = _pick_block_rows(rows, [(cols, jnp.float32), (w, jnp.uint32),
                                 (1, jnp.float32)], temps=[cols] * 4)
    (x,), pad = _pad_rows([x], bm, rows)
    grid = ((rows + pad) // bm,)
    kernel = functools.partial(_quant_pack_kernel, bits=bits, levels=levels,
                               block_rows=bm, cols=cols)
    packed, scale = pl.pallas_call(
        kernel,
        name="quantize_pack",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((bm, cols), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, w), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows + pad, w), jnp.uint32),
            jax.ShapeDtypeStruct((rows + pad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(seed.reshape(1).astype(jnp.uint32), x.astype(jnp.float32))
    if pad:
        packed, scale = packed[:rows], scale[:rows]
    return packed, scale


def dequantize_2d(codes: jax.Array, scale: jax.Array, *, bits: int, interpret: bool = False) -> jax.Array:
    rows, cols = codes.shape
    levels = 2 ** (bits - 1) - 1
    bm = _pick_block_rows(rows, [(cols, codes.dtype), (1, jnp.float32),
                                 (cols, jnp.float32)], temps=[cols])
    (codes, scale), pad = _pad_rows([codes, scale], bm, rows)
    grid = ((rows + pad) // bm,)
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, levels=levels),
        name="dequantize",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, cols), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, cols), jnp.float32),
        interpret=interpret,
    )(codes, scale.astype(jnp.float32))
    return out[:rows] if pad else out


def unpack_dequant_2d(packed: jax.Array, scale: jax.Array, *, bits: int,
                      interpret: bool = False) -> jax.Array:
    """Fused unpack + dequantize: uint32 words -> f32 (rows, cols)."""
    rows, w = packed.shape
    assert bits in PACKABLE_BITS
    levels = 2 ** (bits - 1) - 1
    cols = w * 32 // bits
    bm = _pick_block_rows(rows, [(w, jnp.uint32), (1, jnp.float32),
                                 (cols, jnp.float32)], temps=[w])
    (packed, scale), pad = _pad_rows([packed, scale], bm, rows)
    grid = ((rows + pad) // bm,)
    out = pl.pallas_call(
        functools.partial(_unpack_dequant_kernel, bits=bits, levels=levels),
        name="unpack_dequant",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, w), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, cols), jnp.float32),
        interpret=interpret,
    )(packed, scale.astype(jnp.float32))
    return out[:rows] if pad else out


def unpack_dequant_axpy(packed: jax.Array, scale: jax.Array, acc: jax.Array, *,
                        bits: int, weight, acc_weight=1.0,
                        interpret: bool = False) -> jax.Array:
    """Fused unpack + dequantize + accumulate over a leaf's own row view:
    ``acc_weight * acc + weight * dequant(packed)``.

    The receive side of a gossip round: the reconstructed fp32 neighbor never
    exists in HBM — each unpacked bit-plane is scaled and added into the mix
    accumulator while still in VMEM.  Both weights may be python floats or
    traced scalars (they ride a (2,) operand, like the seed on the send side);
    ``acc_weight`` serves ECD's ``(1-2/s)*tilde + (2/s)*decode`` update
    without pre-scaling the accumulator through HBM.

    ``acc`` (rows, last) is the leaf with its leading dims merged, and
    ``packed`` (rows, nblk, W) uint32 / ``scale`` (rows, nblk) its payload
    the same way: ``nblk = ceil(last / block)`` blocks of ``block = W*32/bits``
    along the last dim.  Merging leading dims is a bitcast on a TPU, so no
    operand and no result is copied into another layout.  The grid runs over
    (row tiles, blocks); a row tile's codes are fetched 8 blocks at a time.
    A last block that runs past ``last`` is Pallas's masked edge block (its
    lanes past ``last`` are read as don't-care and never written), as are a
    last group of fewer than 8 blocks and a last row tile past ``rows``.
    """
    rows, last = acc.shape
    _, nblk, w = packed.shape
    assert bits in PACKABLE_BITS
    levels = 2 ** (bits - 1) - 1
    block = w * 32 // bits
    assert packed.shape[0] == rows and nblk == -(-last // block), (packed.shape, acc.shape)
    assert scale.shape == (rows, nblk), (scale.shape, (rows, nblk))
    # codes of a group of nb blocks a row tile, (bm, nb, W) in one (8, 128)
    # tile of sublanes, so the tile's VMEM does not grow with nblk
    nb = min(nblk, 8)
    code_cols = w if nblk == 1 else 8 * (-(-w // 128) * 128)
    bm = _pick_block_rows(rows, [(code_cols, jnp.uint32), (nblk, jnp.float32),
                                 (block, jnp.float32), (block, jnp.float32)],
                          temps=[w])
    if nblk == 1:
        packed = packed.reshape(rows, w)
        words = pl.BlockSpec((bm, w), lambda i, j: (i, 0))
    else:
        words = pl.BlockSpec((bm, nb, w), lambda i, j: (i, j // nb, 0))
    weights = jnp.stack([jnp.asarray(acc_weight, jnp.float32).reshape(()),
                         jnp.asarray(weight, jnp.float32).reshape(())])
    return pl.pallas_call(
        functools.partial(_unpack_dequant_axpy_kernel, bits=bits, levels=levels),
        name="unpack_dequant_axpy",
        grid=(pl.cdiv(rows, bm), nblk),
        in_specs=[
            pl.BlockSpec((2,), lambda i, j: (0,)),  # [acc_weight, weight], broadcast
            words,
            pl.BlockSpec((bm, nblk), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, block), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, last), jnp.float32),
        interpret=interpret,
    )(weights, packed, scale.astype(jnp.float32), acc.astype(jnp.float32))


def unpack_dequant_axpy_2d(packed: jax.Array, scale: jax.Array, acc: jax.Array, *,
                           bits: int, weight, acc_weight=1.0,
                           interpret: bool = False) -> jax.Array:
    """:func:`unpack_dequant_axpy` of a (rows, cols) view whose every row is
    one block: ``packed`` (rows, cols*bits/32), ``scale`` (rows, 1)."""
    rows, w = packed.shape
    cols = w * 32 // bits
    assert acc.shape == (rows, cols), (acc.shape, (rows, cols))
    return unpack_dequant_axpy(packed[:, None], scale, acc, bits=bits, weight=weight,
                               acc_weight=acc_weight, interpret=interpret)


# --------------------------------------------------------------- sparse codec

def _sparse_select_pack_kernel(seed_ref, x_ref, vals_ref, idx_ref, *, mode: str,
                               k: int, kpad: int, idx_bits: int,
                               block_rows: int, cols: int, value_dtype):
    """Fused select + gather + index-pack for one (block_rows, cols) tile.

    Selection is ``k`` unrolled rounds of masked row argmax with
    first-occurrence (smallest-index) tie-break — the canonical order shared
    with the stable-argsort oracle — followed by the same shift-and-OR stream
    pack as the quantizer, over raw ``idx_bits``-wide unsigned fields.
    """
    x = x_ref[...].astype(jnp.float32)
    # Mosaic reduces over signed ints and floats only: lanes and randk keys
    # ride int32.  Flipping the top bit before the bitcast maps the uint32
    # key order onto the int32 order, so the selection is unchanged.
    lanes = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    if mode == "randk":
        rows = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 0) \
            + (pl.program_id(0) * block_rows).astype(jnp.uint32)
        h = pcg_hash((rows * jnp.uint32(cols) + lanes.astype(jnp.uint32))
                     ^ seed_ref[0])
        key = jax.lax.bitcast_convert_type(h ^ jnp.uint32(0x80000000), jnp.int32)
        sentinel = jnp.int32(-(1 << 31))   # == uint32 key 0
    else:
        mag = jnp.abs(x)
        # NaN ranks below every real magnitude but above masked-out lanes —
        # the iterative argmax then selects NaN lanes last, in ascending index
        # order, exactly where the oracle's total-order sort (NaN last) puts
        # them; a bare max() would NaN-poison the whole block instead
        key = jnp.where(jnp.isnan(mag), jnp.float32(-0.5), mag)
        sentinel = jnp.float32(-1.0)    # key >= -0.5: never shadows a live lane
    valid = jnp.ones(x.shape, jnp.bool_)
    val_cols, sel_cols = [], []
    for _ in range(k):
        masked = jnp.where(valid, key, sentinel)
        m = jnp.max(masked, axis=1, keepdims=True)
        sel = jnp.min(jnp.where(valid & (masked == m), lanes, jnp.int32(cols)),
                      axis=1, keepdims=True)
        hit = lanes == sel
        val_cols.append(jnp.sum(jnp.where(hit, x, 0.0), axis=1, keepdims=True))
        sel_cols.append(sel.astype(jnp.uint32))
        valid = valid & ~hit
    vals = jnp.concatenate(val_cols, axis=1)
    if mode == "randk":
        vals = vals * jnp.float32(cols / k)   # inclusion prob k/cols => unbiased
    vals_ref[...] = vals.astype(value_dtype)

    if kpad > k:   # container padding to whole stream groups (dropped on unpack)
        sel_cols = sel_cols + [jnp.zeros((x.shape[0], 1), jnp.uint32)] * (kpad - k)
    u = jnp.concatenate(sel_cols, axis=1)
    cpg, wpg = stream_geometry(idx_bits)
    g = kpad // cpg
    words = [jnp.zeros(u.shape[:-1] + (g,), jnp.uint32) for _ in range(wpg)]
    for j in range(cpg):
        w, off = divmod(j * idx_bits, 32)
        uj = u[:, j * g:(j + 1) * g]
        words[w] = words[w] | (uj << jnp.uint32(off))
        if off + idx_bits > 32:
            words[w + 1] = words[w + 1] | (uj >> jnp.uint32(32 - off))
    for w in range(wpg):
        idx_ref[:, w * g:(w + 1) * g] = words[w]


def _sparse_idx_entries(word, *, k: int, idx_bits: int):
    """Yield (entry i, (rows, 1) uint32 block-local index) from packed words."""
    cpg, wpg = stream_geometry(idx_bits)
    g = word.shape[-1] // wpg
    mask = jnp.uint32((1 << idx_bits) - 1)
    planes = [word[:, w * g:(w + 1) * g] for w in range(wpg)]
    fields = {}
    for j in range(cpg):
        w, off = divmod(j * idx_bits, 32)
        v = planes[w] >> jnp.uint32(off)
        if off + idx_bits > 32:
            v = v | (planes[w + 1] << jnp.uint32(32 - off))
        fields[j] = v & mask
    for i in range(k):   # entry i lives in group i % g at stream position i // g
        yield i, fields[i // g][:, i % g:i % g + 1]


def _sparse_scatter_kernel(vals_ref, idx_ref, out_ref, *, k: int, idx_bits: int):
    out = jnp.zeros(out_ref.shape, jnp.float32)
    lanes = jax.lax.broadcasted_iota(jnp.uint32, out.shape, 1)
    for i, idx_i in _sparse_idx_entries(idx_ref[...], k=k, idx_bits=idx_bits):
        val_i = vals_ref[:, i:i + 1].astype(jnp.float32)
        out = out + jnp.where(lanes == idx_i, val_i, 0.0)
    out_ref[...] = out


def _sparse_scatter_axpy_kernel(weights_ref, vals_ref, idx_ref, acc_ref, out_ref,
                                *, k: int, idx_bits: int):
    # weights_ref = [acc_weight, weight], exactly like _unpack_dequant_axpy_kernel
    out = weights_ref[0] * acc_ref[...].astype(jnp.float32)
    wt = weights_ref[1]
    lanes = jax.lax.broadcasted_iota(jnp.uint32, out.shape, 1)
    for i, idx_i in _sparse_idx_entries(idx_ref[...], k=k, idx_bits=idx_bits):
        val_i = vals_ref[:, i:i + 1].astype(jnp.float32)
        out = out + jnp.where(lanes == idx_i, wt * val_i, 0.0)
    out_ref[...] = out


def _scatter_temps(cols: int, k: int, idx_bits: int):
    """f32-sized values the scatter kernels keep live: the dense accumulator,
    the lane iota, and the ``cpg`` unpacked index fields of ``G`` lanes each."""
    cpg, wpg = stream_geometry(idx_bits)
    return [cols] * 2 + [max(1, -(-k // cpg))] * cpg


def sparse_select_pack_2d(x: jax.Array, seed: jax.Array, *, p: float, mode: str,
                          value_dtype=jnp.float32, interpret: bool = False):
    """Fused fixed-capacity selection of a (rows, cols) f32 array.

    Returns (values (rows, k) ``value_dtype``, packed indices (rows, words)
    uint32) with ``k, words`` from ``sparse_geometry(cols, p)`` — identical
    word-for-word to the kernels/ref.py oracle for the same seed.
    ``cols % 128 == 0`` (lane contract), like the quantize kernels.
    """
    rows, cols = x.shape
    assert cols % 128 == 0, f"block_size must be a multiple of 128, got {cols}"
    assert mode in SPARSE_MODES, f"sparse modes are {SPARSE_MODES}, got {mode}"
    k, idx_bits, kpad, w_idx = sparse_geometry(cols, p)
    # the k selected (bm, 1) values and indices stay live until the final
    # concatenate, each padded to a full lane tile
    bm = _pick_block_rows(rows, [(cols, jnp.float32), (k, value_dtype),
                                 (w_idx, jnp.uint32)],
                          temps=[cols] * 4 + [1] * (2 * kpad))
    (x,), pad = _pad_rows([x], bm, rows)
    grid = ((rows + pad) // bm,)
    kernel = functools.partial(
        _sparse_select_pack_kernel, mode=mode, k=k, kpad=kpad, idx_bits=idx_bits,
        block_rows=bm, cols=cols, value_dtype=value_dtype)
    vals, idx = pl.pallas_call(
        kernel,
        name="sparse_select_pack",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((bm, cols), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((bm, w_idx), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows + pad, k), value_dtype),
            jax.ShapeDtypeStruct((rows + pad, w_idx), jnp.uint32),
        ],
        interpret=interpret,
    )(seed.reshape(1).astype(jnp.uint32), x.astype(jnp.float32))
    if pad:
        vals, idx = vals[:rows], idx[:rows]
    return vals, idx


def sparse_unpack_scatter_2d(values: jax.Array, packed: jax.Array, *, cols: int,
                             interpret: bool = False) -> jax.Array:
    """Fused unpack + scatter: k values + packed index words -> (rows, cols) f32."""
    rows, k = values.shape
    idx_bits = idx_bits_for(cols)
    bm = _pick_block_rows(rows, [(k, values.dtype), (packed.shape[-1], jnp.uint32),
                                 (cols, jnp.float32)],
                          temps=_scatter_temps(cols, k, idx_bits))
    (values, packed), pad = _pad_rows([values, packed], bm, rows)
    grid = ((rows + pad) // bm,)
    out = pl.pallas_call(
        functools.partial(_sparse_scatter_kernel, k=k, idx_bits=idx_bits),
        name="sparse_unpack_scatter",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((bm, packed.shape[-1]), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, cols), jnp.float32),
        interpret=interpret,
    )(values, packed)
    return out[:rows] if pad else out


# ----------------------------------------------------------------- sign codec

def _sign_scale(x, *, scale_mode: str):
    """Per-row scale of the 1-bit codec: ``mean`` = mean|x| (scaled-sign,
    a delta-contraction), ``l2`` = ||x||_2/sqrt(cols) (signSGD-style, not
    contractive in general).  Identical expressions to the oracle's
    ``sign_scale_2d`` so kernel and reference scales are bit-equal."""
    if scale_mode == "mean":
        return jnp.mean(jnp.abs(x), axis=1, keepdims=True)
    return jnp.sqrt(jnp.mean(x * x, axis=1, keepdims=True))


def _sign_pack_kernel(x_ref, packed_ref, scale_ref, *, scale_mode: str, cols: int):
    """Fused sign + width-1 bit-pack of one (block_rows, cols) tile.

    No seed operand: the sign codec is deterministic (bit = x >= 0, so -0.0
    codes as +1 like +0.0).  Width-1 stream geometry collapses to cpg=32,
    wpg=1: group ``g`` packs the 32 bits ``{u[j*G + g] : j}`` into one word,
    bit ``j`` at position ``j`` — the plane-major shift-and-OR loop below is
    exactly :func:`repro.kernels.ref.pack_uint` at ``bits=1``.
    """
    x = x_ref[...].astype(jnp.float32)
    u = (x >= 0.0).astype(jnp.uint32)
    g = cols // 32
    word = jnp.zeros(u.shape[:-1] + (g,), jnp.uint32)
    for j in range(32):
        word = word | (u[:, j * g:(j + 1) * g] << jnp.uint32(j))
    packed_ref[...] = word
    scale_ref[...] = _sign_scale(x, scale_mode=scale_mode)


def _unpack_sign_axpy_kernel(weights_ref, packed_ref, scale_ref, acc_ref,
                             out_ref):
    # weights_ref = [acc_weight, weight], exactly like _unpack_dequant_axpy_kernel;
    # the unpacked factor is exactly +-1, so folding weight into the scale
    # cannot change the rounding vs the oracle's weight * ((2u-1) * scale)
    word = packed_ref[...]
    aw = weights_ref[0]
    ws = scale_ref[...] * weights_ref[1]
    g = word.shape[-1]
    for j in range(32):
        u = ((word >> jnp.uint32(j)) & jnp.uint32(1)).astype(jnp.int32) \
            .astype(jnp.float32)
        out_ref[:, j * g:(j + 1) * g] = (
            aw * acc_ref[:, j * g:(j + 1) * g] + (u * 2.0 - 1.0) * ws)


def sign_pack_2d(x: jax.Array, *, scale_mode: str = "mean",
                 interpret: bool = False):
    """Fused 1-bit sign + pack of a (rows, cols) f32 array.

    Returns (packed uint32 (rows, cols/32), scale f32 (rows, 1)) — identical
    word-for-word to the kernels/ref.py oracle (the codec is deterministic,
    so no seed rides the call).  ``cols % 128 == 0`` (lane contract), which
    also guarantees the width-1 stream's cols % 32 == 0.
    """
    rows, cols = x.shape
    assert cols % 128 == 0, f"block_size must be a multiple of 128, got {cols}"
    assert scale_mode in SIGN_SCALE_MODES, \
        f"sign scale modes are {SIGN_SCALE_MODES}, got {scale_mode}"
    w = cols // 32
    bm = _pick_block_rows(rows, [(cols, jnp.float32), (w, jnp.uint32),
                                 (1, jnp.float32)], temps=[cols] * 2)
    (x,), pad = _pad_rows([x], bm, rows)
    grid = ((rows + pad) // bm,)
    packed, scale = pl.pallas_call(
        functools.partial(_sign_pack_kernel, scale_mode=scale_mode, cols=cols),
        name="sign_pack",
        grid=grid,
        in_specs=[pl.BlockSpec((bm, cols), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bm, w), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows + pad, w), jnp.uint32),
            jax.ShapeDtypeStruct((rows + pad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x.astype(jnp.float32))
    if pad:
        packed, scale = packed[:rows], scale[:rows]
    return packed, scale


def unpack_sign_axpy_2d(packed: jax.Array, scale: jax.Array, acc: jax.Array, *,
                        weight, acc_weight=1.0,
                        interpret: bool = False) -> jax.Array:
    """Fused unpack + sign-decode + accumulate:
    ``acc_weight * acc + weight * (scale * sign)``.

    The 1-bit receive side of a gossip round: the reconstructed fp32 neighbor
    never exists in HBM — each of the 32 bit planes is scaled and added into
    the mix accumulator while still in VMEM.  Both weights ride the same (2,)
    operand as the quantized/sparse axpy kernels, so traced mixing weights
    drive this kernel too.
    """
    rows, w = packed.shape
    cols = w * 32
    assert acc.shape == (rows, cols), (acc.shape, (rows, cols))
    bm = _pick_block_rows(rows, [(w, jnp.uint32), (1, jnp.float32),
                                 (cols, jnp.float32), (cols, jnp.float32)],
                          temps=[w])
    (packed, scale, acc), pad = _pad_rows([packed, scale, acc], bm, rows)
    grid = ((rows + pad) // bm,)
    weights = jnp.stack([jnp.asarray(acc_weight, jnp.float32).reshape(()),
                         jnp.asarray(weight, jnp.float32).reshape(())])
    out = pl.pallas_call(
        _unpack_sign_axpy_kernel,
        name="unpack_sign_axpy",
        grid=grid,
        in_specs=[
            pl.BlockSpec((2,), lambda i: (0,)),
            pl.BlockSpec((bm, w), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, cols), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, cols), jnp.float32),
        interpret=interpret,
    )(weights, packed, scale.astype(jnp.float32), acc.astype(jnp.float32))
    return out[:rows] if pad else out


def sparse_scatter_axpy_2d(values: jax.Array, packed: jax.Array, acc: jax.Array,
                           *, weight, acc_weight=1.0,
                           interpret: bool = False) -> jax.Array:
    """Fused unpack + scatter + accumulate:
    ``acc_weight * acc + weight * scatter(values -> indices)``.

    The sparse receive side of a gossip round: the reconstructed dense fp32
    neighbor delta never exists in HBM.  Both weights ride the same (2,)
    scalar operand as the quantized axpy kernel, so ECD's traced
    ``(1-2/s, 2/s)`` blend drives this kernel too.
    """
    rows, k = values.shape
    cols = acc.shape[-1]
    assert acc.shape == (rows, cols), (acc.shape, (rows, cols))
    idx_bits = idx_bits_for(cols)
    bm = _pick_block_rows(rows, [(k, values.dtype), (packed.shape[-1], jnp.uint32),
                                 (cols, jnp.float32), (cols, jnp.float32)],
                          temps=_scatter_temps(cols, k, idx_bits))
    (values, packed, acc), pad = _pad_rows([values, packed, acc], bm, rows)
    grid = ((rows + pad) // bm,)
    weights = jnp.stack([jnp.asarray(acc_weight, jnp.float32).reshape(()),
                         jnp.asarray(weight, jnp.float32).reshape(())])
    out = pl.pallas_call(
        functools.partial(_sparse_scatter_axpy_kernel, k=k, idx_bits=idx_bits),
        name="sparse_scatter_axpy",
        grid=grid,
        in_specs=[
            pl.BlockSpec((2,), lambda i: (0,)),
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((bm, packed.shape[-1]), lambda i: (i, 0)),
            pl.BlockSpec((bm, cols), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, cols), jnp.float32),
        interpret=interpret,
    )(weights, values, packed, acc.astype(jnp.float32))
    return out[:rows] if pad else out
