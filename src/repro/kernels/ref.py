"""Pure-jnp oracles for the Pallas kernels (bit-exact where deterministic).

``quantize_2d_ref`` replicates quant.py exactly — including the counter-based PCG
stochastic rounding — so kernel tests can assert exact equality of codes, not just
statistical agreement.  ``pack_codes`` / ``unpack_codes`` implement the bit-exact
stream layout documented in kernels/quant.py (wire format v2: any width 2..7,
codes straddle uint32 word boundaries); they are the *shared* reference codec:
the distributed WireCodec and the compression operators call these, and the
Pallas kernels are tested word-for-word against them.

The same stream layout carries the *sparse* wire format: ``pack_uint`` /
``unpack_uint`` pack raw unsigned fields of any width 1..16 (no sign bias),
which the sparse codec uses for its block-local indices
(``idx_bits_for(block)`` bits each), and ``sparse_select_2d_ref`` /
``sparse_scatter_2d_ref`` are the selection/scatter oracles the fused Pallas
kernels and the SparseWireCodec are tested word-for-word against.  The
selection order is canonical — descending key, ties broken toward the smaller
index — so all three implementations emit identical ``{values, indices}``
payloads for identical seeds.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.quant import (  # noqa: F401  (shared single source of truth)
    PACKABLE_BITS,
    SIGN_SCALE_MODES,
    SPARSE_MODES,
    idx_bits_for,
    pcg_hash,
    sparse_geometry,
    stream_geometry,
    uniform_from_hash,
)


def packed_auto(bits: int, block: int) -> bool:
    """The shared auto-pack policy (``pack=None``): pack whenever the width is
    packable and the block is a whole number of stream groups; otherwise fall
    back to the int8 container (honestly reported by the measured wire bits).
    Single source of truth for WireCodec and RandomQuantizer."""
    if bits not in PACKABLE_BITS:
        return False
    cpg, _ = stream_geometry(bits)
    return block % cpg == 0


def assert_packable(bits: int, block: int) -> None:
    """Validate an *explicit* ``pack=True`` request against the geometry."""
    assert bits in PACKABLE_BITS, \
        f"packable bits are {PACKABLE_BITS}, got {bits}"
    cpg, _ = stream_geometry(bits)
    assert block % cpg == 0, \
        f"packed {bits}-bit needs block % {cpg} == 0"


def aligned_block(limit: int, n: int, *, bits: int) -> int:
    """Block size for an ``n``-element (last-dim) leaf: shrink toward ``n`` to
    limit padding, rounded up to a whole number of packed *groups* so the block
    always packs cleanly into whole uint32 words.  Shared by RandomQuantizer
    and WireCodec so the two codecs agree on block geometry."""
    cpg, _ = stream_geometry(bits)
    block = min(limit, max(n, 1))
    return min(limit, -(-block // cpg) * cpg)


def pack_uint(u: jax.Array, *, bits: int) -> jax.Array:
    """Bit-pack raw unsigned ``bits``-wide fields along the last dim.

    (..., cols) uint32 (each value < 2^bits) -> (..., cols*bits/32) uint32, the
    stream layout of kernels/quant.py with no sign bias: fields are grouped
    into ``cpg = lcm(bits,32)/bits``-field groups laid out plane-major across
    the ``G = cols/cpg`` groups, and each group's ``cpg * bits``-bit stream
    fills ``wpg = lcm(bits,32)/32`` words exactly (fields straddle word
    boundaries when 32 % bits != 0).  ``cols`` must be a multiple of ``cpg``.
    Any width 1..16 packs — the quantizer restricts itself to 2..7, the sparse
    index stream uses ``idx_bits_for(block)``.
    """
    assert 1 <= bits <= 16, f"uint stream widths are 1..16, got {bits}"
    cpg, wpg = stream_geometry(bits)
    cols = u.shape[-1]
    assert cols % cpg == 0, f"last dim {cols} not a multiple of {cpg}"
    g = cols // cpg
    u = u.astype(jnp.uint32)
    words = [jnp.zeros(u.shape[:-1] + (g,), jnp.uint32) for _ in range(wpg)]
    for j in range(cpg):
        w, off = divmod(j * bits, 32)
        uj = u[..., j * g:(j + 1) * g]
        words[w] = words[w] | (uj << jnp.uint32(off))      # uint32: high bits drop
        if off + bits > 32:                                # straddles into word w+1
            words[w + 1] = words[w + 1] | (uj >> jnp.uint32(32 - off))
    return jnp.concatenate(words, axis=-1)


def unpack_uint(packed: jax.Array, *, bits: int) -> jax.Array:
    """Inverse of :func:`pack_uint`: (..., W) uint32 -> (..., W*32/bits) uint32."""
    assert 1 <= bits <= 16, f"uint stream widths are 1..16, got {bits}"
    cpg, wpg = stream_geometry(bits)
    mask = jnp.uint32((1 << bits) - 1)
    W = packed.shape[-1]
    assert W % wpg == 0, f"word count {W} not a multiple of {wpg}"
    g = W // wpg
    planes = [packed[..., w * g:(w + 1) * g] for w in range(wpg)]
    parts = []
    for j in range(cpg):
        w, off = divmod(j * bits, 32)
        v = planes[w] >> jnp.uint32(off)
        if off + bits > 32:
            v = v | (planes[w + 1] << jnp.uint32(32 - off))
        parts.append(v & mask)
    return jnp.concatenate(parts, axis=-1)


def pack_codes(codes: jax.Array, *, bits: int) -> jax.Array:
    """Bit-pack int8 codes in [-levels, levels] along the last dim.

    (..., cols) int8 -> (..., cols*bits/32) uint32: the codes are biased to
    the unsigned range [1, 2^bits - 1] and shipped through :func:`pack_uint`
    (the single stream layout shared with the sparse index codec).
    """
    assert bits in PACKABLE_BITS, f"packable bits are {PACKABLE_BITS}, got {bits}"
    levels = 2 ** (bits - 1) - 1
    return pack_uint((codes.astype(jnp.int32) + (levels + 1)).astype(jnp.uint32),
                     bits=bits)


def unpack_codes(packed: jax.Array, *, bits: int) -> jax.Array:
    """Inverse of :func:`pack_codes`: (..., W) uint32 -> (..., W*32/bits) int8."""
    assert bits in PACKABLE_BITS, f"packable bits are {PACKABLE_BITS}, got {bits}"
    levels = 2 ** (bits - 1) - 1
    u = unpack_uint(packed, bits=bits)
    return (u.astype(jnp.int32) - (levels + 1)).astype(jnp.int8)


def quantize_2d_ref(x: jax.Array, seed: jax.Array, *, bits: int):
    rows, cols = x.shape
    levels = 2 ** (bits - 1) - 1
    x = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    safe = jnp.where(scale > 0.0, scale, 1.0)
    v = x * (levels / safe)
    idx = (
        jax.lax.broadcasted_iota(jnp.uint32, x.shape, 0) * jnp.uint32(cols)
        + jax.lax.broadcasted_iota(jnp.uint32, x.shape, 1)
    )
    u = uniform_from_hash(idx, jnp.asarray(seed).reshape(()).astype(jnp.uint32))
    floor = jnp.floor(v)
    q = floor + (u < (v - floor)).astype(jnp.float32)
    codes = jnp.clip(q, -levels, levels).astype(jnp.int8)
    return codes, scale


def dequantize_2d_ref(codes: jax.Array, scale: jax.Array, *, bits: int) -> jax.Array:
    levels = 2 ** (bits - 1) - 1
    # reciprocal multiply, never a divide: XLA rewrites div-by-constant into a
    # reciprocal multiply under jit, so the multiply IS the canonical semantics
    # (kernels and codecs share this formulation; tested bit-exact)
    return codes.astype(jnp.float32) * (scale.astype(jnp.float32) * jnp.float32(1.0 / levels))


def quantize_pack_2d_ref(x: jax.Array, seed: jax.Array, *, bits: int):
    """Oracle for the fused quantize+pack kernel: quantize, then pack."""
    codes, scale = quantize_2d_ref(x, seed, bits=bits)
    return pack_codes(codes, bits=bits), scale


def unpack_dequant_2d_ref(packed: jax.Array, scale: jax.Array, *, bits: int) -> jax.Array:
    return dequantize_2d_ref(unpack_codes(packed, bits=bits), scale, bits=bits)


def unpack_dequant_axpy_2d_ref(packed: jax.Array, scale: jax.Array, acc: jax.Array, *,
                               bits: int, weight: float,
                               acc_weight: float = 1.0) -> jax.Array:
    return acc_weight * acc.astype(jnp.float32) \
        + weight * unpack_dequant_2d_ref(packed, scale, bits=bits)


# ------------------------------------------------------------ sparse codec


def sparse_order_2d_ref(x: jax.Array, seed: jax.Array, *, mode: str) -> jax.Array:
    """Canonical selection order of a (rows, cols) block view: every column
    index, sorted by descending selection key with ties broken toward the
    smaller index.  ``randk`` keys are the counter-based PCG hash of the
    global element index (the hash is a bijection on uint32, so keys within a
    row are distinct and the order is a uniform pseudo-random permutation);
    ``topk`` keys are |x| (stable sort => smallest index wins ties — the same
    tie-break as the kernel's iterative first-occurrence argmax)."""
    assert mode in SPARSE_MODES, f"sparse modes are {SPARSE_MODES}, got {mode}"
    rows, cols = x.shape
    if mode == "randk":
        idx = (
            jax.lax.broadcasted_iota(jnp.uint32, x.shape, 0) * jnp.uint32(cols)
            + jax.lax.broadcasted_iota(jnp.uint32, x.shape, 1)
        )
        key = pcg_hash(idx ^ jnp.asarray(seed).reshape(()).astype(jnp.uint32))
        return jnp.argsort(key ^ jnp.uint32(0xFFFFFFFF), axis=1, stable=True)
    return jnp.argsort(-jnp.abs(x.astype(jnp.float32)), axis=1, stable=True)


def sparse_select_2d_ref(x: jax.Array, seed: jax.Array, *, k: int, mode: str,
                         value_dtype=jnp.float32):
    """Fixed-capacity selection oracle: (rows, cols) -> (values (rows, k),
    indices (rows, k) uint32), in canonical selection order.  ``randk``
    rescales kept values by ``cols/k`` (inclusion probability is exactly
    ``k/cols`` for a uniform k-subset => unbiased); ``topk`` keeps raw values.
    """
    rows, cols = x.shape
    x = x.astype(jnp.float32)
    sel = sparse_order_2d_ref(x, seed, mode=mode)[:, :k]
    vals = jnp.take_along_axis(x, sel, axis=1)
    if mode == "randk":
        vals = vals * jnp.float32(cols / k)
    return vals.astype(value_dtype), sel.astype(jnp.uint32)


def sparse_pack_idx(indices: jax.Array, *, block: int, kpad: int) -> jax.Array:
    """(..., k) uint32 block-local indices -> (..., words) uint32 packed
    stream: zero-pad the tail to ``kpad`` whole groups, then :func:`pack_uint`
    at ``idx_bits_for(block)`` bits per field.  The zero tail is container
    padding, not payload — unpack slices it back off with ``[..., :k]``."""
    k = indices.shape[-1]
    pad = kpad - k
    if pad:
        indices = jnp.pad(indices, [(0, 0)] * (indices.ndim - 1) + [(0, pad)])
    return pack_uint(indices.astype(jnp.uint32), bits=idx_bits_for(block))


def sparse_unpack_idx(packed: jax.Array, *, block: int, k: int) -> jax.Array:
    """Inverse of :func:`sparse_pack_idx`: (..., words) -> (..., k) uint32."""
    return unpack_uint(packed, bits=idx_bits_for(block))[..., :k]


def sparse_select_pack_2d_ref(x: jax.Array, seed: jax.Array, *, p: float,
                              mode: str, value_dtype=jnp.float32):
    """Oracle for the fused select+gather+pack kernel: select, then pack the
    index stream.  Returns (values (rows, k), packed indices (rows, words))."""
    cols = x.shape[1]
    k, _, kpad, _ = sparse_geometry(cols, p)
    vals, sel = sparse_select_2d_ref(x, seed, k=k, mode=mode,
                                     value_dtype=value_dtype)
    return vals, sparse_pack_idx(sel, block=cols, kpad=kpad)


def sparse_scatter_2d_ref(values: jax.Array, indices: jax.Array, *,
                          cols: int) -> jax.Array:
    """(rows, k) values + (rows, k) duplicate-free block-local indices ->
    dense (rows, cols) f32.  Each output lane receives at most one value, so
    the sum order is irrelevant and the result is bit-exact across the jnp,
    codec, and kernel formulations."""
    lanes = jax.lax.broadcasted_iota(jnp.uint32, (values.shape[0], 1, cols), 2)
    hit = indices[..., :, None].astype(jnp.uint32) == lanes
    return jnp.sum(jnp.where(hit, values[..., :, None].astype(jnp.float32), 0.0),
                   axis=-2)


def sparse_unpack_scatter_2d_ref(values: jax.Array, packed: jax.Array, *,
                                 k: int, cols: int) -> jax.Array:
    return sparse_scatter_2d_ref(
        values, sparse_unpack_idx(packed, block=cols, k=k), cols=cols)


def sparse_scatter_axpy_2d_ref(values: jax.Array, packed: jax.Array,
                               acc: jax.Array, *, k: int, weight: float,
                               acc_weight: float = 1.0) -> jax.Array:
    cols = acc.shape[-1]
    return acc_weight * acc.astype(jnp.float32) \
        + weight * sparse_unpack_scatter_2d_ref(values, packed, k=k, cols=cols)


# -------------------------------------------------------------- sign codec


def sign_scale_2d(x: jax.Array, *, scale_mode: str) -> jax.Array:
    """Per-row magnitude of the 1-bit codec, shared by oracle, kernel, and
    codec so all three compute the identical (rows, 1) f32 scale.  ``mean`` is
    the scaled-sign compressor ``mean|x| * sign(x)`` (a delta-contraction:
    ``||x - C(x)||^2 = ||x||^2 - ||x||_1^2/d <= (1 - 1/d) ||x||^2``); ``l2``
    is the signSGD-style ``||x||_2/sqrt(d)`` normalization, NOT contractive in
    general — exactly the biased regime error feedback exists for."""
    assert scale_mode in SIGN_SCALE_MODES, \
        f"sign scale modes are {SIGN_SCALE_MODES}, got {scale_mode}"
    x = x.astype(jnp.float32)
    if scale_mode == "mean":
        return jnp.mean(jnp.abs(x), axis=1, keepdims=True)
    return jnp.sqrt(jnp.mean(x * x, axis=1, keepdims=True))


def sign_pack_2d_ref(x: jax.Array, *, scale_mode: str = "mean"):
    """Oracle for the fused sign+pack kernel: one sign bit per element
    (``x >= 0``, so -0.0 and +0.0 both code as +1) plus a per-row scale,
    bits packed 32-per-word through the width-1 :func:`pack_uint` stream.
    Deterministic — the sign codec takes no seed.  ``cols % 32 == 0``.

    Returns (packed uint32 (rows, cols/32), scale f32 (rows, 1))."""
    x = x.astype(jnp.float32)
    bits = (x >= 0.0).astype(jnp.uint32)
    return pack_uint(bits, bits=1), sign_scale_2d(x, scale_mode=scale_mode)


def unpack_sign_2d_ref(packed: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`sign_pack_2d_ref`: ``scale * (2u - 1)``."""
    u = unpack_uint(packed, bits=1).astype(jnp.float32)
    return (u * 2.0 - 1.0) * scale.astype(jnp.float32)


def unpack_sign_axpy_2d_ref(packed: jax.Array, scale: jax.Array,
                            acc: jax.Array, *, weight: float,
                            acc_weight: float = 1.0) -> jax.Array:
    # the sign factor is exactly +-1, so weight association cannot change the
    # rounding — this matches the fused kernel's (scale * weight) grouping
    # bit-for-bit
    return acc_weight * acc.astype(jnp.float32) \
        + weight * unpack_sign_2d_ref(packed, scale)


# ---------------------------------------------------------- low-rank codec


def lowrank_orthonormalize_ref(p: jax.Array, *, eps: float = 1e-8) -> jax.Array:
    """Batched modified Gram-Schmidt over the trailing ``(m, r)`` factor.

    Orthonormalizes the ``r`` columns of every leading-batch slice in input
    order.  The column loop is a Python loop over the static rank (r is tiny —
    2..8), so the op sequence is fixed and the result is bit-reproducible.
    A degenerate column keeps its projected residual scaled by ``1/eps``-safe
    norm (``max(||v||, eps)``) instead of dividing by zero — the next power
    iteration re-mixes it, so transient rank deficiency cannot NaN the step.
    """
    p = p.astype(jnp.float32)
    r = p.shape[-1]
    cols = []
    for j in range(r):
        v = p[..., j]
        for q in cols:
            v = v - jnp.sum(q * v, axis=-1, keepdims=True) * q
        norm = jnp.sqrt(jnp.sum(v * v, axis=-1, keepdims=True))
        cols.append(v / jnp.maximum(norm, jnp.float32(eps)))
    return jnp.stack(cols, axis=-1)


# Low-rank matmuls run at full f32 precision.  For f32 operands a TPU's
# default is one bf16 pass, which would make the kernel, the codec and these
# oracles disagree by bf16 rounding; the CPU backend computes f32 either way.
F32_MATMUL = jax.lax.Precision.HIGHEST


def _factor_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` contracting the LAST dim of both operands (i.e. ``a @ b.T``
    without materializing the transpose) in f32 accumulation.  Shared by the
    oracle and the Pallas kernel body so the dot_general dimension numbers —
    and therefore the accumulation order — are identical in both."""
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        (((a.ndim - 1,), (b.ndim - 1,)), ((), ())),
        precision=F32_MATMUL, preferred_element_type=jnp.float32)


def lowrank_project_2d_ref(m: jax.Array, v: jax.Array) -> jax.Array:
    """Power-iteration projection oracle: ``P = M @ V``.

    (m, n) f32 x (n, r) f32 -> (m, r) f32.  The encode half of the lowrank
    wire format (project the leaf onto the right factor); the Pallas kernel
    tiles only the output rows and keeps the n-contraction unsplit, so kernel
    and oracle reduce each output element in the same order — exact equality,
    not atol."""
    return jax.lax.dot_general(
        m.astype(jnp.float32), v.astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=F32_MATMUL, preferred_element_type=jnp.float32)


def lowrank_axpy_2d_ref(p: jax.Array, v: jax.Array, acc: jax.Array, *,
                        weight, acc_weight=1.0) -> jax.Array:
    """Decode-axpy oracle: ``acc_weight * acc + weight * (P @ V^T)``.

    (m, r) x (n, r) factors -> rank-r reconstruction accumulated straight
    into a (m, n) accumulator, matching the fused kernel's
    ``aw * acc + w * dot`` association bit-for-bit."""
    return acc_weight * acc.astype(jnp.float32) \
        + weight * _factor_matmul(p, v)
