"""One wire-format protocol for every gossip payload.

A :class:`WireFormat` is the single codec abstraction shared by the sharded
runtime (:mod:`repro.distributed.decentralized`), the stacked reference
(:mod:`repro.core.compression` compressors are thin views over these objects),
and every accounting surface (netsim, dryrun, roofline, kernel_bench).  The
per-leaf protocol:

* ``encode(leaf, seed) -> Payload`` — a pytree of *wire arrays* (packed uint32
  words / per-block scales / sparse values), blocked along the LAST dim only so
  leading-dim sharding is preserved (see :func:`_quantize_nd`).
* ``decode(payload, like) -> array`` — reconstruct a ``like``-shaped leaf.
* ``decode_axpy(payload, acc, weight, acc_weight) -> array`` —
  ``acc_weight * acc + weight * decode(payload)`` in one pass; packed formats
  route through the fused Pallas kernels behind the shared 128-lane gate
  (:meth:`WireFormat._kernel_ok`).

Tree-level plumbing (``encode_tree`` / ``decode_tree`` / ``decode_axpy_tree``)
derives per-leaf seeds from ``(step, salt, leaf index)`` through one PCG-style
recipe (:func:`leaf_seed`) — the SAME derivation on the sharded runtime and the
stacked reference, so the two produce bit-identical payloads (the differential
test tier asserts it, packed sparse indices included).

Wire accounting is *measured*, never modeled: ``wire_nbytes`` /
``wire_bits_per_element`` evaluate the real payload containers via
``jax.eval_shape`` (nothing is computed, only shapes).

Registered implementations (``make_wire_format`` specs):

* ``quant``    — stochastic ``bits``-bit quantization, bit-exact stream-packed
  uint32 words for widths 2..7, int8 container at 8.
* ``sparse``   — fixed-capacity random-k / top-k values + bit-packed indices.
* ``sign``     — 1-bit sign + per-block magnitude scale (~1.03 measured wire
  bits/element at block 1024; biased — the error-feedback algorithms' regime).
* ``fp16``     — half-precision cast (deterministic, 16 wire bits/element).
* ``identity`` — no-op (full-precision wire; recovers exact D-PSGD).
* ``lowrank``  — rank-r power-iteration factors (PowerGossip) for matrix
  leaves, ``32·r·(m+n)/(m·n)`` measured wire bits/element; 1-D leaves fall
  through to fp16.  ``lowrank:<r>:warm`` warm-starts the right factor across
  rounds through the optional per-leaf aux channel (see :class:`LowRankWire`).
* ``adaptive`` — per-leaf combinator: routes each leaf to a ``small=`` or
  ``large=`` sub-format by per-replica element count, with optional
  ``leaf.<pattern>=`` per-leaf-path overrides (see :class:`AdaptiveWire`).

Spec strings are ``name[:arg[:arg...]]`` where each arg is ``key=value`` or a
positional value (``quant:4`` == ``quant:bits=4``; ``sparse:0.25:topk`` ==
``sparse:p=0.25,mode=topk``).  New formats are a :func:`register_wire_format`
call, not a fork of the runtime.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Callable, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as _kops
from repro.kernels.ops import payload_nbytes as _payload_nbytes
from repro.kernels.quant import (
    pcg_hash,
    sparse_scatter_axpy_2d,
    uniform_from_hash,
    unpack_dequant_axpy,
    unpack_sign_axpy_2d,
)
from repro.kernels.ref import (
    F32_MATMUL,
    SIGN_SCALE_MODES,
    SPARSE_MODES,
    aligned_block,
    assert_packable,
    pack_codes,
    pack_uint,
    packed_auto,
    sparse_geometry,
    sparse_pack_idx,
    sparse_unpack_idx,
    unpack_codes,
    unpack_uint,
)

Payload = Any   # pytree of wire arrays (uint32 words / scales / values)


def leaf_seed(step: jax.Array, salt: int, leaf_index: int) -> jax.Array:
    """The one (step, salt, leaf)-seeding recipe shared by the sharded runtime
    and the stacked reference: Knuth-hash the step counter, XOR a static
    per-(salt, leaf) offset.  Deterministic and key-free inside the compiled
    step; both runs derive identical seeds, so payloads are bit-identical.

    Multi-round gossip schedules fold their round index into this same recipe
    by passing the effective counter ``step * period + round`` as ``step`` —
    no second salt axis, a 1-round schedule seeds exactly like its flat plan,
    and the stacked reference reproduces any round's payload bits by chaining
    its own steps with the same counters."""
    return (jnp.asarray(step).astype(jnp.uint32) * jnp.uint32(2654435761)
            ^ jnp.uint32(salt * 97 + leaf_index))


def _block_counters(xb: jax.Array) -> jax.Array:
    """Per-element flat counter of a blocked view, from per-dim iotas
    (elementwise => sharding-friendly).  Counters live in uint32 (mod 2^32):
    >4B-element leaves reuse counter values, which only correlates the
    randomness of far-apart element pairs — harmless for unbiasedness."""
    idx = jnp.zeros(xb.shape, jnp.uint32)
    stride = 1
    for d in range(xb.ndim - 1, -1, -1):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, xb.shape, d) * \
            jnp.uint32(stride % (1 << 32))
        stride *= xb.shape[d]
    return idx


def _quantize_nd(x: jax.Array, seed: jax.Array, *, bits: int, block: int):
    """Stochastic quantization with blocks along the LAST dim only.

    Sharding-preserving by construction: leading dims keep their partitioning
    and the last-dim split (d -> (d/block, block)) divides across shards, so no
    all-gather is inserted before the quantize — flattening the whole leaf
    (the naive formulation) forces GSPMD to gather every sharded parameter
    (§Perf iteration 3: measured +21 GiB/chip of gathers on granite train).
    """
    levels = 2 ** (bits - 1) - 1
    last = x.shape[-1]
    pad = (-last) % block
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    xb = x.reshape(*x.shape[:-1], (last + pad) // block, block).astype(jnp.float32)
    scale = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    safe = jnp.where(scale > 0.0, scale, 1.0)
    v = xb * (levels / safe)
    u = uniform_from_hash(_block_counters(xb), seed)
    floor = jnp.floor(v)
    q = floor + (u < (v - floor)).astype(jnp.float32)
    return jnp.clip(q, -levels, levels).astype(jnp.int8), scale


def _dequantize_nd(codes: jax.Array, scale: jax.Array, *, bits: int,
                   orig_last: int, dtype) -> jax.Array:
    levels = 2 ** (bits - 1) - 1
    # reciprocal multiply == the kernels' dequant formulation (see kernels/ref.py)
    vals = codes.astype(jnp.float32) * (scale * jnp.float32(1.0 / levels))
    out = vals.reshape(*vals.shape[:-2], vals.shape[-2] * vals.shape[-1])
    return out[..., :orig_last].astype(dtype)


def _sparsify_nd(x: jax.Array, seed: jax.Array, *, p: float, block: int,
                 mode: str, value_dtype=jnp.float32):
    """Fixed-capacity sparse selection with blocks along the LAST dim only.

    Sharding-preserving exactly like :func:`_quantize_nd`: leading dims keep
    their partitioning, and the selection (a stable argsort + gather along the
    block axis) never mixes elements across blocks.  Canonical selection order
    — descending key, ties toward the smaller index — matches the kernels and
    the kernels/ref.py oracle word for word (same PCG counters for randk).
    """
    k, _, kpad, _ = sparse_geometry(block, p)
    last = x.shape[-1]
    pad = (-last) % block
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    xb = x.reshape(*x.shape[:-1], (last + pad) // block, block).astype(jnp.float32)
    if mode == "randk":
        key = pcg_hash(_block_counters(xb) ^ seed)
        order = jnp.argsort(key ^ jnp.uint32(0xFFFFFFFF), axis=-1, stable=True)
    else:
        order = jnp.argsort(-jnp.abs(xb), axis=-1, stable=True)
    sel = order[..., :k]
    vals = jnp.take_along_axis(xb, sel, axis=-1)
    if mode == "randk":
        vals = vals * jnp.float32(block / k)   # inclusion prob k/block => unbiased
    return vals.astype(value_dtype), \
        sparse_pack_idx(sel.astype(jnp.uint32), block=block, kpad=kpad)


def _sparse_scatter_nd(values: jax.Array, packed_idx: jax.Array, *, block: int,
                       orig_last: int, dtype) -> jax.Array:
    """Inverse of :func:`_sparsify_nd`: scatter each block's values back into
    a dense last dim.  Indices within a block are duplicate-free, so each
    output lane receives at most one value — the one-hot contraction below is
    bit-exact regardless of reduction order.  It intentionally restates
    ``sparse_scatter_2d_ref`` over the *unreshaped* leading dims: folding them
    into rows would reshape across the sharded node axis, which is exactly
    what this sharding-preserving path exists to avoid (same split as
    ``_dequantize_nd`` vs ``dequantize_2d_ref``)."""
    k = values.shape[-1]
    idx = sparse_unpack_idx(packed_idx, block=block, k=k)
    lanes = jax.lax.broadcasted_iota(
        jnp.uint32, idx.shape[:-1] + (1, block), idx.ndim)
    hit = idx[..., :, None].astype(jnp.uint32) == lanes
    dense = jnp.sum(
        jnp.where(hit, values[..., :, None].astype(jnp.float32), 0.0), axis=-2)
    out = dense.reshape(*dense.shape[:-2], dense.shape[-2] * block)
    return out[..., :orig_last].astype(dtype)


# ------------------------------------------------------------------- protocol

class WireFormat:
    """Base class: the wire-format protocol plus the shared tree plumbing.

    Subclasses implement the three per-leaf methods (``encode`` / ``decode``
    and, when they have a fused receive kernel, ``decode_axpy``); seeding,
    tree traversal, the 128-lane fused-kernel gate, and the eval_shape wire
    accounting live here once instead of per codec.
    """

    name: ClassVar[str] = "base"

    # --- per-leaf protocol ------------------------------------------------
    def encode(self, leaf: jax.Array, seed: jax.Array) -> Payload:
        raise NotImplementedError

    def decode(self, payload: Payload, like) -> jax.Array:
        raise NotImplementedError

    def decode_axpy(self, payload: Payload, acc: jax.Array, weight,
                    acc_weight=1.0) -> jax.Array:
        """``acc_weight * acc + weight * decode(payload)``; the default decodes
        at f32 then accumulates (matching the fused kernels' precision), and
        keeps ``acc``'s dtype.  Packed subclasses override with a fused
        Pallas kernel behind :meth:`_kernel_ok`."""
        d = self.decode(payload, jax.ShapeDtypeStruct(acc.shape, jnp.float32))
        return (acc_weight * acc + weight * d).astype(acc.dtype)

    @property
    def packed(self) -> bool:
        """True when the payload is a bit-packed container with a fused decode
        kernel — ``make_dist_train_step`` decodes through that kernel only when this
        holds."""
        return False

    @property
    def wire_format(self) -> str:
        """Human-readable container description (dryrun records carry it)."""
        return self.name

    @staticmethod
    def _kernel_ok(block: int) -> bool:
        """The one fused-kernel gate: the Pallas kernels' lane contract is
        ``block % 128 == 0`` (kernels/quant.py); smaller blocks (e.g. an
        8-wide bias leaf) stay on the jnp reference path — negligible traffic,
        and Mosaic never sees an off-contract tile on real TPUs."""
        return block % 128 == 0

    # --- optional cross-step codec state (per-leaf aux channel) -----------
    @property
    def stateful(self) -> bool:
        """True when the codec carries cross-step per-leaf state (e.g. the
        warm-started power-iteration factors of ``lowrank:<r>:warm``).  The
        runtime then threads :meth:`init_aux`'s tree through
        :meth:`encode_tree_stateful` under the :attr:`aux_name` key of the
        plan-keyed DistState aux — initialized by ``init_dist_state``,
        checkpointed like every other aux leaf, and re-keyed at phase
        boundaries by ``rekey_dist_state``."""
        return False

    @property
    def aux_name(self) -> str:
        """DistState aux key the codec state rides under.  Parameterized
        formats embed their identity (``wire_lowrank:2``), so restoring a
        checkpoint into a *different* parameterization fails loudly with the
        checkpoint loader's missing-leaf KeyError instead of silently feeding
        mis-shaped factors."""
        return f"wire_{self.name}"

    def init_aux(self, tree: Any) -> Dict[str, jax.Array]:
        """Initial codec state for ``tree`` (stacked ``(n, ...)`` leaves).
        Stateless formats carry none."""
        return {}

    def encode_tree_stateful(self, tree: Any, step: jax.Array, salt: int,
                             aux: Dict[str, jax.Array]):
        """Like :meth:`encode_tree`, but threading the per-leaf codec state:
        returns ``(treedef, payloads, new_aux)``.  The default (stateless
        formats) ignores and passes through ``aux`` — the runtime calls this
        unconditionally so round fns stay codec-agnostic."""
        treedef, payloads = self.encode_tree(tree, step, salt)
        return treedef, payloads, aux

    # --- tree-level plumbing (one step/salt/leaf seeding path) ------------
    def encode_tree(self, tree: Any, step: jax.Array, salt: int):
        """tree leaves (n, ...) -> (treedef, [payload per leaf]); per-leaf
        seeds from :func:`leaf_seed` (step, salt, leaf index)."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        return treedef, [self.encode(leaf, leaf_seed(step, salt, li))
                         for li, leaf in enumerate(leaves)]

    def decode_tree(self, treedef, payloads, like_tree: Any) -> Any:
        likes = jax.tree_util.tree_leaves(like_tree)
        return jax.tree_util.tree_unflatten(
            treedef, [self.decode(p, like) for p, like in zip(payloads, likes)])

    def decode_axpy_tree(self, treedef, payloads, acc_tree: Any, weight,
                         acc_weight=1.0) -> Any:
        """``acc_weight * acc + weight * decode(payloads)`` leafwise; both
        weights may be floats or traced scalars (ECD's 1-2/s decay and 2/s
        blend ride the fused kernels' scalar operands)."""
        accs = jax.tree_util.tree_leaves(acc_tree)
        return jax.tree_util.tree_unflatten(
            treedef, [self.decode_axpy(p, acc, weight, acc_weight)
                      for p, acc in zip(payloads, accs)])

    # --- eval_shape-derived wire accounting -------------------------------
    def wire_nbytes(self, tree: Any) -> int:
        """Measured wire bytes of one encoded gossip payload for ``tree``
        (shape-only: evaluated via eval_shape, nothing is computed)."""
        payloads = jax.eval_shape(
            lambda t: self.encode_tree(t, jnp.zeros((), jnp.int32), 0)[1], tree)
        return _payload_nbytes(payloads)

    def wire_bits_per_element(self, shape=None) -> float:
        """Wire bits/element from the *actual* payload containers, measured on
        a ``shape``-sized leaf (default: one full block, which is also the
        asymptotic figure for leaves that fill whole blocks)."""
        n = int(np.prod(shape)) if shape is not None else \
            getattr(self, "block", 128)
        return _measured_wire_bits(self, n)


@functools.lru_cache(maxsize=256)
def _measured_wire_bits(wire: WireFormat, n: int) -> float:
    return 8.0 * wire.wire_nbytes(
        jax.ShapeDtypeStruct((n,), jnp.float32)) / n


# ------------------------------------------------------------ implementations

@dataclasses.dataclass(frozen=True)
class QuantWire(WireFormat):
    """Quantized wire format: stochastic ``bits``-bit codes + per-block scales.

    ``pack=True`` (default for bits in 2..7) bit-packs the codes into uint32
    words *before* the collective-permute using the bit-exact stream layout
    shared with the Pallas kernels (kernels/quant.py) and the jnp reference
    codec (kernels/ref.py): codes straddle word boundaries, so *every* width
    ships exactly ``bits`` wire bits/element plus the per-block scale.  The
    stacked payload that ``jnp.roll`` moves over the node axis is therefore
    the packed words + scales: a ``bits=3`` ring step ships ~3.03
    bits/element — the paper's low-bit sweet spot as actual wire bytes (the
    paper's own MPI implementation sent one value per byte even at 4 bits).

    Packing is along the last (block) dim only, so it preserves the leaf's
    leading-dim sharding exactly like :func:`_quantize_nd` does.
    """

    bits: int = 8
    block: int = 1024
    pack: Optional[bool] = None

    name: ClassVar[str] = "quant"

    def __post_init__(self):
        assert 2 <= self.bits <= 8, "2..8-bit levels supported"
        if self.pack:   # explicit request: the geometry must support it
            assert_packable(self.bits, self.block)

    @property
    def packed(self) -> bool:
        """Auto mode (``pack=None``) packs whenever the block geometry allows
        it; a block that is not a whole number of stream groups falls back to
        the int8 container (honest ~8 measured wire bits)."""
        return packed_auto(self.bits, self.block) if self.pack is None else self.pack

    @property
    def levels(self) -> int:
        return 2 ** (self.bits - 1) - 1

    @property
    def wire_format(self) -> str:
        return "packed-stream-u32" if self.packed else "int8"

    def _block_for(self, last: int) -> int:
        if self.packed:
            return aligned_block(self.block, last, bits=self.bits)
        return min(self.block, max(last, 1))

    def encode(self, leaf: jax.Array, seed: jax.Array) -> Payload:
        """leaf (..., d) -> {codes (..., nblk, W) uint32 packed words (or
        (..., nblk, block) int8 unpacked), scale (..., nblk, 1) f32} — blocked
        over the last dim so the quantize stays shard-local (_quantize_nd)."""
        block = self._block_for(leaf.shape[-1])
        codes, scale = _quantize_nd(leaf, seed, bits=self.bits, block=block)
        if self.packed:
            codes = pack_codes(codes, bits=self.bits)
        return {"codes": codes, "scale": scale}

    def decode(self, payload: Payload, like) -> jax.Array:
        codes = unpack_codes(payload["codes"], bits=self.bits) \
            if self.packed else payload["codes"]
        return _dequantize_nd(codes, payload["scale"], bits=self.bits,
                              orig_last=like.shape[-1], dtype=like.dtype)

    def decode_axpy(self, payload: Payload, acc: jax.Array, weight,
                    acc_weight=1.0) -> jax.Array:
        """One fused Pallas kernel per packed leaf: unpack -> dequantize ->
        scale-and-accumulate in a single VMEM pass, so neither the
        reconstructed fp32 neighbor tensor nor a pre-scaled accumulator ever
        lands in HBM.  Off-gate (unpacked, or block below the 128-lane
        contract) falls back to the base jnp path."""
        block = payload["codes"].shape[-1] * 32 // self.bits \
            if self.packed else payload["codes"].shape[-1]
        if self.packed and self._kernel_ok(block):
            _DECODES["rows"] += 1
            return _fused_axpy_leaf(payload["codes"], payload["scale"], acc,
                                    bits=self.bits, weight=weight,
                                    acc_weight=acc_weight)
        _DECODES["jnp"] += 1
        return super().decode_axpy(payload, acc, weight, acc_weight)


# leaves traced by ``QuantWire.decode_axpy``, by path (``decode_stats``)
_DECODES: collections.Counter = collections.Counter()


def decode_stats() -> Dict[str, int]:
    """How many leaves :meth:`QuantWire.decode_axpy` has traced in this
    process, by path: ``rows`` through the fused kernel over the leaf's own
    row view (:func:`_fused_axpy_leaf`), ``jnp`` on the base class's
    reference path (the int8 container, or a block below the 128-lane gate).
    A decode traced once counts once, however many nodes or steps run it."""
    return {path: _DECODES[path] for path in ("rows", "jnp")}


def _fused_axpy_leaf(codes: jax.Array, scale: jax.Array, acc: jax.Array, *,
                     bits: int, weight, acc_weight=1.0) -> jax.Array:
    """One leaf of :meth:`QuantWire.decode_axpy` through the fused kernel.

    acc (lead..., last), codes (lead..., nblk, W) uint32 and scale
    (lead..., nblk, 1) go to the kernel with their leading dims merged into
    rows — a bitcast of each, so nothing is copied around the call; the
    leading (node) axis stays outermost, so the view preserves leading-dim
    sharding under shard_map."""
    rows = int(np.prod(acc.shape[:-1], dtype=np.int64))
    nblk, w = codes.shape[-2:]
    out = unpack_dequant_axpy(
        codes.reshape(rows, nblk, w), scale.reshape(rows, nblk),
        acc.reshape(rows, acc.shape[-1]),
        bits=bits, weight=weight, acc_weight=acc_weight,
        interpret=_kops.interpret_kernels())
    return out.reshape(acc.shape).astype(acc.dtype)


@dataclasses.dataclass(frozen=True)
class SparseWire(WireFormat):
    """Sparse wire format: fixed-capacity values + bit-packed indices.

    The fixed-capacity counterpart of :class:`QuantWire`: every
    ``block``-element block of a leaf's last dim keeps ``k = ceil(p * block)``
    values (``randk``: a seeded uniform k-subset rescaled by ``block/k``;
    ``topk``: the k largest magnitudes), and the stacked payload the gossip
    collective-permute moves is ``{values: (..., nblk, k) fp32/fp16,
    idx: (..., nblk, words) uint32}`` — the block-local indices bit-packed
    to ``ceil(log2(block))`` bits each via the same stream layout as the
    quantized codec.  Fixed capacity keeps every shape static (SPMD-friendly:
    one collective-permute per leaf, no data-dependent sizes), and blocking
    along the last dim only preserves leading-dim sharding exactly like
    ``_quantize_nd``.
    """

    p: float = 0.25
    block: int = 128
    mode: str = "randk"
    value_dtype: str = "float32"    # "float32" | "float16" (wire container)

    name: ClassVar[str] = "sparse"

    def __post_init__(self):
        assert 0.0 < self.p <= 1.0, f"keep fraction p must be in (0, 1], got {self.p}"
        assert self.mode in SPARSE_MODES, self.mode
        assert self.value_dtype in ("float32", "float16"), self.value_dtype

    @property
    def packed(self) -> bool:
        """The index stream is always bit-packed — there is no unpacked
        container for this codec."""
        return True

    @property
    def wire_format(self) -> str:
        vals = "f16" if self.value_dtype == "float16" else "f32"
        return f"sparse-{self.mode}-{vals}+packed-idx-u32"

    @property
    def _vdtype(self):
        return jnp.float16 if self.value_dtype == "float16" else jnp.float32

    def _block_for(self, last: int) -> int:
        return min(self.block, max(last, 1))

    def encode(self, leaf: jax.Array, seed: jax.Array) -> Payload:
        block = self._block_for(leaf.shape[-1])
        vals, idx = _sparsify_nd(leaf, seed, p=self.p, block=block,
                                 mode=self.mode, value_dtype=self._vdtype)
        return {"values": vals, "idx": idx}

    def decode(self, payload: Payload, like) -> jax.Array:
        return _sparse_scatter_nd(
            payload["values"], payload["idx"],
            block=self._block_for(like.shape[-1]),
            orig_last=like.shape[-1], dtype=like.dtype)

    def decode_axpy(self, payload: Payload, acc: jax.Array, weight,
                    acc_weight=1.0) -> jax.Array:
        """One fused Pallas kernel per leaf: unpack the index stream ->
        scatter -> scale-and-accumulate in a single VMEM pass (the
        reconstructed dense fp32 neighbor delta never lands in HBM).  Same
        gate as the quantized codec: blocks off the 128-lane kernel contract
        take the base jnp path."""
        block = self._block_for(acc.shape[-1])
        if self._kernel_ok(block):
            return _fused_sparse_axpy_leaf(
                payload["values"], payload["idx"], acc, block=block,
                weight=weight, acc_weight=acc_weight)
        return super().decode_axpy(payload, acc, weight, acc_weight)


def _fused_sparse_axpy_leaf(values: jax.Array, packed_idx: jax.Array,
                            acc: jax.Array, *, block: int, weight,
                            acc_weight=1.0) -> jax.Array:
    """One leaf of :meth:`SparseWire.decode_axpy` through the fused kernel:
    fold (lead..., nblk, k) into a (lead*nblk, k) 2-D view — the leading
    (node) axis stays outermost, so the fold preserves leading-dim sharding
    under shard_map, exactly like :func:`_fused_axpy_leaf`."""
    nblk = values.shape[-2]
    lead = acc.shape[:-1]
    orig_last = acc.shape[-1]
    accf = acc.astype(jnp.float32)
    pad = nblk * block - orig_last
    if pad:
        accf = jnp.pad(accf, [(0, 0)] * (accf.ndim - 1) + [(0, pad)])
    rows = int(np.prod(lead, dtype=np.int64)) * nblk
    out = sparse_scatter_axpy_2d(
        values.reshape(rows, values.shape[-1]),
        packed_idx.reshape(rows, packed_idx.shape[-1]),
        accf.reshape(rows, block),
        weight=weight, acc_weight=acc_weight,
        interpret=_kops.interpret_kernels())
    out = out.reshape(*lead, nblk * block)[..., :orig_last]
    return out.astype(acc.dtype)


def _sign_nd(x: jax.Array, *, block: int, scale_mode: str):
    """1-bit sign codec with blocks along the LAST dim only.

    Sharding-preserving exactly like :func:`_quantize_nd`: leading dims keep
    their partitioning, the last-dim split never mixes elements across blocks,
    and the width-1 :func:`pack_uint` stream ships 32 sign bits per uint32
    word.  Deterministic — the seed plumbing carries no entropy here (like
    topk selection), so sharded and stacked payloads are trivially
    bit-identical."""
    last = x.shape[-1]
    pad = (-last) % block
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    xb = x.reshape(*x.shape[:-1], (last + pad) // block, block).astype(jnp.float32)
    bits = (xb >= 0.0).astype(jnp.uint32)
    if scale_mode == "mean":
        scale = jnp.mean(jnp.abs(xb), axis=-1, keepdims=True)
    else:
        scale = jnp.sqrt(jnp.mean(xb * xb, axis=-1, keepdims=True))
    return pack_uint(bits, bits=1), scale


def _sign_decode_nd(codes: jax.Array, scale: jax.Array, *, orig_last: int,
                    dtype) -> jax.Array:
    u = unpack_uint(codes, bits=1).astype(jnp.float32)
    vals = (u * 2.0 - 1.0) * scale
    out = vals.reshape(*vals.shape[:-2], vals.shape[-2] * vals.shape[-1])
    return out[..., :orig_last].astype(dtype)


@dataclasses.dataclass(frozen=True)
class SignWire(WireFormat):
    """1-bit sign wire format: per-block sign bits + one magnitude scale.

    The codec that motivates the error-feedback algorithm family: each
    ``block``-element block of a leaf's last dim ships 1 sign bit per element
    (packed 32-per-word through the same width-1 stream layout the sparse
    index codec uses) plus one f32 scale — a measured ``1 + 32/block``
    wire bits/element (~1.03 at block 1024), the most aggressive compression
    in the registry.  ``scale="mean"`` decodes ``mean|x| * sign(x)``, the
    scaled-sign compressor with delta-contraction
    ``||x - C(x)||^2 <= (1 - 1/block) ||x||^2`` — *biased*, so plain DCD/ECD
    (which assume unbiasedness) are outside their guarantees while
    CHOCO/DeepSqueeze converge.  ``scale="l2"`` is the signSGD-style
    ``||x||_2/sqrt(block)`` normalization (not contractive in general).
    Deterministic — the seed is unused, like topk selection.
    """

    block: int = 1024
    scale: str = "mean"

    name: ClassVar[str] = "sign"

    def __post_init__(self):
        assert self.scale in SIGN_SCALE_MODES, \
            f"sign scale modes are {SIGN_SCALE_MODES}, got {self.scale}"
        assert self.block % 32 == 0, \
            f"sign block must pack whole uint32 words (block % 32 == 0), " \
            f"got {self.block}"

    @property
    def packed(self) -> bool:
        """The sign stream is always bit-packed — there is no unpacked
        container for this codec."""
        return True

    @property
    def wire_format(self) -> str:
        return f"sign-{self.scale}-packed-u32"

    def _block_for(self, last: int) -> int:
        return aligned_block(self.block, last, bits=1)

    def encode(self, leaf: jax.Array, seed: jax.Array) -> Payload:
        """leaf (..., d) -> {codes (..., nblk, block/32) uint32 packed sign
        bits, scale (..., nblk, 1) f32} — blocked over the last dim so the
        encode stays shard-local (same split as ``_quantize_nd``)."""
        block = self._block_for(leaf.shape[-1])
        codes, scale = _sign_nd(leaf, block=block, scale_mode=self.scale)
        return {"codes": codes, "scale": scale}

    def decode(self, payload: Payload, like) -> jax.Array:
        return _sign_decode_nd(payload["codes"], payload["scale"],
                               orig_last=like.shape[-1], dtype=like.dtype)

    def decode_axpy(self, payload: Payload, acc: jax.Array, weight,
                    acc_weight=1.0) -> jax.Array:
        """One fused Pallas kernel per leaf: unpack 32 bit planes -> sign
        decode -> scale-and-accumulate in a single VMEM pass.  Same gate as
        the quantized codec: blocks off the 128-lane kernel contract take the
        base jnp path."""
        block = payload["codes"].shape[-1] * 32
        if self._kernel_ok(block):
            return _fused_sign_axpy_leaf(payload["codes"], payload["scale"],
                                         acc, weight=weight,
                                         acc_weight=acc_weight)
        return super().decode_axpy(payload, acc, weight, acc_weight)


def _fused_sign_axpy_leaf(codes: jax.Array, scale: jax.Array, acc: jax.Array,
                          *, weight, acc_weight=1.0) -> jax.Array:
    """One leaf of :meth:`SignWire.decode_axpy` through the fused kernel:
    fold (lead..., nblk, W) into a (lead*nblk, W) 2-D view — the leading
    (node) axis stays outermost, so the fold preserves leading-dim sharding
    under shard_map, exactly like :func:`_fused_axpy_leaf`."""
    block = codes.shape[-1] * 32
    nblk = codes.shape[-2]
    lead = acc.shape[:-1]
    orig_last = acc.shape[-1]
    accf = acc.astype(jnp.float32)
    pad = nblk * block - orig_last
    if pad:
        accf = jnp.pad(accf, [(0, 0)] * (accf.ndim - 1) + [(0, pad)])
    rows = int(np.prod(lead, dtype=np.int64)) * nblk
    out = unpack_sign_axpy_2d(
        codes.reshape(rows, codes.shape[-1]),
        scale.reshape(rows, 1),
        accf.reshape(rows, block),
        weight=weight, acc_weight=acc_weight,
        interpret=_kops.interpret_kernels())
    out = out.reshape(*lead, nblk * block)[..., :orig_last]
    return out.astype(acc.dtype)


@dataclasses.dataclass(frozen=True)
class Fp16Wire(WireFormat):
    """Half-precision wire: cast values to fp16 for the collective-permute.

    Deterministic (the seed is unused), 16 wire bits/element, relative error
    bounded by the fp16 rounding (2^-11) — the classic "compression-free"
    baseline between full precision and the quantized codecs."""

    name: ClassVar[str] = "fp16"

    def encode(self, leaf: jax.Array, seed: jax.Array) -> Payload:
        return {"values": leaf.astype(jnp.float16)}

    def decode(self, payload: Payload, like) -> jax.Array:
        return payload["values"].astype(like.dtype)


@dataclasses.dataclass(frozen=True)
class IdentityWire(WireFormat):
    """No-op wire format: the full-precision leaf IS the payload (alpha = 0;
    DCD/ECD degenerate to exact D-PSGD)."""

    name: ClassVar[str] = "identity"

    def encode(self, leaf: jax.Array, seed: jax.Array) -> Payload:
        return {"values": leaf}

    def decode(self, payload: Payload, like) -> jax.Array:
        return payload["values"].astype(like.dtype)


# ------------------------------------------------------------ low-rank codec

def _batch_dot(a: jax.Array, b: jax.Array, a_dim: int, b_dim: int) -> jax.Array:
    """``dot_general`` contracting ``a``'s axis ``a_dim`` (negative, counted
    from the end) with ``b``'s ``b_dim``, batching over the shared leading
    dims.  Every low-rank matmul — project, re-project, reconstruct — goes
    through this one helper so the dimension numbers (and therefore the
    f32 accumulation order) are identical across encode, decode, and the
    kernels/ref.py oracles."""
    lead = tuple(range(a.ndim - 2))
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        (((a.ndim + a_dim,), (b.ndim + b_dim,)), (lead, lead)),
        precision=F32_MATMUL, preferred_element_type=jnp.float32)


@dataclasses.dataclass(frozen=True)
class LowRankWire(WireFormat):
    """Rank-r power-iteration wire format (PowerGossip, Vogels et al.).

    The first codec that exploits leaf *structure* instead of treating every
    leaf as a flat stream: a matrix leaf — stacked shape ``(nodes..., m, n)``,
    i.e. ``ndim >= 3`` with the leading node axis — ships one power-iteration
    step of its last-two-dims view as rank-``r`` factors:

        P  = M @ V0          (project onto the right factor)
        P  = MGS(P)          (orthonormalize columns, safe-norm'd)
        Vt = M^T @ P         (re-project)
        payload = {p: (..., m, r) f32, v: (..., n, r) f32}
        decode  = P @ Vt^T   (rank-r reconstruction)

    so the wire cost is ``32·r·(m+n)`` bits against the dense ``32·m·n`` —
    measured off the real payload containers via eval_shape like every other
    format, the formula is just what the measurement comes out to.  Leaves
    with ``ndim <= 2`` (a stacked 1-D param) fall through to the fp16
    container: rank structure is a property of matrices, and the small leaves
    are negligible traffic.

    ``warm=False`` (default) re-seeds ``V0`` from the ``(step, salt, leaf)``
    counter every round — a seeded uniform ``(n, r)`` start shared across the
    node axis, so the per-shard ``(1, m, n)`` slab and the stacked
    ``(nodes, m, n)`` leaf encode bit-identical words (the sharded==stacked
    differential contract).  ``warm=True`` is the PowerGossip mode: the codec
    declares itself :attr:`stateful` and carries last round's ``Vt`` per
    matrix leaf through the aux channel (:meth:`init_aux` /
    :meth:`encode_tree_stateful`), making each round one more subspace
    iteration on the evolving difference — reconstruction error *decreases*
    with rounds per step where every other codec's is i.i.d. per round.  The
    warm factors ride the plan-keyed DistState aux under
    ``wire_lowrank:<r>`` (rank-embedded: restoring into a different rank
    KeyErrors in the checkpoint loader), and phase boundaries re-seed them
    via ``rekey_dist_state`` exactly like algorithm aux.

    The decode side routes matrix leaves through the fused
    factor-matmul-accumulate Pallas kernel (`kernels/lowrank.py`) behind the
    same 128-lane gate as every packed codec; the kernel tiles only output
    rows with the contraction unsplit, so kernel == oracle == codec word for
    word."""

    rank: int = 2
    warm: bool = False

    name: ClassVar[str] = "lowrank"

    def __post_init__(self):
        assert 1 <= int(self.rank) <= 128, \
            f"lowrank rank must be in 1..128, got {self.rank}"
        object.__setattr__(self, "rank", int(self.rank))
        object.__setattr__(self, "warm", bool(self.warm))

    @property
    def packed(self) -> bool:
        """Factor payloads have a fused decode-axpy kernel (the gate is the
        same 128-lane contract); the containers are plain f32 factors, so
        "packed" here keys the fused receive path, not bit-packing."""
        return True

    @property
    def wire_format(self) -> str:
        return f"lowrank-r{self.rank}-{'warm' if self.warm else 'cold'}-f32"

    @property
    def stateful(self) -> bool:
        return self.warm

    @property
    def aux_name(self) -> str:
        return f"wire_lowrank:{self.rank}"

    @staticmethod
    def _eligible(shape) -> bool:
        """Matrix routing is by STACKED shape: ``(nodes..., m, n)`` needs
        ``ndim >= 3`` so that a stacked 1-D param (``(nodes, d)``) is not
        mistaken for a matrix — and so the per-shard ``(1, m, n)`` slab
        inside shard_map routes identically to the stacked leaf."""
        return len(shape) >= 3

    def _factor_init(self, n: int, seed) -> jax.Array:
        """Seeded pseudo-random ``(n, r)`` start factor, shared across the
        node axis (no leading-dim dependence — the slab/stacked bit-equality
        contract).  Centered uniform from the same counter-hash primitive as
        the stochastic quantizer; never zero, so the safe-norm
        orthonormalization cannot collapse the subspace."""
        shape = (n, self.rank)
        idx = (jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
               * jnp.uint32(self.rank)
               + jax.lax.broadcasted_iota(jnp.uint32, shape, 1))
        u = uniform_from_hash(idx, jnp.asarray(seed).reshape(()).astype(jnp.uint32))
        return (u - jnp.float32(0.5)).astype(jnp.float32)

    def _encode_leaf(self, leaf: jax.Array, v0: jax.Array):
        """One power-iteration step of ``leaf``'s trailing (m, n) view against
        ``v0`` ((n, r) cold start, or (..., n, r) warm factors batched over
        the node axis).  Returns (payload, new right factor)."""
        from repro.kernels.ref import lowrank_orthonormalize_ref

        m = leaf.astype(jnp.float32)
        if v0.ndim == 2:
            p = jax.lax.dot_general(
                m, v0, (((m.ndim - 1,), (0,)), ((), ())),
                precision=F32_MATMUL, preferred_element_type=jnp.float32)
        else:
            p = _batch_dot(m, v0, -1, -2)
        p = lowrank_orthonormalize_ref(p)
        vt = _batch_dot(m, p, -2, -2)
        return {"p": p, "v": vt}, vt

    # --- per-leaf protocol -------------------------------------------------
    def encode(self, leaf: jax.Array, seed: jax.Array) -> Payload:
        """Cold-start encode (also the warm format's shape-accounting path:
        factor shapes don't depend on warmth).  1-D leaves ride fp16."""
        if not self._eligible(leaf.shape):
            return {"values": leaf.astype(jnp.float16)}
        payload, _ = self._encode_leaf(leaf, self._factor_init(leaf.shape[-1],
                                                               seed))
        return payload

    def decode(self, payload: Payload, like) -> jax.Array:
        if "values" in payload:
            return payload["values"].astype(like.dtype)
        return _batch_dot(payload["p"], payload["v"], -1, -1).astype(like.dtype)

    def decode_axpy(self, payload: Payload, acc: jax.Array, weight,
                    acc_weight=1.0) -> jax.Array:
        """Matrix leaves route through the fused factor-matmul-accumulate
        kernel: the rank-r reconstruction is built directly into the mix
        accumulator, one (m, n) VMEM pass per node slab, dense fp32 never in
        HBM.  Off-gate (last dim below the 128-lane contract) and fp16
        fallthrough leaves take the base jnp path."""
        if "values" in payload or not self._kernel_ok(acc.shape[-1]):
            return super().decode_axpy(payload, acc, weight, acc_weight)
        return _fused_lowrank_axpy_leaf(payload["p"], payload["v"], acc,
                                        weight=weight, acc_weight=acc_weight)

    # --- cross-step codec state (the warm-start factor channel) -----------
    def init_aux(self, tree: Any) -> Dict[str, jax.Array]:
        """Warm-start factors for every matrix leaf of the stacked ``tree``,
        keyed by flatten-order leaf index.  A pure function of shapes — the
        cold factor at a fixed constant seed, broadcast over the node axis —
        so ``init_dist_state`` and ``rekey_dist_state`` produce identical
        factors and a phase boundary is an honest re-key, not hidden state.
        Never zeros: a zero factor is a fixed point of the power iteration
        (P = M @ 0 = 0 stays 0 through the safe-norm MGS)."""
        if not self.warm:
            return {}
        aux: Dict[str, jax.Array] = {}
        for li, leaf in enumerate(jax.tree_util.tree_leaves(tree)):
            if self._eligible(leaf.shape):
                f = self._factor_init(leaf.shape[-1],
                                      jnp.uint32(0x9E3779B9 ^ (li * 101)))
                aux[str(li)] = jnp.broadcast_to(
                    f, leaf.shape[:-2] + f.shape)
        return aux

    def encode_tree_stateful(self, tree: Any, step: jax.Array, salt: int,
                             aux: Dict[str, jax.Array]):
        """Warm path: project each matrix leaf against ITS carried factor and
        write the re-projected factor back — one more power iteration per
        round.  Cold mode defers to the stateless tree encode."""
        if not self.warm:
            return super().encode_tree_stateful(tree, step, salt, aux)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        new_aux = dict(aux)
        payloads = []
        for li, leaf in enumerate(leaves):
            if self._eligible(leaf.shape):
                payload, vt = self._encode_leaf(leaf, aux[str(li)])
                new_aux[str(li)] = vt
                payloads.append(payload)
            else:
                payloads.append(
                    self.encode(leaf, leaf_seed(step, salt, li)))
        return treedef, payloads, new_aux

    # --- accounting --------------------------------------------------------
    def wire_bits_per_element(self, shape=None) -> float:
        """Measured off the real factor containers via eval_shape.  A 2-D
        ``(m, n)`` shape is taken as the un-stacked matrix leaf (measured as
        its ``(1, m, n)`` stacked form — same element count, so the figure is
        exactly ``32·r·(m+n)/(m·n)``); no shape gives the bulk asymptote on a
        1024x1024 leaf; 1-D shapes report the fp16 fallthrough figure."""
        if shape is None:
            shape = (1, 1024, 1024)
        shape = tuple(int(s) for s in shape)
        if len(shape) == 2:
            shape = (1,) + shape
        leaf = jax.ShapeDtypeStruct(shape if shape else (1,), jnp.float32)
        payload = jax.eval_shape(
            lambda l: self.encode(l, jnp.zeros((), jnp.uint32)), leaf)
        return 8.0 * _payload_nbytes(payload) / \
            float(np.prod(shape, dtype=np.int64) if shape else 1)

    @staticmethod
    def parse_spec_args(args) -> Dict[str, Any]:
        """Spec-arg parser for ``lowrank:<rank>[:warm]``: the bare literal
        ``warm`` sets the flag (``lowrank:2:warm``); ``key=value`` args pass
        through; the single positional is the rank."""
        kwargs: Dict[str, Any] = {}
        pos = 0
        for part in args:
            for piece in part.split(","):
                if not piece:
                    continue
                if piece == "warm":
                    kwargs["warm"] = True
                elif "=" in piece:
                    key, val = piece.split("=", 1)
                    kwargs[key] = _coerce(val)
                else:
                    if pos >= 1:
                        raise ValueError(
                            f"lowrank spec takes one positional arg (rank); "
                            f"unexpected {piece!r}")
                    kwargs["rank"] = int(piece)
                    pos += 1
        return kwargs


def _fused_lowrank_axpy_leaf(p: jax.Array, v: jax.Array, acc: jax.Array, *,
                             weight, acc_weight=1.0) -> jax.Array:
    """One matrix leaf of :meth:`LowRankWire.decode_axpy` through the fused
    kernel: fold the leading (node) dims into a batch axis and vmap the 2-D
    kernel over it — the leading axis stays outermost, so the fold preserves
    leading-dim sharding under shard_map exactly like the other fused
    leaves (the right factor differs per node, so rows cannot fold)."""
    from repro.kernels.lowrank import lowrank_axpy_2d

    lead = acc.shape[:-2]
    mm, nn = acc.shape[-2:]
    r = p.shape[-1]
    b = int(np.prod(lead, dtype=np.int64)) if lead else 1
    fn = functools.partial(lowrank_axpy_2d, weight=weight,
                           acc_weight=acc_weight,
                           interpret=_kops.interpret_kernels())
    out = jax.vmap(fn)(p.reshape(b, mm, r), v.reshape(b, nn, r),
                       acc.astype(jnp.float32).reshape(b, mm, nn))
    return out.reshape(*lead, mm, nn).astype(acc.dtype)


# --------------------------------------------------------- adaptive combinator

def leaf_path_str(path) -> str:
    """``decoder/kernel``-style leaf path — the SAME naming the checkpoint
    manifests use (``repro.checkpoint``), so the patterns that select a leaf
    in an ``adaptive`` spec select the same leaf in a saved DistState."""
    def one(p):
        if hasattr(p, "key"):
            return str(p.key)
        if hasattr(p, "idx"):
            return str(p.idx)
        if hasattr(p, "name"):
            return str(p.name)
        return str(p)
    return "/".join(one(p) for p in path)


def routed_size(shape) -> int:
    """Per-replica element count of a leaf — what ``adaptive`` thresholds
    compare against.  Every runtime surface (the sharded runtime, the stacked
    reference, the dryrun accounting) presents leaves *stacked* along a
    leading node axis, so the leading dim is excluded: a 64-wide bias is
    "small" at any node count, and the routing decision is identical outside
    the jit, inside ``shard_map`` (where the leading dim is the per-shard
    slab), and under ``eval_shape``.  Rank-1 leaves are taken whole — the
    stacked form of a scalar parameter."""
    if len(shape) > 1:
        return int(np.prod(shape[1:], dtype=np.int64))
    return int(np.prod(shape, dtype=np.int64)) if shape else 1


@dataclasses.dataclass(frozen=True)
class AdaptiveWire(WireFormat):
    """Per-leaf size-adaptive combinator: one wire format per *leaf*, not per
    tree.

    Routing (static — shapes are compile-time, so jit sees one fixed codec
    per leaf, and the compiled collective-permutes move mixed payloads):

    1. ``leaf.<pattern>=`` overrides first: a leaf whose ``/``-joined path
       (:func:`leaf_path_str` — checkpoint-manifest naming) matches an
       override's fnmatch pattern uses that sub-format, first match wins.
    2. Otherwise by size: leaves with fewer than ``threshold`` per-replica
       elements (:func:`routed_size` — the leading stacked node axis is
       excluded) encode through ``small``, the rest through ``large``.

    Spec grammar (sub-specs may themselves contain ``:``/``,`` — every part
    after a ``small=`` / ``large=`` / ``leaf.<pattern>=`` key that does not
    start a new key is absorbed into that key's sub-spec):

        adaptive:<threshold>[:small=<spec>][:large=<spec>][:leaf.<pat>=<spec>]*
        adaptive:4096:small=fp16:large=quant:4
        adaptive:8192:large=sparse:0.25:topk:leaf.embed*=quant:bits=3,block=1024

    Everything else is inherited unchanged: the tree plumbing derives the SAME
    ``(step, salt, leaf index)`` seeds as every other format (payloads stay
    bit-identical between the sharded runtime and the stacked
    :class:`~repro.core.algorithms.GossipReference`), the aux/state trees of
    DCD/ECD/CHOCO/DeepSqueeze are keyed per shift exactly as today (the codec
    never touches them), and ``wire_nbytes`` measures each leaf through its
    routed sub-format's real containers via ``eval_shape``.  Nesting adaptive
    inside adaptive is refused — routing must stay a single static decision.

    The per-leaf methods (``encode``/``decode``/``decode_axpy``) see no path,
    so direct per-leaf calls route by size alone; path overrides apply on the
    tree-level surfaces (``encode_tree`` & co.), which is where both runtimes
    live."""

    threshold: int = 4096
    small: Any = "fp16"            # WireFormat | spec str (normalized in init)
    large: Any = "quant:4"
    overrides: Tuple[Tuple[str, Any], ...] = ()   # ((fnmatch pattern, wire)..)

    name: ClassVar[str] = "adaptive"

    def __post_init__(self):
        assert int(self.threshold) >= 0, self.threshold
        object.__setattr__(self, "threshold", int(self.threshold))
        for fld in ("small", "large"):
            w = make_wire_format(getattr(self, fld))
            assert not isinstance(w, AdaptiveWire), \
                "adaptive wire formats do not nest"
            object.__setattr__(self, fld, w)
        ov = self.overrides
        if isinstance(ov, dict):
            ov = tuple(ov.items())
        norm = []
        for pat, w in ov:
            w = make_wire_format(w)
            assert not isinstance(w, AdaptiveWire), \
                "adaptive wire formats do not nest"
            norm.append((str(pat), w))
        object.__setattr__(self, "overrides", tuple(norm))

    # --- routing ----------------------------------------------------------
    def route_size(self, shape) -> WireFormat:
        """Size-only routing (what the per-leaf protocol can see)."""
        return self.small if routed_size(shape) < self.threshold else self.large

    def route(self, path: str, shape) -> WireFormat:
        """Full routing: first matching ``leaf.<pattern>=`` override, else by
        per-replica size."""
        import fnmatch

        for pat, w in self.overrides:
            if fnmatch.fnmatchcase(path, pat):
                return w
        return self.route_size(shape)

    def leaf_wires(self, tree: Any) -> Tuple[Tuple[str, WireFormat], ...]:
        """``(path, routed sub-format)`` per leaf in flatten order — the
        audit surface (dryrun records ``wire_spec_per_leaf`` from it)."""
        return tuple(
            (leaf_path_str(p), self.route(leaf_path_str(p), leaf.shape))
            for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0])

    # --- per-leaf protocol (size-routed: no path at this level) -----------
    def encode(self, leaf: jax.Array, seed: jax.Array) -> Payload:
        return self.route_size(leaf.shape).encode(leaf, seed)

    def decode(self, payload: Payload, like) -> jax.Array:
        return self.route_size(like.shape).decode(payload, like)

    def decode_axpy(self, payload: Payload, acc: jax.Array, weight,
                    acc_weight=1.0) -> jax.Array:
        return self.route_size(acc.shape).decode_axpy(payload, acc, weight,
                                                      acc_weight)

    # --- tree-level plumbing: path-aware, same (step, salt, leaf) seeding --
    def encode_tree(self, tree: Any, step: jax.Array, salt: int):
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        return treedef, [
            self.route(leaf_path_str(p), leaf.shape).encode(
                leaf, leaf_seed(step, salt, li))
            for li, (p, leaf) in enumerate(flat)]

    def decode_tree(self, treedef, payloads, like_tree: Any) -> Any:
        flat = jax.tree_util.tree_flatten_with_path(like_tree)[0]
        return jax.tree_util.tree_unflatten(
            treedef,
            [self.route(leaf_path_str(p), like.shape).decode(pl, like)
             for pl, (p, like) in zip(payloads, flat)])

    def decode_axpy_tree(self, treedef, payloads, acc_tree: Any, weight,
                         acc_weight=1.0) -> Any:
        flat = jax.tree_util.tree_flatten_with_path(acc_tree)[0]
        return jax.tree_util.tree_unflatten(
            treedef,
            [self.route(leaf_path_str(p), acc.shape).decode_axpy(
                pl, acc, weight, acc_weight)
             for pl, (p, acc) in zip(payloads, flat)])

    # --- accounting / display --------------------------------------------
    def wire_bits_per_element(self, shape=None) -> float:
        """With a shape: measured on that leaf through its size-routed
        sub-format (a 64-element bias measures 16 b/e under ``small=fp16``
        while the matmul leaves measure the ``large=`` figure).  With no
        shape: the ``large`` route's asymptotic figure — model wire traffic
        is dominated by the large leaves, so that is the honest single
        number for netsim costing (``wire_nbytes`` on the real tree remains
        the exact per-leaf account)."""
        if shape is None:
            return self.large.wire_bits_per_element()
        return self.route_size(shape).wire_bits_per_element(shape)

    @property
    def packed(self) -> bool:
        """Fused-capable iff any routed sub-format is; each sub-format's own
        ``decode_axpy`` still applies its own 128-lane kernel gate per leaf."""
        return self.small.packed or self.large.packed or \
            any(w.packed for _, w in self.overrides)

    @property
    def wire_format(self) -> str:
        ov = "".join(f";{pat}={w.wire_format}" for pat, w in self.overrides)
        return (f"adaptive<{self.threshold};small={self.small.wire_format};"
                f"large={self.large.wire_format}{ov}>")

    @staticmethod
    def parse_spec_args(args) -> Dict[str, Any]:
        """Spec-arg parser for :func:`make_wire_format` (hooked via the
        ``parse_spec_args`` attribute): sub-specs contain ``:`` and ``,``, so
        every part that does not start a reserved key
        (``threshold=``/``small=``/``large=``/``leaf.<pat>=``) is absorbed
        into the preceding key's sub-spec — ``adaptive:4096:large=quant:4``
        keeps the ``4`` with ``quant``."""
        kwargs: Dict[str, Any] = {}
        overrides: list = []
        current: Optional[str] = None    # key whose sub-spec absorbs parts
        pos = 0
        for part in args:
            key = part.split("=", 1)[0] if "=" in part else None
            reserved = key in ("threshold", "small", "large") or \
                (key is not None and key.startswith("leaf."))
            if reserved:
                val = part.split("=", 1)[1]
                if key.startswith("leaf."):
                    overrides.append([key[len("leaf."):], val])
                    current = "__override__"
                elif key == "threshold":
                    kwargs["threshold"] = int(val)
                    current = None
                else:
                    kwargs[key] = val
                    current = key
            elif current == "__override__":
                overrides[-1][1] += ":" + part
            elif current is not None:
                kwargs[current] += ":" + part
            else:
                if pos >= 1:
                    raise ValueError(
                        f"adaptive spec takes one positional arg (threshold); "
                        f"unexpected {part!r}")
                kwargs["threshold"] = int(part)
                pos += 1
        if overrides:
            kwargs["overrides"] = tuple((p, s) for p, s in overrides)
        return kwargs


def wire_spec(w: WireFormat) -> str:
    """Canonical spec string of a registered wire format — the inverse of
    :func:`make_wire_format` (``make_wire_format(wire_spec(w)) == w``), used
    by the netsim controller to emit ``--wire`` flags and by dryrun records."""
    if isinstance(w, QuantWire):
        s = f"quant:{w.bits}:{w.block}"
        return s if w.pack is None else s + f":pack={str(w.pack).lower()}"
    if isinstance(w, SparseWire):
        s = f"sparse:{w.p:g}:{w.mode}:{w.block}"
        return s if w.value_dtype == "float32" \
            else s + f":value_dtype={w.value_dtype}"
    if isinstance(w, SignWire):
        return f"sign:{w.scale}:{w.block}"
    if isinstance(w, Fp16Wire):
        return "fp16"
    if isinstance(w, IdentityWire):
        return "identity"
    if isinstance(w, LowRankWire):
        return f"lowrank:{w.rank}" + (":warm" if w.warm else "")
    if isinstance(w, AdaptiveWire):
        parts = [f"adaptive:{w.threshold}", f"small={wire_spec(w.small)}",
                 f"large={wire_spec(w.large)}"]
        parts += [f"leaf.{pat}={wire_spec(sub)}" for pat, sub in w.overrides]
        return ":".join(parts)
    raise TypeError(f"no canonical spec for wire format {w!r}")


# ------------------------------------------------------------------- registry

# name -> (constructor, positional spec-arg names in order)
WIRE_FORMATS: Dict[str, Tuple[Callable[..., WireFormat], Tuple[str, ...]]] = {}


def register_wire_format(name: str, ctor: Callable[..., WireFormat],
                         positional: Tuple[str, ...] = ()) -> None:
    """Register a wire format under ``name`` for :func:`make_wire_format`.

    ``positional`` names the constructor kwargs that bare spec args map to,
    in order (e.g. ``("bits", "block")`` makes ``"quant:4:128"`` work)."""
    WIRE_FORMATS[name] = (ctor, positional)


register_wire_format("quant", QuantWire, positional=("bits", "block"))
register_wire_format("sparse", SparseWire, positional=("p", "mode", "block"))
register_wire_format("sign", SignWire, positional=("scale", "block"))
register_wire_format("fp16", Fp16Wire)
register_wire_format("identity", IdentityWire)
register_wire_format("lowrank", LowRankWire, positional=("rank",))
register_wire_format("adaptive", AdaptiveWire, positional=("threshold",))


def _coerce(text: str):
    for cast in (int, float):
        try:
            return cast(text)
        except ValueError:
            pass
    if text.lower() in ("true", "false"):
        return text.lower() == "true"
    return text


def make_wire_format(spec, **overrides) -> WireFormat:
    """The one factory: spec -> :class:`WireFormat`.

    ``spec`` is a registered instance (returned as-is, or
    ``dataclasses.replace``d with ``overrides``), or a spec string
    ``name[:arg[:arg...]]`` with ``key=value`` or positional args.  Every
    registered spec:

    * ``quant[:bits[:block]]`` — stochastic ``bits``-bit quantization
      (``quant:4``; packed stream words for bits 2..7).
    * ``sparse[:p[:mode[:block]]]`` — fixed-capacity random-k/top-k
      (``sparse:0.25:topk``).
    * ``sign[:scale[:block]]`` — 1-bit sign + per-block magnitude scale
      (``sign`` ≈ 1.03 measured bits/element).
    * ``fp16`` — half-precision cast.
    * ``identity`` — full-precision no-op (exact D-PSGD).
    * ``lowrank[:rank[:warm]]`` — rank-r power-iteration factors for matrix
      leaves (``lowrank:2``; ``lowrank:2:warm`` carries the factors across
      rounds through the DistState aux channel); 1-D leaves ride fp16.
    * ``adaptive:<threshold>[:small=<spec>][:large=<spec>][:leaf.<pat>=<spec>]``
      — per-leaf combinator routing by per-replica element count with
      fnmatch path overrides (``adaptive:4096:small=fp16:large=quant:4``);
      see :class:`AdaptiveWire`.

    >>> make_wire_format("quant:4")             # QuantWire(bits=4)
    >>> make_wire_format("quant:bits=3,block=1024")
    >>> make_wire_format("sparse:0.25:topk")    # SparseWire(p=.25, mode="topk")
    >>> make_wire_format("adaptive:4096:small=fp16:large=quant:4")

    A format whose constructor exposes a ``parse_spec_args`` staticmethod
    (``AdaptiveWire`` does — its sub-specs contain ``:``/``,``) parses its own
    spec args; everything else gets the standard positional/``key=value``
    split."""
    if isinstance(spec, WireFormat):
        return dataclasses.replace(spec, **overrides) if overrides else spec
    if not isinstance(spec, str):
        raise TypeError(f"wire spec must be a WireFormat or str, got {type(spec)}")
    parts = spec.split(":")
    name, args = parts[0], parts[1:]
    if name not in WIRE_FORMATS:
        raise ValueError(
            f"unknown wire format {name!r}; registered: {sorted(WIRE_FORMATS)}")
    ctor, positional = WIRE_FORMATS[name]
    parse = getattr(ctor, "parse_spec_args", None)
    if parse is not None:
        kwargs = parse(args)
        kwargs.update(overrides)
        return ctor(**kwargs)
    kwargs: Dict[str, Any] = {}
    pos = 0
    for arg in args:
        for piece in arg.split(","):
            if not piece:
                continue
            if "=" in piece:
                key, val = piece.split("=", 1)
                kwargs[key] = _coerce(val)
            else:
                if pos >= len(positional):
                    raise ValueError(
                        f"too many positional args in wire spec {spec!r} "
                        f"(format {name!r} takes {positional})")
                kwargs[positional[pos]] = _coerce(piece)
                pos += 1
    kwargs.update(overrides)
    return ctor(**kwargs)
