"""Jitted public wrappers around the Pallas kernels.

Handles: arbitrary input shapes (flatten/pad to the 2-D blocked view), PRNG-key ->
seed derivation, interpret mode on the CPU backend (:func:`interpret_kernels`),
and payloads in the same wire format as
:class:`repro.core.compression.RandomQuantizer`:

* ``bits=8``: ``codes`` int8 ``(n_blocks, block_size)`` + ``scale`` f32
  ``(n_blocks, 1)``.
* ``bits in 2..7``: ``codes`` **uint32** ``(n_blocks, block_size*bits/32)``
  (bit-exact stream packing — see kernels/quant.py) + ``scale``.

The payload's ``codes.dtype`` is therefore self-describing: uint32 means packed.
``payload_nbytes`` is the honest wire cost used by the netsim cost model and the
benchmarks.

The sparse codec rides the same contract: ``sparse_compress`` returns
``{values: (n_blocks, k) fp16/fp32, idx: (n_blocks, words) uint32}`` — the
fixed-capacity top-k / rescaled random-k payload with the block-local indices
bit-packed to ``idx_bits_for(block_size)`` bits each (kernels/quant.py stream
layout, raw unsigned fields).  Same wire format as
:class:`repro.core.compression.RandomSparsifier` / ``TopKSparsifier``.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import quant as _q


def interpret_kernels() -> bool:
    """Whether the Pallas kernels run in interpret mode — the one place the
    repository decides it.

    On a TPU they compile with Mosaic; on the CPU backend, where the tests run,
    they are interpreted.  Any other backend is an error, never a silent
    fallback.  Code that compiles for a described TPU from a CPU process
    (``jax.experimental.topologies``) patches this function to return False.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"the Pallas kernels run on a TPU, or interpreted on "
                       f"the CPU backend; this process's backend is {backend!r}")


def _to_blocks(x: jax.Array, block_size: int) -> jax.Array:
    n = x.size
    pad = (-n) % block_size
    flat = jnp.pad(x.reshape(-1).astype(jnp.float32), (0, pad))
    return flat.reshape(-1, block_size)


def payload_nbytes(payload: Any) -> int:
    """Total wire bytes of a payload pytree (works on arrays or ShapeDtypeStructs)."""
    return sum(
        int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
        for l in jax.tree.leaves(payload)
    )


@functools.partial(jax.jit, static_argnames=("bits", "block_size", "pack"))
def quantize(key: jax.Array, x: jax.Array, *, bits: int = 8, block_size: int = 1024,
             pack: bool | None = None) -> dict:
    """Stochastic-quantize any-shaped ``x`` into a {codes, scale} payload.

    For ``bits in 2..7`` (and ``pack`` not explicitly False) the codes come
    out of the fused quantize+pack kernel as uint32 words — the payload is the
    packed wire format, ``bits + 32/block`` bits per element on the wire.
    """
    assert block_size % 128 == 0
    packed = bits in _q.PACKABLE_BITS if pack is None else pack
    assert not packed or bits in _q.PACKABLE_BITS, \
        f"packable bits are {_q.PACKABLE_BITS}, got {bits}"
    seed = jax.random.bits(key, (1,), dtype=jnp.uint32)
    blocks = _to_blocks(x, block_size)
    if packed:
        codes, scale = _q.quantize_pack_2d(blocks, seed, bits=bits, interpret=interpret_kernels())
    else:
        codes, scale = _q.quantize_2d(blocks, seed, bits=bits, interpret=interpret_kernels())
    return {"codes": codes, "scale": scale}


@functools.partial(jax.jit, static_argnames=("bits", "shape", "dtype"))
def dequantize(payload: dict, *, bits: int = 8, shape: tuple = (), dtype: Any = jnp.float32) -> jax.Array:
    if payload["codes"].dtype == jnp.uint32:
        out = _q.unpack_dequant_2d(payload["codes"], payload["scale"], bits=bits,
                                   interpret=interpret_kernels())
    else:
        out = _q.dequantize_2d(payload["codes"], payload["scale"], bits=bits,
                               interpret=interpret_kernels())
    n = int(np.prod(shape)) if shape else 1
    return out.reshape(-1)[:n].reshape(shape).astype(dtype)


@functools.partial(jax.jit, static_argnames=("p", "block_size", "mode", "value_dtype"))
def sparse_compress(key: jax.Array, x: jax.Array, *, p: float = 0.25,
                    block_size: int = 128, mode: str = "randk",
                    value_dtype: Any = jnp.float32) -> dict:
    """Fixed-capacity sparsification of any-shaped ``x`` into {values, idx}.

    Per ``block_size``-element block, ``k = ceil(p * block_size)`` values are
    kept (``randk``: a seeded uniform k-subset, rescaled by ``block/k``;
    ``topk``: the k largest magnitudes, unscaled) through the fused
    select+gather+pack kernel — only the k values and the ~``k * idx_bits``
    index bits ever leave it.
    """
    assert block_size % 128 == 0
    seed = jax.random.bits(key, (1,), dtype=jnp.uint32)
    blocks = _to_blocks(x, block_size)
    vals, idx = _q.sparse_select_pack_2d(blocks, seed, p=p, mode=mode,
                                         value_dtype=value_dtype,
                                         interpret=interpret_kernels())
    return {"values": vals, "idx": idx}


@functools.partial(jax.jit, static_argnames=("block_size", "shape", "dtype"))
def sparse_decompress(payload: dict, *, block_size: int = 128, shape: tuple = (),
                      dtype: Any = jnp.float32) -> jax.Array:
    out = _q.sparse_unpack_scatter_2d(payload["values"], payload["idx"],
                                      cols=block_size, interpret=interpret_kernels())
    n = int(np.prod(shape)) if shape else 1
    return out.reshape(-1)[:n].reshape(shape).astype(dtype)


@functools.partial(jax.jit, static_argnames=("block_size",))
def sparse_axpy(payload: dict, acc: jax.Array, *, block_size: int,
                weight: float) -> jax.Array:
    """Fused sparse receive path: ``acc + weight * sparse_decompress(payload)``.

    One kernel pass — unpack the index stream, scatter, and accumulate in
    VMEM; the reconstructed dense fp32 tensor never lands in HBM.
    """
    blocks = _to_blocks(acc, block_size)
    out = _q.sparse_scatter_axpy_2d(payload["values"], payload["idx"], blocks,
                                    weight=weight, interpret=interpret_kernels())
    n = acc.size
    return out.reshape(-1)[:n].reshape(acc.shape).astype(acc.dtype)


@functools.partial(jax.jit, static_argnames=("bits",))
def dequant_axpy(payload: dict, acc: jax.Array, *, bits: int, weight: float) -> jax.Array:
    """Fused receive path: ``acc + weight * dequantize(payload)``, acc-shaped.

    For packed payloads this is one kernel — unpack, dequantize and accumulate
    in VMEM, never writing the reconstructed fp32 tensor to HBM.  ``weight``
    may be a float or a traced scalar.
    """
    packed = payload["codes"].dtype == jnp.uint32
    block_size = payload["codes"].shape[-1] * 32 // bits if packed \
        else payload["codes"].shape[-1]
    blocks = _to_blocks(acc, block_size)
    if packed:
        out = _q.unpack_dequant_axpy_2d(payload["codes"], payload["scale"], blocks,
                                        bits=bits, weight=weight, interpret=interpret_kernels())
    else:
        out = blocks + weight * _q.dequantize_2d(payload["codes"], payload["scale"],
                                                 bits=bits, interpret=interpret_kernels())
    n = acc.size
    return out.reshape(-1)[:n].reshape(acc.shape).astype(acc.dtype)
