"""Work of the wire's decode, computed from leaf shapes and the wire's spec.

The packed ``bits``-bit wire blocks each leaf along its last dimension (at
most 1024 elements a block, shrunk toward short dimensions and rounded up to
whole packed groups) and ships per block the packed codes and one float32
scale.  Decoding one payload into an accumulator (DCD's decode-axpy) reads
the payload, reads the float32 accumulator and writes it back.
"""
from __future__ import annotations

import math
from typing import Iterable, Tuple


def block_len(last: int, bits: int, limit: int = 1024) -> int:
    group = math.lcm(bits, 32) // bits
    return min(limit, -(-min(limit, last) // group) * group)


def payload_bytes(shape: Tuple[int, ...], bits: int) -> int:
    last = shape[-1] if shape else 1
    lead = math.prod(shape[:-1]) if len(shape) > 1 else 1
    block = block_len(last, bits)
    nblk = -(-last // block)
    return lead * nblk * (block * bits // 8 + 4)


def decode_axpy_bytes(shapes: Iterable[Tuple[int, ...]], bits: int) -> int:
    """Least bytes one decode-axpy of every leaf moves: the payload, plus
    4 B read and 4 B written per element of the accumulator."""
    return sum(payload_bytes(s, bits) + 8 * math.prod(s) for s in shapes)


def ring_shifts(n: int) -> int:
    """Distinct neighbours of a node on a ring of ``n``: the shifts DCD keeps
    a replica for, each decoded once a step besides the node's own payload."""
    return len({1 % n, -1 % n} - {0})
