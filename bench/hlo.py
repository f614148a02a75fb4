"""Counts read from a compiled program's HLO text and memory analysis."""
from __future__ import annotations

import re
from typing import List, Tuple

_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2, "u16": 2,
          "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
          "c64": 8, "c128": 16}
_TYPE = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")
_PERMUTE = re.compile(r"=\s*(.*?)\s(collective-permute(?:-start)?)\(")


def permute_operands(hlo_text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, shape) of what every collective-permute instruction sends.

    The compiled text types an instruction's result, not its operands.  A
    synchronous permute's result has its operand's type; an asynchronous
    start's result is the tuple (operands, results, context scalars), so its
    arrays of rank one and up come twice."""
    out = []
    for line in hlo_text.splitlines():
        m = _PERMUTE.search(line)
        if not m:
            continue
        arrays = [(d, tuple(int(x) for x in dims.split(",")))
                  for d, dims in _TYPE.findall(m.group(1)) if dims]
        if m.group(2).endswith("-start"):
            arrays = arrays[:len(arrays) // 2]
        out += arrays
    return out


def nbytes(dtype: str, shape: Tuple[int, ...]) -> int:
    n = 1
    for d in shape:
        n *= d
    return n * _BYTES[dtype]


def permute_bytes(hlo_text: str) -> int:
    """Bytes the program's collective-permutes send from one device per run."""
    return sum(nbytes(d, s) for d, s in permute_operands(hlo_text))


def program_bytes(compiled) -> int:
    """Device memory the compiled program needs on its fullest device:
    arguments + outputs - what the outputs alias + temporaries."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
