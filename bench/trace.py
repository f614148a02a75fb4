"""Reduction of a profiler trace to intervals, sums and overlaps.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData`` reads
it.  On a TPU each chip is a plane ``/device:TPU:<i>`` whose line
``XLA Ops`` holds one event per HLO instruction executed (its name is the
instruction's HLO text, ``%name = type opcode(...)``), ``Async XLA Ops`` the
spans of asynchronous instructions from start to done, and ``XLA Modules`` one
event per program run (``jit_<fn>(<id>)``).  The host plane ``/host:CPU``
holds the benchmark's own spans (``jax.profiler.TraceAnnotation``).  Host and
device events share one clock, in nanoseconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

HOST_SPANS = ("dispatch_input", "dispatch_step", "wait_step")
WINDOW_SPAN = "traced_window"
# opcodes that move data between chips
COLLECTIVE = re.compile(r"^(collective-permute|all-reduce|all-gather|reduce-scatter|"
                        r"all-to-all|collective-broadcast)")
# opcodes that are no work of their own: they wait, start an async copy, or
# hold a body whose instructions have events of their own
NOT_COMPUTE = re.compile(r"(-start|-done|^while$|^conditional$|^call$|^async-start$|"
                         r"^async-done$|^send$|^recv$|^copy-start$|^copy-done$)")


@dataclasses.dataclass
class Op:
    name: str        # instruction name, without the leading %
    opcode: str
    start: float     # ns
    end: float


@dataclasses.dataclass
class Device:
    ops: List[Op]
    async_ops: List[Op]
    modules: List[Tuple[str, float, float]]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Tuple[str, float, float]]
    window: Interval

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def parse_instruction(text: str) -> Tuple[str, str]:
    """(name, opcode) of an HLO instruction line ``%name = type opcode(...)``."""
    m = re.match(r"\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s*", text)
    if not m:
        return text.split(" ")[0].lstrip("%"), ""
    name, rest = m.group(1), text[m.end():]
    if rest.startswith("("):                      # tuple type: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    op = re.match(r"\s*([a-z][a-z0-9\-]*)\(", rest)
    return name, op.group(1) if op else ""


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns)


def load(trace_dir: str) -> Trace:
    """The trace under ``trace_dir`` (one run of ``jax.profiler.trace``)."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, found {files}")
    pd = ProfileData.from_file(files[0])
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") or plane.name.startswith("/device:CPU:"):
            dev = Device([], [], [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = [Op(*parse_instruction(n), s, e) for n, s, e in _events(line)]
                elif line.name == "Async XLA Ops":
                    dev.async_ops = [Op(*parse_instruction(n), s, e)
                                     for n, s, e in _events(line)]
                elif line.name == "XLA Modules":
                    dev.modules = list(_events(line))
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [ev for ev in _events(line)
                         if ev[0] in HOST_SPANS or ev[0] == WINDOW_SPAN]
    win = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if len(win) != 1:
        raise RuntimeError(f"the trace holds {len(win)} '{WINDOW_SPAN}' spans, not one")
    return Trace(devices=devices, host=[h for h in host if h[0] != WINDOW_SPAN],
                 window=win[0])


# ----------------------------------------------------------------- interval arithmetic

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the (disjoint, sorted) intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    b = list(b)
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    return subtract([window], busy)


# ----------------------------------------------------------------- per-device readings

def busy(dev: Device, window: Interval) -> List[Interval]:
    """When any instruction ran on the device."""
    return union(clip(((o.start, o.end) for o in dev.ops), window))


def compute(dev: Device, window: Interval) -> List[Interval]:
    """When an instruction that does work of its own ran (no collective, no
    async start or done, no loop or call that holds other instructions)."""
    return union(clip(((o.start, o.end) for o in dev.ops
                       if not COLLECTIVE.match(o.opcode)
                       and not NOT_COMPUTE.search(o.opcode)), window))


def collective(dev: Device, window: Interval, pattern: str) -> List[Interval]:
    """When a collective whose opcode matches ``pattern`` was in flight:
    synchronous ones from ``XLA Ops``, asynchronous ones from start to done."""
    rx = re.compile(pattern)
    spans = [(o.start, o.end) for o in dev.ops
             if rx.match(o.opcode) and not NOT_COMPUTE.search(o.opcode)]
    spans += [(o.start, o.end) for o in dev.async_ops if rx.match(o.opcode)]
    return union(clip(spans, window))


def exposed(dev: Device, window: Interval, pattern: str) -> float:
    """Nanoseconds in which a matching collective was in flight and no
    compute instruction ran on the device."""
    return length(subtract(collective(dev, window, pattern), compute(dev, window)))


def op_time(dev: Device, window: Interval, name_pattern: str) -> float:
    """Summed nanoseconds of the instructions whose name matches."""
    rx = re.compile(name_pattern)
    return length([iv for o in dev.ops if rx.match(o.name)
                   for iv in clip([(o.start, o.end)], window)])


def module_time(dev: Device, window: Interval, name_pattern: str) -> float:
    """Summed nanoseconds of the program runs whose module name matches."""
    rx = re.compile(name_pattern)
    return length([iv for n, s, e in dev.modules if rx.match(n)
                   for iv in clip([(s, e)], window)])


def top_ops(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` instructions (by name and opcode) that took the most device
    time in the window, in seconds per device."""
    tot: Dict[str, float] = {}
    for dev in trace.devices:
        for o in dev.ops:
            if NOT_COMPUTE.search(o.opcode) and not o.opcode.endswith("-done"):
                continue
            for s, e in clip([(o.start, o.end)], trace.window):
                key = f"{o.name} {o.opcode}".strip()
                tot[key] = tot.get(key, 0.0) + (e - s)
    n = max(len(trace.devices), 1)
    return [[name, ns * 1e-9 / n] for name, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def host_span_at(trace: Trace, t: float) -> str:
    """The innermost benchmark span open on the host at ``t``."""
    best: Optional[Tuple[str, float, float]] = None
    for n, s, e in trace.host:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (n, s, e)
    return best[0] if best else "no_span"


def idle_gaps(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` longest idle gaps of the devices in the window, each named by
    the host span open at its middle, in seconds."""
    out = []
    for dev in trace.devices:
        for s, e in gaps(busy(dev, trace.window), trace.window):
            out.append([host_span_at(trace, (s + e) / 2), (e - s) * 1e-9])
    return sorted(out, key=lambda g: -g[1])[:k]
