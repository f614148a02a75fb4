"""Device time of the train step split by phase, from the program's named scopes.

The step's optimized HLO (``ctx.hlo_text``) gives each instruction's
``op_name`` metadata: the path of ``jax.named_scope``s and of JAX's own
transformation markers it was traced under.  A fusion carries the
``op_name`` of its root instruction.  The trace's ``XLA Ops`` events name the
instructions that ran; those that ran inside the step module's ``XLA
Modules`` intervals are joined to their ``op_name`` by instruction name, and
each one's time goes to one phase:

- the innermost of the program's phase scopes (``SCOPES``) in its path;
- else, under ``fwd_bwd``: ``remat`` where the path holds
  ``rematted_computation``, ``backward`` where it holds ``transpose(``,
  ``forward`` otherwise;
- else ``unscoped``, also for an instruction the HLO does not name.

The phases add up to the step module's leaf-op time.  A phase that some
instruction of the HLO is named under reads its time, zero where XLA fused all
of its instructions into fusions rooted in another phase; a phase that no
instruction is named under reads nothing.  The scope names are written here,
not imported from the program, so a program that renames a scope leaves that
phase with nothing to read rather than moving the yardstick.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from bench import trace as T

SCOPES = ("optimizer", "encode", "permute", "decode", "mix", "step_metrics")
MODEL = "fwd_bwd"
REMAT = "rematted_computation"
TRANSPOSE = "transpose("
PHASES = ("forward", "backward", "remat") + SCOPES + ("unscoped",)

# opcodes that hold other instructions or only wait (a copy of
# ``trace.NOT_COMPUTE``, kept here so that an edit there cannot move this)
NOT_COMPUTE = re.compile(r"(-start|-done|^while$|^conditional$|^call$|^async-start$|"
                         r"^async-done$|^send$|^recv$|^copy-start$|^copy-done$)")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=")
_OP_NAME = re.compile(r'metadata=\{[^}]*?\bop_name="((?:[^"\\]|\\.)*)"')
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)


def op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction name: ``op_name``} of every instruction that has one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line, m.end())
        if op:
            out[m.group(1)] = op.group(1)
    return out


def module_name(hlo_text: str) -> Optional[str]:
    m = _MODULE.search(hlo_text)
    return m.group(1) if m else None


def phase_of(op_name: Optional[str]) -> str:
    if not op_name:
        return "unscoped"
    parts = op_name.split("/")
    for part in reversed(parts):
        if part in SCOPES:
            return part
    if MODEL not in parts:
        return "unscoped"
    if REMAT in op_name:
        return "remat"
    return "backward" if TRANSPOSE in op_name else "forward"


def scoped(names: Dict[str, str]) -> bool:
    """Whether the program was traced under any of the phase scopes."""
    known = set(SCOPES) | {MODEL}
    return any(known.intersection(p.split("/")) for p in names.values())


def named_phases(names: Dict[str, str]) -> set:
    """The phases that some instruction, fused ones included, is named under;
    ``unscoped`` always, as the rest of the step."""
    return {phase_of(p) for p in names.values()} | {"unscoped"}


def _overlap(s: float, e: float, spans: List[Tuple[float, float]]) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for lo, hi in spans if lo < e and s < hi)


def device_split(dev, window: Tuple[float, float], module: str,
                 names: Dict[str, str]) -> Dict[str, float]:
    """Nanoseconds per phase on one device: the leaf ops that ran inside the
    runs of ``module`` in ``window``, each clipped to them."""
    runs = T.union(T.clip(((s, e) for n, s, e in dev.modules
                           if n == module or n.startswith(module + "(")), window))
    out = dict.fromkeys(PHASES, 0.0)
    if not runs:
        return out
    for o in dev.ops:
        if NOT_COMPUTE.search(o.opcode):
            continue
        t = _overlap(o.start, o.end, runs)
        if t > 0:
            out[phase_of(names.get(o.name))] += t
    return out


_cache: list = []     # (trace, hlo_text, split, named): the ten readers of a run share one


def split(trace, hlo_text: str) -> Optional[Dict[str, float]]:
    """Nanoseconds per phase in the traced window, as the mean over the
    devices; None without a trace, without the step module's runs in it, or
    for a program with no phase scopes."""
    return _split(trace, hlo_text)[0]


def _split(trace, hlo_text: str):
    if _cache and _cache[0] is trace and _cache[1] is hlo_text:
        return _cache[2], _cache[3]
    result, named = None, set()
    module = module_name(hlo_text or "")
    if trace is not None and trace.devices and module:
        names = op_names(hlo_text)
        if scoped(names):
            per_dev = [device_split(d, trace.window, module, names) for d in trace.devices]
            if any(sum(p.values()) > 0 for p in per_dev):
                result = {k: sum(p[k] for p in per_dev) / len(per_dev) for k in PHASES}
                named = named_phases(names)
    _cache[:] = [trace, hlo_text, result, named]
    return result, named


def ms_per_step(ctx, phase: str) -> Optional[float]:
    """The phase's device milliseconds per traced step (mean over the chips);
    None where there is nothing to read or no instruction is named under the
    phase."""
    if ctx.trace is None or not ctx.traced_steps:
        return None
    got, named = _split(ctx.trace, ctx.hlo_text)
    if got is None or phase not in named:
        return None
    return got[phase] * 1e-6 / ctx.traced_steps
