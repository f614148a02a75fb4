"""Device time of the attention core, and the least time its work needs.

The program traces the core of its self-attention (the scores, the softmax
and the weighted sum of the values, dense or chunked; not the projections or
RoPE) under ``jax.named_scope("attention")``.  That scope lies inside the
model's forward, backward and recomputed passes, so its instructions also
belong to the ``forward``, ``backward`` and ``remat`` phases of
``bench/scopes.py`` (all but the causal mask, which depends on no input and
which JAX therefore traces outside those passes, as ``unscoped``); it is read
here on its own and moves no phase.

The device time is that of the leaf ops that ran inside the step module's
runs in the traced window and whose ``op_name`` path has a component
``attention``, as the mean over the chips.  A program traced without the
scope reads nothing.  The scope's name is written here, not imported from
the program.

The least time is counted from the shapes, whatever implements the core:
causal attention's two products (the scores and the weighted values) over the
(S + 1) / 2 positions a query sees on average, forward, and twice that
backward; recomputation is not counted.  Its bytes are q, k, v, o and the
output's cotangent read once, and o, dq, dk and dv written once, in bfloat16.
"""
from __future__ import annotations

from typing import Dict, Optional

from bench import scopes
from bench import trace as T

SCOPE = "attention"
BF16_BYTES = 2


def in_scope(op_name: Optional[str]) -> bool:
    return bool(op_name) and SCOPE in op_name.split("/")


def device_ns(dev: T.Device, window: T.Interval, module: str,
              names: Dict[str, str]) -> float:
    """Nanoseconds of one device's attention ops inside the runs of
    ``module`` in ``window``, each clipped to them."""
    runs = T.union(T.clip(((s, e) for n, s, e in dev.modules
                           if n == module or n.startswith(module + "(")), window))
    ops = [(o.start, o.end) for o in dev.ops
           if not scopes.NOT_COMPUTE.search(o.opcode) and in_scope(names.get(o.name))]
    return sum(T.length(T.clip(ops, run)) for run in runs)


def ms_per_step(ctx) -> Optional[float]:
    """The attention core's device milliseconds per traced step (mean over
    the chips); None without a trace or where no instruction of the step is
    named under the scope."""
    tr = ctx.trace
    module = scopes.module_name(ctx.hlo_text or "")
    if tr is None or not tr.devices or not ctx.traced_steps or not module:
        return None
    names = scopes.op_names(ctx.hlo_text)
    if not any(in_scope(p) for p in names.values()):
        return None
    ns = sum(device_ns(d, tr.window, module, names) for d in tr.devices) / len(tr.devices)
    return ns * 1e-6 / ctx.traced_steps


def _sequence_layers(cfg, traffic, nodes_per_device: int) -> int:
    return cfg["n_layers"] * nodes_per_device * traffic["seqs_per_node"]


def core_flops(cfg, traffic, nodes_per_device: int) -> float:
    """FLOPs of the causal attention core in one training step on one chip:
    2 products x 2 FLOPs x heads x head size x S (S + 1) / 2 forward, twice
    that backward, per sequence and layer."""
    s, h, hd = traffic["seq_len"], cfg["n_heads"], cfg["head_dim"]
    forward = 2 * 2 * h * hd * s * (s + 1) / 2
    return 3 * forward * _sequence_layers(cfg, traffic, nodes_per_device)


def core_bytes(cfg, traffic, nodes_per_device: int) -> float:
    """Least bytes of the same work: q, k, v, o and dO read once, o, dq, dk
    and dv written once, in bfloat16."""
    s, h, kv, hd = (traffic["seq_len"], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["head_dim"])
    reads = 3 * h + 2 * kv          # q, o, dO; k, v
    writes = 2 * h + 2 * kv         # o, dq; dk, dv
    return (reads + writes) * s * hd * BF16_BYTES * _sequence_layers(
        cfg, traffic, nodes_per_device)


def least_s(cfg, traffic, nodes_per_device: int, peaks: Dict[str, float]) -> float:
    """Seconds the work needs at the chip's peaks: the larger of its FLOPs at
    the bf16 peak and its bytes at the HBM bandwidth."""
    return max(core_flops(cfg, traffic, nodes_per_device) / peaks["bf16_flops_per_s"],
               core_bytes(cfg, traffic, nodes_per_device) / peaks["hbm_bytes_per_s"])
