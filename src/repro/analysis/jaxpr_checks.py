"""Layer 2: programmatic invariant analyzer over jaxprs/HLO of compiled
distributed train steps.

This module is the single source of truth for the guarantees the old
subprocess tests asserted by grepping ``compile().as_text()``:

- **permute payload whitelist** — every operand of a ``collective-permute``
  is a wire container (packed u32 words, s8 codes, f16 halves, the tiny
  per-block f32 scale/value arrays).  The dense stacked f32 param leaves
  never ride the wire for a compressing wire format.
- **fused-kernel call count** — the number of pallas decode-kernel calls
  in the jaxpr equals ``decode_sites(algo, sched) * kernels_per_site``,
  where the replica share of ``decode_sites`` is exactly
  ``sched.replica_payloads`` (the figure netsim charges for).
- **no f64, no host callbacks** inside the jitted step.
- **retrace guard** — ``jit_compile_count`` exposes the jit cache size so
  ``launch/train.py --phase-plan`` can assert exactly one compile per
  segment.

Imports jax (unlike ``repro.analysis.staticcheck``).  HLO-level checks
need a multi-device mesh: run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
``python -m repro.analysis.lint --jaxpr`` CLI sets this up before
importing this module).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.decentralized import init_dist_state, make_dist_train_step
from repro.distributed.gossip import as_schedule, make_gossip_plan
from repro.distributed.wire import IdentityWire, make_wire_format
from repro.launch.mesh import node_mesh
from repro.optim import sgd
from repro.optim.schedules import constant

# The fused Pallas decode kernels, by the ``name=`` their pallas_call carries
# (kernels/quant.py, kernels/lowrank.py).  A jaxpr prints it as
# ``name=<kernel>``, and a TPU profile names the kernel after it.
DECODE_KERNELS = (
    "unpack_dequant_axpy",
    "sparse_scatter_axpy",
    "unpack_sign_axpy",
    "lowrank_axpy",
)

_NAME_PARAM = re.compile(r"\bname=(\w+)")

_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                   "host_callback")
_HLO_CALLBACK_MARKERS = ("xla_python_cpu_callback", "xla_ffi_python",
                         "CustomCall_callback")

_HLO_DTYPE = {
    "uint32": "u32", "uint16": "u16", "uint8": "u8", "int8": "s8",
    "int16": "s16", "int32": "s32", "float16": "f16", "bfloat16": "bf16",
    "float32": "f32", "float64": "f64",
}

_TYPE_TOKEN = re.compile(r"\b([a-z]+[0-9]+)\[([0-9,]*)\]")


@dataclasses.dataclass(frozen=True)
class PermuteOperand:
    """One ``dtype[shape]`` token on a collective-permute HLO line."""

    dtype: str
    shape: Tuple[int, ...]


def permute_operands(hlo_text: str) -> List[PermuteOperand]:
    """All typed tokens on collective-permute *instruction* lines of an HLO
    dump (result + operand types).  Consumer lines that merely reference a
    ``%collective-permute.N`` value by name are excluded — their own types
    are not what moves on the wire."""
    out = []
    for line in hlo_text.splitlines():
        if "collective-permute(" not in line and \
                "collective-permute-start(" not in line:
            continue
        for dtype, dims in _TYPE_TOKEN.findall(line):
            shape = tuple(int(x) for x in dims.split(",")) if dims else ()
            out.append(PermuteOperand(dtype, shape))
    return out


def kernel_call_counts(jaxpr_text: str) -> Dict[str, int]:
    """pallas_call equations of each fused decode kernel in a jaxpr dump."""
    names = _NAME_PARAM.findall(jaxpr_text)
    return {k: names.count(k) for k in DECODE_KERNELS}


def check_no_f64(text: str) -> List[str]:
    return ["f64 value inside the jitted step"] if "f64[" in text else []


def check_no_callbacks(jaxpr_text: str,
                       hlo_text: Optional[str] = None) -> List[str]:
    out = [f"host callback primitive '{p}' inside the jitted step"
           for p in _CALLBACK_PRIMS if p in jaxpr_text]
    if hlo_text is not None:
        out += [f"host callback custom-call '{m}' in compiled HLO"
                for m in _HLO_CALLBACK_MARKERS if m in hlo_text]
    return out


# ---------------------------------------------------------------------------
# wire payload accounting
# ---------------------------------------------------------------------------


def payload_dtype_shapes(wire, stacked_tree,
                         salt: int = 2) -> set:
    """{(hlo_dtype, shape)} of every leaf container one encoded payload
    ships — measured via eval_shape off the wire itself, never modeled."""
    payloads = jax.eval_shape(
        lambda t: wire.encode_tree(t, jnp.zeros((), jnp.int32), salt)[1],
        stacked_tree)
    out = set()
    for leaf in jax.tree_util.tree_leaves(payloads):
        out.add((_HLO_DTYPE.get(leaf.dtype.name, leaf.dtype.name),
                 tuple(leaf.shape)))
    return out


def dense_leaf_shapes(stacked_tree) -> set:
    return {tuple(leaf.shape)
            for leaf in jax.tree_util.tree_leaves(stacked_tree)
            if leaf.dtype in (jnp.float32, jnp.float64)}


def _shape_variants(shape: Tuple[int, ...], n_devices: Optional[int]) -> set:
    """A global container shape plus its per-chip form under node-axis
    sharding (compiled HLO prints post-SPMD per-chip shapes)."""
    out = {shape}
    if n_devices and shape and shape[0] % n_devices == 0:
        out.add((shape[0] // n_devices,) + shape[1:])
    return out


def check_permute_payload_whitelist(hlo_text: str, wire, stacked_params,
                                    n_devices: Optional[int] = None) -> List[str]:
    """The acceptance contract: permute operands are wire containers only.

    - every non-f32 payload container dtype must actually appear on a
      permute (the compressed words are what moves);
    - no f32/f64 permute operand may have the (global or per-chip) shape
      of a dense stacked param leaf, unless the wire's own payload
      legitimately ships a container of that shape (IdentityWire values).
    """
    violations: List[str] = []
    perms = permute_operands(hlo_text)
    if not perms:
        return ["no collective-permute found in compiled HLO"]
    containers = payload_dtype_shapes(wire, stacked_params)
    expected = {d for d, _ in containers if d not in ("f32", "f64")}
    allowed_f32 = set()
    for d, s in containers:
        if d in ("f32", "f64"):
            allowed_f32 |= _shape_variants(s, n_devices)
    seen = {p.dtype for p in perms}
    for d in sorted(expected):
        if d not in seen:
            violations.append(
                f"wire container dtype {d} never rides a collective-permute "
                f"(saw {sorted(seen)})")
    dense = set()
    for s in dense_leaf_shapes(stacked_params):
        dense |= _shape_variants(s, n_devices)
    for p in perms:
        if p.dtype in ("f32", "f64") and p.shape in dense \
                and p.shape not in allowed_f32:
            violations.append(
                f"dense {p.dtype}{list(p.shape)} param leaf rides a "
                "collective-permute — wire compression is bypassed")
    return violations


# ---------------------------------------------------------------------------
# fused decode-kernel call accounting
# ---------------------------------------------------------------------------


def decode_sites(algo: str, sched) -> int:
    """Number of decode-axpy call sites the traced step contains.

    Per gossip round the replica-tracking algorithms (dcd/ecd/choco)
    decode 1 self payload + one payload per union shift; the replica share
    per step is ``period * |union| == sched.replica_payloads`` for
    per-step schedules.  DeepSqueeze (stateless receive) decodes its own
    error-compensated model payload twice (residual update + the D_self
    displacement term) plus one per neighbor shift of the round.
    Time-varying schedules lower through lax.switch, so the *trace* still
    contains every round's sites even though one executes per step.
    """
    sched = as_schedule(sched)
    if algo in ("dcd", "ecd", "choco"):
        return sched.period * (1 + len(sched.shift_union))
    if algo == "deepsqueeze":
        return sum(2 + len(r.shifts) for r in sched.rounds)
    return 0


def kernels_per_site(wire, stacked_tree, salt: int = 2) -> int:
    """Fused kernel calls one encode+decode_axpy round-trip emits for this
    (wire, tree) — measured by tracing the wire's own tree path, so the
    128-lane eligibility gate is never re-modeled here."""
    wire = make_wire_format(wire)

    def one(tree):
        tdef, payload = wire.encode_tree(tree, jnp.zeros((), jnp.int32), salt)
        return wire.decode_axpy_tree(tdef, payload, tree, 0.5, 0.5)

    txt = str(jax.make_jaxpr(one)(stacked_tree))
    return sum(kernel_call_counts(txt).values())


def expected_kernel_calls(algo: str, sched, wire, stacked_tree) -> int:
    if wire is None:
        return 0
    return decode_sites(algo, sched) * kernels_per_site(wire, stacked_tree)


# ---------------------------------------------------------------------------
# case runner: build a dist step, trace, (optionally) compile, check
# ---------------------------------------------------------------------------

# Three-leaf testbed: a small leaf under the adaptive threshold (rides fp16),
# a kernel-eligible bulk leaf, and a matrix leaf so the structure-exploiting
# lowrank format has a 2-D payload to factor (128 columns keeps the fused
# axpy kernel's lane gate open).
_D_SMALL, _D_LARGE, _D_COLS = 32, 1024, 128
_ADAPTIVE_SPEC = "adaptive:128:small=fp16:large=quant:4"


def _toy_params():
    return {"bias": jnp.zeros((_D_SMALL,)),
            "weight": jnp.zeros((_D_LARGE,)),
            "proj": jnp.zeros((_D_SMALL, _D_COLS))}


def _toy_batch(n: int, m: int = 4):
    return {"Ab": jnp.ones((n, m, _D_SMALL)),
            "Aw": jnp.ones((n, m, _D_LARGE)),
            "b": jnp.ones((n, m))}


def _toy_loss(params, batch):
    pred = batch["Ab"] @ params["bias"] + batch["Aw"] @ params["weight"] \
        + jnp.mean(batch["Ab"] @ params["proj"], axis=-1)
    loss = 0.5 * jnp.mean((pred - batch["b"]) ** 2)
    return loss, {"xent": loss}


@dataclasses.dataclass(frozen=True)
class CaseReport:
    algo: str
    topology: str
    wire: Optional[str]
    drop: float
    kernel_calls: int
    expected_kernels: int
    permute_dtypes: Tuple[str, ...]
    violations: Tuple[str, ...]

    @property
    def ok(self) -> bool:
        return not self.violations

    def describe(self) -> str:
        return (f"{self.algo}@{self.topology}@{self.wire or 'dense'}"
                f"@drop={self.drop} kernels={self.kernel_calls}"
                f"/{self.expected_kernels} permutes={list(self.permute_dtypes)}")


def analyze_case(algo: str, topology: str, wire_spec: Optional[str],
                 drop: float = 0.0, *, n: int = 8,
                 hlo: bool = True) -> CaseReport:
    """Trace (and, with ``hlo=True``, compile on an n-device mesh) one
    (algo, topology, wire, drop) config and run every invariant check."""
    sched = make_gossip_plan(topology, n)
    wire = make_wire_format(wire_spec) if wire_spec else None
    mesh = node_mesh(jax.devices()[:n]) if hlo else None
    step = make_dist_train_step(
        _toy_loss, algo, sgd(), wire, sched, constant(0.05),
        mesh=mesh, drop=drop or None)
    state = init_dist_state(algo, _toy_params(), sched, sgd(),
                            drop=drop or None, wire=wire)
    batch = _toy_batch(n)

    violations: List[str] = []
    jaxpr_text = str(jax.make_jaxpr(step)(state, batch))
    kernel_calls = sum(kernel_call_counts(jaxpr_text).values())
    expected = expected_kernel_calls(algo, sched, wire, state.params)
    if kernel_calls != expected:
        violations.append(
            f"fused decode-kernel calls {kernel_calls} != expected "
            f"{expected} (= decode sites x kernels/site; replica share is "
            "sched.replica_payloads)")
    if mesh is not None and kernel_calls and "shard_map" not in jaxpr_text:
        violations.append(
            "fused decode kernels present but not under shard_map on a "
            "node mesh — the sharded decode path is not being exercised")
    violations += check_no_f64(jaxpr_text)
    violations += check_no_callbacks(jaxpr_text)

    permute_dtypes: Tuple[str, ...] = ()
    if hlo:
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = jax.tree.map(
            lambda l: NamedSharding(mesh, P(*(("node",) + (None,) * (l.ndim - 1))))
            if l.ndim else NamedSharding(mesh, P()), state)
        bsh = jax.tree.map(lambda l: NamedSharding(mesh, P("node")), batch)
        with mesh:
            hlo_text = jax.jit(step, in_shardings=(sh, bsh)).lower(
                state, batch).compile().as_text()
        perms = permute_operands(hlo_text)
        permute_dtypes = tuple(sorted({p.dtype for p in perms}))
        if wire is not None and not isinstance(wire, IdentityWire):
            violations += check_permute_payload_whitelist(
                hlo_text, wire, state.params, n_devices=n)
        elif not perms:
            violations.append("no collective-permute found in compiled HLO")
        violations += check_no_f64(hlo_text)
        violations += check_no_callbacks(jaxpr_text, hlo_text)

    return CaseReport(algo, topology, wire_spec, drop, kernel_calls,
                      expected, permute_dtypes, tuple(violations))


# Representative grid: the acceptance set {ring, torus, full_logn} x
# {quant:4, sign, adaptive} plus every guarantee the legacy subprocess
# asserts covered (s8 codes at quant:8, packed u32 at 3/4-bit and sparse,
# chain/torus2d plans, error-feedback families, a drop-rate case, and the
# dense dpsgd baseline).
DEFAULT_GRID: Tuple[Tuple[str, str, Optional[str], float], ...] = tuple(
    [("dcd", topo, w, 0.0)
     for topo in ("ring", "torus", "full_logn")
     for w in ("quant:4", "sign", _ADAPTIVE_SPEC)]
    + [
        ("dcd", "ring", "quant:8", 0.0),
        ("dcd", "ring", "quant:3", 0.0),
        ("dcd", "chain", "quant:4", 0.0),
        ("dcd", "torus2d", "sparse:0.25", 0.0),
        ("ecd", "torus", "quant:4", 0.0),
        ("choco", "ring", "sign", 0.0),
        ("deepsqueeze", "ring", "sign", 0.0),
        ("dcd", "ring", "lowrank:2", 0.0),
        ("dcd", "ring", "quant:4", 0.2),
        ("dpsgd", "ring", None, 0.0),
    ])


def run_sweep(grid: Optional[Sequence] = None, *,
              require_hlo: bool = False, n: int = 8) -> List[CaseReport]:
    """Analyze every grid case; with ``require_hlo`` the process must see
    >= n devices (forced-host or real) or this raises."""
    hlo = len(jax.devices()) >= n
    if require_hlo and not hlo:
        raise RuntimeError(
            f"HLO checks need {n} devices, found {len(jax.devices())} — set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            "importing jax (the lint CLI does this)")
    return [analyze_case(algo, topo, w, drop, n=n, hlo=hlo)
            for algo, topo, w, drop in (grid or DEFAULT_GRID)]


# ---------------------------------------------------------------------------
# retrace guard + dryrun summary record
# ---------------------------------------------------------------------------


def jit_compile_count(jitted_fn) -> int:
    """Number of distinct compilations a ``jax.jit`` function has cached.

    The --phase-plan retrace guard: after running a segment, the segment's
    freshly-jitted step must report exactly 1 — more means something
    (shape, dtype, weak-type) varied per step and every call recompiled.
    """
    return int(jitted_fn._cache_size())


def analysis_record(compiled, params=None, wire=None) -> Dict[str, Any]:
    """Non-failing invariant summary for a compiled step (dryrun JSONL).

    Records the permute payload picture so a wire-honesty regression is
    visible in every dryrun artifact, without gating multi-axis meshes
    (where resharding collectives legitimately move f32).
    """
    hlo_text = compiled.as_text()
    perms = permute_operands(hlo_text)
    rec: Dict[str, Any] = {
        "collective_permutes": len(perms),
        "permute_dtypes": sorted({p.dtype for p in perms}),
        "f64_free": not check_no_f64(hlo_text),
        "host_callback_free": not check_no_callbacks("", hlo_text),
    }
    if params is not None and wire is not None and \
            not isinstance(wire, IdentityWire):
        rec["permute_whitelist_violations"] = check_permute_payload_whitelist(
            hlo_text, wire, params)
    return rec
