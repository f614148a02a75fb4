"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds, priced against a TPU v5e
target model (published per-chip peaks, whatever device runs the dry-run):

    compute    = HLO_FLOPs_per_chip / peak_FLOPs           (197 TFLOP/s bf16)
    memory     = HLO_bytes_per_chip / HBM_bw               (819 GB/s)
    collective = collective_bytes_per_chip / link_bw       (~50 GB/s/link ICI)

``cost_analysis()`` supplies per-device FLOPs and bytes-accessed for the SPMD
module.  Collective bytes are NOT in cost_analysis: we parse the partitioned HLO
and sum the output sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute (all-reduce counted twice: reduce + broadcast
phases both cross links).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

# The v5e target model the dry-run prices against — not the current device.
V5E_PEAK_FLOPS = 197e12     # bf16 / chip
V5E_HBM_BW = 819e9          # bytes/s / chip
V5E_LINK_BW = 50e9          # bytes/s / link (ICI); DCN is ~10-25x slower

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """'f32[2,128,1024]' -> bytes; tuples handled by caller splitting."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_op: Dict[str, int]
    counts_by_op: Dict[str, int]
    dcn_bytes: int = 0     # bytes crossing pod boundaries (multi-pod runs)

    @property
    def total_bytes(self) -> int:
        # all-reduce crosses the links twice (reduce + broadcast phases)
        return sum(b * (2 if op == "all-reduce" else 1)
                   for op, b in self.bytes_by_op.items())


_LINE_RE = re.compile(
    r"=\s*(.*?)\s(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_WHILE_RE = re.compile(r"while\(.*?\).*?condition=%?([\w.\-]+).*?body=%?([\w.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count[^}]*?"n"\s*:\s*"(\d+)"')


def _split_computations(hlo_text: str):
    """-> {name: [lines]}, entry_name.  HLO computations end with '}' at col 0."""
    comps, cur, name, entry = {}, None, None, None
    for line in hlo_text.splitlines():
        if cur is None and line.rstrip().endswith("{") and not line.startswith(" "):
            m = _COMP_RE.match(line)
            if m:
                name = m.group(1)
                cur = []
                comps[name] = cur
                if line.startswith("ENTRY"):
                    entry = name
                continue
        if line.startswith("}"):
            name, cur = None, None
            continue
        if cur is not None:
            cur.append(line)
    return comps, entry


def _trip_count(cond_lines) -> int:
    """JAX scans compare the induction var against a constant in the condition."""
    cands = [int(m.group(1)) for l in cond_lines
             for m in [re.search(r"constant\((\d+)\)", l)] if m]
    return max(cands) if cands else 1


_IOTA_RG_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[(\d+)\](T\()?")
_LIST_RG_RE = re.compile(r"replica_groups=\{\{(.*?)\}\}")


def _crosses_pod(line: str, pod_size: int) -> bool:
    """Does this collective's communication pattern cross a pod boundary?

    Handles: explicit source_target_pairs; explicit replica_groups lists; and
    iota-format groups ``[G,S]<=[N]`` (without transpose, group g is the
    contiguous range [g*S, (g+1)*S) — crossing iff the pod size is not a
    multiple of the group stride).  Transposed iota groups interleave devices
    across the flattened order and are treated conservatively as crossing.
    """
    if "source_target_pairs" in line:
        return any(int(a) // pod_size != int(b) // pod_size
                   for a, b in re.findall(r"\{(\d+),(\d+)\}", line))
    m = _IOTA_RG_RE.search(line)
    if m:
        g, s, n, transposed = int(m.group(1)), int(m.group(2)), int(m.group(3)), m.group(4)
        if n <= pod_size:
            return False
        if transposed:
            return True   # interleaved: conservative
        return pod_size % s != 0   # contiguous groups cross iff stride misaligned
    m = _LIST_RG_RE.search(line)
    if m:
        for group in m.group(1).split("},{"):
            ids = [int(x) for x in re.findall(r"\d+", group)]
            if ids and max(ids) // pod_size != min(ids) // pod_size:
                return True
        return False
    return False


def parse_collectives(hlo_text: str, pod_size: Optional[int] = None) -> CollectiveStats:
    """Per-device collective bytes for ONE step, while-loop aware.

    Collectives inside scan bodies run once per iteration: we parse computation
    blocks, recover each while's trip count from its condition constant, and
    multiply.  ``pod_size``: bytes whose source->target pairs / replica groups
    cross a pod boundary also tally as DCN traffic (the slow links the paper's
    compression targets).
    """
    comps, mult = _comp_multipliers(hlo_text)

    bytes_by_op: Dict[str, int] = {}
    counts: Dict[str, int] = {}
    dcn = 0.0
    for cname, lines in comps.items():
        m_factor = mult.get(cname, 1.0)
        for line in lines:
            m = _LINE_RE.search(line)
            if not m or m.group(3) == "-done":
                continue
            op = m.group(2)
            sz = _shape_bytes(m.group(1)) * m_factor
            bytes_by_op[op] = bytes_by_op.get(op, 0) + int(sz)
            counts[op] = counts.get(op, 0) + int(m_factor)
            if pod_size:
                crosses = _crosses_pod(line, pod_size)
                if crosses:
                    dcn += sz * (2 if op == "all-reduce" else 1)
    return CollectiveStats(bytes_by_op=bytes_by_op, counts_by_op=counts,
                           dcn_bytes=int(dcn))


_SKIP_OPS = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
             "after-all", "partition-id", "replica-id", "iota"}


def _comp_multipliers(hlo_text: str):
    """{computation: product of enclosing while trip counts} (entry-reachable only,
    fusion-internal computations excluded — they don't touch HBM)."""
    comps, entry = _split_computations(hlo_text)
    mult = {entry: 1.0} if entry else {}
    stack = [entry] if entry else []
    while stack:
        cname = stack.pop()
        for line in comps.get(cname, ()):
            wm = _WHILE_RE.search(line)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                tm = _TRIP_RE.search(line)   # XLA's own known_trip_count when present
                trips = int(tm.group(1)) if tm else _trip_count(comps.get(cond, ()))
                for sub, m in ((body, mult[cname] * trips), (cond, mult[cname])):
                    if sub in comps and sub not in mult:
                        mult[sub] = m
                        stack.append(sub)
            for key in ("true_computation=", "false_computation=", "branch_computations={"):
                if key in line:
                    for bn in re.findall(r"%([\w.\-]+)", line.split(key, 1)[1]):
                        if bn in comps and bn not in mult:
                            mult[bn] = mult[cname]
                            stack.append(bn)
    return comps, mult


_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(?[^(]*?\)?)\s*([\w\-]+)\(")


_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")


def _dus_fusion_overrides(comps) -> Dict[str, int]:
    """Fusions whose root is dynamic-update-slice write only the *slice*, not the
    whole buffer (XLA aliases the output with the input cache).  Map fusion
    computation -> bytes of the update operand."""
    out: Dict[str, int] = {}
    for cname, lines in comps.items():
        root_dus = None
        shapes = {}
        for line in lines:
            im = _INSTR_RE.match(line)
            if not im:
                continue
            lhs = line.split("=")[0].strip().lstrip("%").replace("ROOT ", "").strip()
            lhs = lhs.lstrip("%")
            shapes[lhs] = im.group(1)
            if im.group(2) == "dynamic-update-slice" and "ROOT" in line:
                ops = re.findall(r"%([\w.\-]+)", line.split("dynamic-update-slice(")[1])
                if len(ops) >= 2:
                    root_dus = ops[1]   # the update operand
        if root_dus and root_dus in shapes:
            out[cname] = _shape_bytes(shapes[root_dus])
    return out


def parse_hbm_bytes(hlo_text: str) -> float:
    """Approximate per-device HBM traffic for one step: sum of instruction OUTPUT
    bytes (top-level, post-fusion — fusion internals never hit HBM) times the
    enclosing while-loop trip counts, plus one read of every entry parameter.
    Dynamic-update-slice (cache writes) counts only the updated slice.  Writes are
    counted once per tensor; reads of produced tensors are omitted (they pair 1:1
    with writes — a consistent ~0.5x convention for intermediate traffic)."""
    comps, mult = _comp_multipliers(hlo_text)
    dus_override = _dus_fusion_overrides(comps)
    total = 0.0
    for cname, m in mult.items():
        lines = comps.get(cname, ())
        shapes = {}
        for line in lines:
            im = _INSTR_RE.match(line)
            if im:
                lhs = line.split("=")[0].replace("ROOT", "").strip().lstrip("%")
                shapes[lhs] = im.group(1)
        for line in lines:
            im = _INSTR_RE.match(line)
            if not im:
                continue
            op = im.group(2)
            if op in _SKIP_OPS or op.endswith("-done"):
                continue
            if op == "fusion":
                cm = _CALLS_RE.search(line)
                if cm and cm.group(1) in dus_override:
                    total += dus_override[cm.group(1)] * m
                    continue
            if op == "dynamic-update-slice":
                ops = re.findall(r"%([\w.\-]+)", line.split("dynamic-update-slice(")[1])
                if len(ops) >= 2 and ops[1] in shapes:
                    total += _shape_bytes(shapes[ops[1]]) * m
                    continue
            total += _shape_bytes(im.group(1)) * m
    # entry parameters (weights, optimizer state, caches) are read once
    _, entry = _split_computations(hlo_text)
    for line in comps.get(entry, ()):
        if re.search(r"=\s*[^(]*\sparameter\(", line):
            im = _INSTR_RE.match(line)
            if im:
                total += _shape_bytes(im.group(1))
    return total


@dataclasses.dataclass
class Roofline:
    flops_per_chip: float
    hbm_bytes_per_chip: float
    collective_bytes_per_chip: float
    collectives: CollectiveStats
    model_flops_global: float = 0.0     # 6*N*D analytic
    n_chips: int = 1
    xla_raw_flops: float = 0.0          # XLA cost_analysis (while bodies counted once)
    scan_factor: float = 1.0            # jaxpr/XLA flop ratio applied to bytes

    @property
    def t_compute(self) -> float:
        return self.flops_per_chip / V5E_PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes_per_chip / V5E_HBM_BW

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_chip / V5E_LINK_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs (global) — catches remat/redundancy waste."""
        hlo_global = self.flops_per_chip * self.n_chips
        return self.model_flops_global / hlo_global if hlo_global else 0.0

    def as_dict(self) -> dict:
        return {
            "flops_per_chip": self.flops_per_chip,
            "hbm_bytes_per_chip": self.hbm_bytes_per_chip,
            "collective_bytes_per_chip": self.collective_bytes_per_chip,
            "collective_breakdown": self.collectives.bytes_by_op,
            "collective_counts": self.collectives.counts_by_op,
            "dcn_bytes_per_chip": self.collectives.dcn_bytes,
            "xla_raw_flops": self.xla_raw_flops,
            "scan_factor": self.scan_factor,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops_global": self.model_flops_global,
            "useful_flops_ratio": self.useful_flops_ratio,
        }


def analyze(compiled, *, model_flops_global: float, n_chips: int,
            jaxpr_flops_global: Optional[float] = None,
            pod_size: Optional[int] = None) -> Roofline:
    """Roofline terms from a compiled SPMD module.

    FLOPs: jaxpr count (scan-aware) / n_chips when available; XLA's raw number is
    kept for reference.  HBM bytes: XLA's fused bytes-accessed, scaled by the
    scan-undercount factor (jaxpr_flops / xla_flops) since XLA counts while
    bodies once.  Collective bytes: while-aware HLO parse.
    """
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # older jaxlib: one dict per device
        cost = cost[0] if cost else {}
    xla_flops = float(cost.get("flops", 0.0))
    if jaxpr_flops_global:
        flops = jaxpr_flops_global / n_chips
        factor = max(flops / max(xla_flops, 1.0), 1.0)
    else:
        flops, factor = xla_flops, 1.0
    hlo_text = compiled.as_text()
    stats = parse_collectives(hlo_text, pod_size=pod_size)
    return Roofline(
        flops_per_chip=flops,
        hbm_bytes_per_chip=parse_hbm_bytes(hlo_text),
        collective_bytes_per_chip=float(stats.total_bytes),
        collectives=stats,
        model_flops_global=model_flops_global,
        n_chips=n_chips,
        xla_raw_flops=xla_flops,
        scan_factor=factor,
    )


# ------------------------------------------------------- jaxpr FLOP counting
#
# XLA's cost_analysis counts a while-loop body ONCE (scan trip counts are not
# multiplied) — for scan-over-layers models that underreports FLOPs by ~n_layers.
# We therefore count matmul/conv FLOPs by walking the jaxpr, multiplying scan
# bodies by their trip count (remat recompute shows up naturally in the grad
# jaxpr).  Elementwise/reduce ops are excluded: they are memory-bound and are
# captured by the memory term.

def _dot_flops(eqn) -> float:
    (c_l, c_r), (b_l, b_r) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval.shape, eqn.invars[1].aval.shape
    batch = 1.0
    for d in b_l:
        batch *= lhs[d]
    contract = 1.0
    for d in c_l:
        contract *= lhs[d]
    m = 1.0
    for i, s in enumerate(lhs):
        if i not in c_l and i not in b_l:
            m *= s
    n = 1.0
    for i, s in enumerate(rhs):
        if i not in c_r and i not in b_r:
            n *= s
    return 2.0 * batch * m * n * contract


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape  # kernel
    import numpy as _np
    kernel_prod = float(_np.prod(rhs))
    # approx: 2 * output_size * kernel_elems_per_output (= prod(kernel)/out_features)
    out_feat = rhs[-1] if len(rhs) >= 2 else 1
    return 2.0 * float(_np.prod(out.shape)) * kernel_prod / max(out_feat, 1) \
        * (out_feat / max(out_feat, 1))


def jaxpr_flops(jaxpr) -> float:
    """Matmul/conv FLOPs of a (closed) jaxpr, with scan bodies x trip count."""
    total = 0.0
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += _dot_flops(eqn)
        elif name == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif name == "scan":
            total += eqn.params["length"] * jaxpr_flops(eqn.params["jaxpr"])
        elif name == "while":
            total += jaxpr_flops(eqn.params["body_jaxpr"])  # trip count unknown
        elif name == "cond":
            branches = eqn.params["branches"]
            total += max(jaxpr_flops(b) for b in branches)
        else:
            for key in ("jaxpr", "call_jaxpr"):
                if key in eqn.params:
                    total += jaxpr_flops(eqn.params[key])
                    break
    return total


def count_fn_flops(fn, *args) -> float:
    import jax as _jax
    return jaxpr_flops(_jax.make_jaxpr(fn)(*args))


# ------------------------------------------------------- analytic MODEL_FLOPS

def model_flops(cfg, shape, params_count: int, active_params: Optional[int] = None) -> float:
    """6*N*D (train) / 2*N*D (inference) with N = (active) non-embedding params."""
    n = active_params if active_params is not None else params_count
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n * tokens


def active_param_count(cfg, params_count: int) -> int:
    """MoE: only top_k + shared experts are active per token."""
    if not cfg.moe:
        return params_count
    m = cfg.moe
    expert_params = cfg.n_layers * m.n_routed * 3 * cfg.d_model * m.d_expert
    active_expert = cfg.n_layers * (m.top_k + m.n_shared) * 3 * cfg.d_model * m.d_expert
    return params_count - expert_params + active_expert
