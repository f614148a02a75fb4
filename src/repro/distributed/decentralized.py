"""Sharded decentralized training step (the paper's algorithms, production form).

Global view: decentralized state is *stacked* — every array gets a leading node
axis sharded over the mesh ``node`` axis, so "node i's replica" is slice ``i``.
Gossip is compiled from a :class:`~repro.distributed.gossip.GossipPlan`: each
plan shift is ``jnp.roll(payload, s, axis=0)``, which XLA lowers to one
``collective-permute`` of exactly the payload we roll.  Because DCD/ECD roll
the **encoded wire payload** — int8 codes at 8 bits, bit-packed uint32 words
at 2..7 bits, fixed-capacity values + packed index words for the sparse format
— the compiled program's wire traffic on the node axis is the compressed
payload: the traffic reduction is visible in the dry-run HLO, not just claimed.

The codec is any :class:`~repro.distributed.wire.WireFormat` (quant / sparse /
fp16 / identity, or a registered new one); the topology is any plan
``make_gossip_plan`` compiles (ring / chain / torus / ... or a custom mixing
matrix) — or a :class:`~repro.distributed.gossip.GossipSchedule` of rounds
(``full_logn``: the dense average at O(log n) permutes per step; ``exp``: the
time-varying one-peer exponential graph, one permute per step).  Compressor
and topology are independently pluggable, per the paper's §2 setup and the
Koloskova/PowerGossip framing.

Algorithm state (beyond params X and optimizer moments):
* D-PSGD/naive: none (naive re-encodes X each round).
* DCD: one replica tree per plan shift (``rep{s:+d}``) — the neighbor models,
  advanced by the received compressed deltas; the invariant
  ``rep{s} == roll(X, s)`` is tested.
* ECD: ``tilde_self`` plus one estimate tree per shift (``tilde{s:+d}``) with
  the (1-2/s, 2/s) update of Algorithm 2.
* CHOCO: ``hat_self`` plus one estimate tree per shift (``hat{s:+d}``) — the
  Koloskova et al. compressed-consensus estimates x-hat, advanced by the
  received compressed differences; mixing happens on the estimates with
  consensus stepsize ``gamma``.
* DeepSqueeze: ``err_self`` only (the local error-feedback residual).  The
  error-compensated MODEL value ``V = X + E`` is compressed — the paper's
  wire quantity, complete on its own — the leftover becomes the next
  residual, and mixing applies the consensus displacement of the decoded
  payloads (``X + mix(D) - D_self``): the receive side is stateless and the
  dense model never rides a collective-permute, only wire containers do.

A *stateful* wire format (``lowrank:<r>:warm``) adds one more aux entry under
``wire.aux_name`` holding its per-leaf codec state — the warm-started
power-iteration factors — initialised by ``init_dist_state(..., wire=...)``
and resynced at phase boundaries by ``rekey_dist_state(..., wire=...)``.

Stochastic rounding uses the same counter-based PCG hash as the Pallas kernel
(kernels/ref.py), seeded by (step, salt, leaf) — deterministic, key-free inside
the compiled step, and identical to the stacked reference's seeding.

Every phase of the step is traced under a ``jax.named_scope`` (:data:`PHASES`),
so each compiled instruction's ``op_name`` metadata names the phase it
belongs to and a profile of the step can be split by phase.  Scopes do not
nest across phases: an argument evaluated at a call site (``_roll(payload,
s)`` handed to the decode) carries its own phase.  The scopes are metadata
only: the compiled program is otherwise the same instruction for instruction.
"""
from __future__ import annotations

import functools
import warnings
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.failures import (
    edge_drop_mask,
    fresh_key,
    make_drop_spec,
    select_delivered,
    update_freshness,
)
from repro.distributed.gossip import (
    GossipPlan,
    GossipSchedule,
    as_schedule,
    make_gossip_plan,
    plan_mix,
    plan_mix_gated,
    roll_tree,
)
from repro.distributed.wire import WireFormat, make_wire_format
from repro.optim.optimizers import Optimizer, apply_updates

# the named scopes of the train step, one per phase; the forward, backward and
# recomputed parts of ``fwd_bwd`` carry JAX's own jvp / transpose /
# rematted_computation markers (``optimizer`` is set in optim/optimizers.py)
PHASES = ("fwd_bwd", "optimizer", "encode", "permute", "decode", "mix",
          "step_metrics")


def _scoped(name: str, fn: Callable) -> Callable:
    """``fn`` traced under ``jax.named_scope(name)``."""
    def scoped(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)
    return scoped


_roll = _scoped("permute", roll_tree)
_mix = _scoped("mix", plan_mix)
_mix_gated = _scoped("mix", plan_mix_gated)
_select_delivered = _scoped("decode", select_delivered)


# --------------------------------------------------------------- state

class DistState(NamedTuple):
    params: Any              # stacked (n, ...)
    opt: Any                 # optimizer state (stacked moments)
    aux: Dict[str, Any]      # algorithm-specific stacked trees, keyed by shift
    step: jax.Array


def _resolve_plan(plan, topology: Optional[str]):
    """plan may be a GossipPlan / GossipSchedule or (deprecated) an int node
    count combined with a ``topology="ring"|"torus"`` string."""
    if isinstance(plan, (GossipPlan, GossipSchedule)):
        assert topology is None, \
            "pass either a GossipPlan or the deprecated topology= string, not both"
        return plan
    n = int(plan)
    if topology is not None:
        warnings.warn(
            "topology=<str> with an integer node count is deprecated; pass "
            f"plan=make_gossip_plan({topology!r}, n) instead",
            DeprecationWarning, stacklevel=3)
        return make_gossip_plan(topology, n)
    return GossipPlan.ring(n)


def init_dist_state(algo: str, params_single: Any, plan, opt: Optimizer,
                    aux_dtype=None, topology: Optional[str] = None,
                    drop=None, wire=None) -> DistState:
    """``plan``: a :class:`GossipPlan` / :class:`GossipSchedule` (or an int
    node count => ring) — one replica/estimate tree per shift in the plan (for
    a schedule: per shift in the union over rounds; one tree serves every
    round that uses the shift).  ``aux_dtype``: storage dtype for
    replicas/estimates (bf16 on the biggest archs — they hold reconstructed
    quantized values, so bf16 rounding is well below the quantization bin; see
    DESIGN.md plans table).

    ``drop`` (a :class:`~repro.distributed.failures.DropSpec`, rate float, or
    ``"rate[:salt[:decay]]"`` spec; None/0 disables): failure injection.  For
    the replica-tracking algorithms it adds one degraded-mode freshness
    vector per union shift — keyed ``fresh{s:+d}@drop{salt}`` so restoring a
    failure-mode checkpoint under a *different* drop salt fails loudly with a
    KeyError instead of silently splicing failure traces.

    ``wire`` (a :class:`~repro.distributed.wire.WireFormat` or spec string):
    required when the codec is *stateful* (``lowrank:<r>:warm``) — its
    per-leaf codec state is added under ``wire.aux_name`` (rank-embedded, so
    restoring a checkpoint with a mismatched rank KeyErrors).  Stateless
    wires ignore it."""
    sched = as_schedule(_resolve_plan(plan, topology))
    n_nodes = sched.n
    drop = make_drop_spec(drop)
    X = jax.tree.map(lambda p: jnp.broadcast_to(p[None], (n_nodes,) + p.shape),
                     params_single)

    def aux_copy():
        if aux_dtype is None:
            return X
        return jax.tree.map(
            lambda l: l.astype(aux_dtype) if l.dtype == jnp.float32 else l, X)

    aux: Dict[str, Any] = {}
    if algo == "dcd":
        aux = {f"rep{s:+d}": aux_copy() for s in sched.shift_union}
    elif algo == "ecd":
        aux = {"tilde_self": aux_copy()}
        aux.update({f"tilde{s:+d}": aux_copy() for s in sched.shift_union})
    elif algo == "choco":
        aux = {"hat_self": aux_copy()}
        aux.update({f"hat{s:+d}": aux_copy() for s in sched.shift_union})
    elif algo == "deepsqueeze":
        aux = {"err_self": jax.tree.map(jnp.zeros_like, aux_copy())}
    if drop is not None and algo in ("dcd", "ecd", "choco"):
        aux.update({fresh_key(s, drop.salt): jnp.ones((n_nodes,), jnp.float32)
                    for s in sched.shift_union})
    if wire is not None:
        wire = make_wire_format(wire)
        if wire.stateful:
            aux[wire.aux_name] = wire.init_aux(X)
    return DistState(params=X, opt=opt.init(X), aux=aux,
                     step=jnp.zeros((), jnp.int32))


def rekey_dist_state(state: DistState, algo: str, plan, aux_dtype=None,
                     drop=None, wire=None) -> DistState:
    """Re-key the gossip aux trees for a NEW ``{plan, wire}`` at a phase
    boundary (``launch/train.py --phase-plan``), keeping params, optimizer
    moments and the step counter.

    Switching plan or wire mid-training invalidates the aux trees twice
    over: the shift-union key set changes with the plan, and the
    replica/estimate *values* encode the compression history of the old
    wire.  The honest reset is a **resync**: every replica/estimate becomes
    the exact current neighbor params (``roll(X, s)`` — one full-precision
    payload round on the real network, which is what a deployment pays at a
    phase switch), DeepSqueeze residuals restart at zero, stateful-wire codec
    state restarts from ``wire.init_aux`` (a pure function of the param
    shapes — cold factors, re-warmed within a few rounds), and degraded-mode
    freshness restarts at fully-fresh.  From there the differential
    invariants of the new phase hold exactly as from ``init_dist_state`` —
    a stacked :class:`~repro.core.algorithms.GossipReference` initialised
    from the same resynced state tracks the sharded runtime at the usual
    atol (tests/test_adaptive.py pins the composite trajectory)."""
    sched = as_schedule(_resolve_plan(plan, None))
    drop = make_drop_spec(drop)
    X = state.params

    def cast(tree):
        if aux_dtype is None:
            return tree
        return jax.tree.map(
            lambda l: l.astype(aux_dtype) if l.dtype == jnp.float32 else l,
            tree)

    n_nodes = sched.n
    aux: Dict[str, Any] = {}
    if algo == "dcd":
        aux = {f"rep{s:+d}": cast(_roll(X, s)) for s in sched.shift_union}
    elif algo == "ecd":
        aux = {"tilde_self": cast(X)}
        aux.update({f"tilde{s:+d}": cast(_roll(X, s))
                    for s in sched.shift_union})
    elif algo == "choco":
        aux = {"hat_self": cast(X)}
        aux.update({f"hat{s:+d}": cast(_roll(X, s))
                    for s in sched.shift_union})
    elif algo == "deepsqueeze":
        aux = {"err_self": jax.tree.map(jnp.zeros_like, cast(X))}
    if drop is not None and algo in ("dcd", "ecd", "choco"):
        aux.update({fresh_key(s, drop.salt): jnp.ones((n_nodes,), jnp.float32)
                    for s in sched.shift_union})
    if wire is not None:
        wire = make_wire_format(wire)
        if wire.stateful:
            aux[wire.aux_name] = wire.init_aux(X)
    return state._replace(aux=aux)


# --------------------------------------------------------------- the step

def _make_decode_axpy(wire: WireFormat, mesh) -> Optional[Callable]:
    """Fused receive path, wrapped in shard_map over the node axis when a mesh
    is given.  Each shard hands its local slab of the stacked payload
    (codes + scales, or sparse values + packed index words) and accumulator
    straight to the fused Pallas kernel — the gate lives in the wire format's
    own ``decode_axpy`` (one 128-lane contract for every format).

    Returns ``None`` for meshes with axes beyond "node": wrapping only the
    node axis would force GSPMD to gather every fsdp/model-sharded leaf at the
    shard_map boundary (the §Perf-iteration-3 regression this runtime exists
    to avoid); the caller then keeps the sharding-preserving jnp reference
    path.  Partial-manual ``shard_map(axis_names={"node"})`` is what would
    lift this.
    """
    if mesh is None or "node" not in getattr(mesh, "axis_names", ()):
        return wire.decode_axpy_tree
    if any(a != "node" for a in mesh.axis_names):
        return None

    from jax.sharding import PartitionSpec as P

    def dec_axpy(treedef, payloads, acc_tree, weight, acc_weight=1.0):
        def inner(payloads_, acc_, w_, aw_):
            return wire.decode_axpy_tree(treedef, payloads_, acc_, w_, aw_)

        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P("node"), P("node"), P(), P()),
            out_specs=P("node"), check_vma=False,
        )(payloads, acc_tree, jnp.asarray(weight, jnp.float32),
          jnp.asarray(acc_weight, jnp.float32))

    return dec_axpy


def make_dist_train_step(
    loss_fn: Callable[[Any, Any], Tuple[jax.Array, Dict]],
    algo: str,
    opt: Optimizer,
    wire: Optional[Any],     # WireFormat | spec str | None (full precision)
    plan,                    # GossipPlan | int node count (=> ring)
    lr_schedule: Callable[[jax.Array], jax.Array],
    *,
    mesh: Optional[Any] = None,
    fused: Optional[bool] = None,
    drop: Optional[Any] = None,       # DropSpec | rate | "rate[:salt[:decay]]"
    gamma: float = 0.5,               # CHOCO consensus stepsize, in (0, 1]
    topology: Optional[str] = None,   # deprecated: use plan=make_gossip_plan(...)
):
    """Build ``step(state, batch) -> (state, metrics)``.

    ``loss_fn(params_i, batch_i)`` is the per-node loss; it is vmapped over the
    stacked node axis.  ``batch`` leaves are (n, per_node_batch, ...).

    ``wire``: the gossip payload codec — any :class:`WireFormat` or a
    ``make_wire_format`` spec string (``"quant:4"``, ``"sparse:0.25:topk"``,
    ``"fp16"``); ``None`` means the raw fp32 leaves ride the permute (only
    meaningful for cpsgd/dpsgd).  ``plan``: the gossip graph — any
    :class:`GossipPlan` or :class:`GossipSchedule`
    (``make_gossip_plan("chain", n)``, ``make_gossip_plan("full_logn", n)``, a
    compiled mixing matrix, ...) or an int node count for the default ring.
    DCD/ECD/CHOCO aux trees key off the schedule's shift union (== the plan's
    shifts for a flat plan); one collective-permute per shift per round.
    ``gamma`` is CHOCO's consensus stepsize (ignored by the other algorithms):
    ``X <- X_half + gamma * (mix(hat) - hat_self)``, valid on (0, 1].

    ``fused`` (default: auto — on iff the wire format packs) routes every
    DCD/ECD receive-side decode through the format's fused axpy Pallas kernel
    (one VMEM pass: unpack -> dequantize/scatter -> accumulate) instead of the
    jnp reference path + XLA fusion.  When ``mesh`` (a pure node-axis mesh) is
    given, the fused decode runs under ``shard_map`` so each shard feeds its
    local payload slab straight into the kernel; without a mesh the kernel is
    called inline (single-process runs).  Multi-axis meshes fall back to the
    reference path — see :func:`_make_decode_axpy`.

    Schedules: a multi-round :class:`GossipSchedule` iterates its rounds
    INSIDE the jitted step — round r of step t re-encodes with the effective
    counter ``t * period + r`` fed to the same (step, salt, leaf) seeding, so
    compression randomness stays bit-reproducible and a single-round schedule
    is bit-identical to the flat plan path.  The gradient update rides round
    0; rounds 1.. are pure compressed gossip (the stacked equivalent is the
    core/algorithms step chained with zero gradients — the differential tier
    pins it).  A ``time_varying`` schedule (``exp``) instead runs ONE round
    per step — ``rounds[t % period]`` via ``lax.switch`` — so every step pays
    a single collective-permute while the effective W over a period is dense.

    Failure injection: ``drop`` (a
    :class:`~repro.distributed.failures.DropSpec`, a rate float, or a
    ``"rate[:salt[:decay]]"`` spec string) injects deterministic per-edge
    payload drops.  Every round, every directed edge ``i <- i-s`` keeps or
    drops its payload by a PCG hash of ``(effective step counter, shift,
    node, drop_salt)`` — the same counter the wire seeding uses, so the
    failure trace is bit-reproducible and shared with the stacked
    :class:`~repro.core.algorithms.GossipReference`.  A dropped edge's
    contribution is zeroed and its mixing weight folded into the self weight
    (each realized W row stays stochastic); for DCD/ECD the stale
    replica/estimate tree is frozen (no phantom update) and its future vote
    decays by ``drop.decay`` per missed delivery, recovering geometrically on
    receipt.  ``drop=None`` (or rate 0) compiles the machinery out entirely —
    the program is bit-identical to one built without the feature.  The
    ``cpsgd`` AllReduce baseline models the reliable datacenter fabric and
    refuses drop injection.
    """
    assert algo in ("cpsgd", "dpsgd", "naive", "dcd", "ecd",
                    "choco", "deepsqueeze")
    assert 0.0 < gamma <= 1.0, f"CHOCO consensus stepsize gamma={gamma} " \
        "must lie in (0, 1]"
    sched = as_schedule(_resolve_plan(plan, topology))
    rounds, n_rounds, union = sched.rounds, sched.period, sched.shift_union
    n_nodes = sched.n
    time_varying = sched.time_varying and n_rounds > 1
    drop = make_drop_spec(drop)
    assert drop is None or algo != "cpsgd", \
        "drop injection models gossip-edge failure; the cpsgd AllReduce " \
        "baseline assumes the reliable datacenter fabric"
    if wire is not None:
        wire = make_wire_format(wire)
    use_fused = (wire is not None and wire.packed) if fused is None else bool(fused)

    dec_axpy = None
    if wire is not None and use_fused:
        dec_axpy = _make_decode_axpy(wire, mesh)
    if wire is not None and dec_axpy is None:
        def dec_axpy(treedef, payloads, acc_tree, weight, acc_weight=1.0):
            # reference path: decode at f32 (like the fused kernel), then axpy
            likes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), acc_tree)
            dec = wire.decode_tree(treedef, payloads, likes)
            return jax.tree.map(
                lambda a, d: (acc_weight * a + weight * d).astype(a.dtype),
                acc_tree, dec)
    if dec_axpy is not None:
        dec_axpy = _scoped("decode", dec_axpy)
    decode_tree = _scoped("decode", wire.decode_tree) if wire is not None else None

    wire_aux_key = wire.aux_name if (wire is not None and wire.stateful) \
        else None

    def encode_tree(tree, enc_step, *, salt, aux):
        # Encode with optional per-leaf codec state (the lowrank warm-start
        # factors, keyed ``wire.aux_name`` in the DistState aux — present iff
        # init_dist_state was given the wire).  Stateless formats pass the
        # aux dict through untouched, so the compiled program is unchanged.
        with jax.named_scope("encode"):
            if wire_aux_key is None:
                tdef, payloads = wire.encode_tree(tree, enc_step, salt)
                return tdef, payloads, aux
            aux = dict(aux)
            tdef, payloads, waux = wire.encode_tree_stateful(
                tree, enc_step, salt, aux[wire_aux_key])
            aux[wire_aux_key] = waux
            return tdef, payloads, aux

    grad_fn = jax.vmap(jax.value_and_grad(loss_fn, has_aux=True), spmd_axis_name="node")

    # ---- one gossip round per algorithm ----------------------------------
    # Each helper advances (X, aux) through ONE plan round; ``upd`` is the
    # optimizer update, threaded only into the round that owns the gradient
    # (round 0 of a per-step schedule; every step of a time-varying one).
    # ``enc_step`` is the effective encode counter — ``step`` for a flat plan,
    # ``step * period + r`` inside a multi-round step — so the stacked
    # reference reproduces the exact payload bits by chaining its own steps.

    # Failure injection (drop is not None): each round first draws the
    # per-edge delivery masks for every union shift at the round's effective
    # counter, advances the degraded-mode freshness vectors, then (a) mixes
    # through plan_mix_gated — gate = mask * freshness, dropped mass folded
    # into the self weight — and (b) freezes every replica/estimate tree on
    # its dropped edges via a post-decode select (the fused axpy kernel keeps
    # its scalar-weight contract; the select fuses into the same pass).

    # the delivery masks, freshness and gates set the mixing weights, so they
    # are traced as part of ``mix``

    def _round_masks(enc_step, shifts):
        with jax.named_scope("mix"):
            return {s: edge_drop_mask(n_nodes, s, enc_step, drop) for s in shifts}

    def _advance_freshness(aux_d, masks):
        with jax.named_scope("mix"):
            for s in union:
                fk = fresh_key(s, drop.salt)
                aux_d[fk] = update_freshness(aux_d[fk], masks[s], drop.decay)
            return aux_d

    def _gates(aux_d, masks, shifts):
        with jax.named_scope("mix"):
            return {s: masks[s] * aux_d[fresh_key(s, drop.salt)] for s in shifts}

    def _dpsgd_round(rnd, enc_step, carry, upd):
        X_cur, aux_d = carry
        nbrs = {s: _roll(X_cur, s) for s in rnd.shift_list}
        if drop is None:
            X_mix = _mix(rnd, X_cur, nbrs)
        else:
            X_mix = _mix_gated(rnd, X_cur, nbrs,
                               _round_masks(enc_step, rnd.shift_list))
        if upd is not None:
            X_mix = apply_updates(X_mix, upd)
        return X_mix, aux_d

    def _naive_round(rnd, enc_step, carry, upd):
        # compress the exchanged models directly — provably non-convergent
        X_cur, aux_d = carry
        tdef, payload, aux_d = encode_tree(X_cur, enc_step, salt=1, aux=aux_d)
        dec_self = decode_tree(tdef, payload, X_cur)
        nbrs = {s: decode_tree(tdef, _roll(payload, s), X_cur)
                for s in rnd.shift_list}
        if drop is None:
            X_mix = _mix(rnd, dec_self, nbrs)
        else:
            X_mix = _mix_gated(rnd, dec_self, nbrs,
                               _round_masks(enc_step, rnd.shift_list))
        if upd is not None:
            X_mix = apply_updates(X_mix, upd)
        return X_mix, aux_d

    def _dcd_round(rnd, enc_step, carry, upd):
        X_cur, aux_d = carry
        aux_d = dict(aux_d)
        reps = {s: aux_d[f"rep{s:+d}"] for s in rnd.shift_list}
        if drop is None:
            masks = None
            X_half = _mix(rnd, X_cur, reps)
        else:
            masks = _round_masks(enc_step, union)
            aux_d = _advance_freshness(aux_d, masks)
            X_half = _mix_gated(rnd, X_cur, reps,
                                _gates(aux_d, masks, rnd.shift_list))
        if upd is not None:
            X_half = apply_updates(X_half, upd)
        with jax.named_scope("encode"):
            Z = jax.tree.map(lambda a, b: a - b, X_half, X_cur)
        tdef, payload, aux_d = encode_tree(Z, enc_step, salt=2, aux=aux_d)
        # receive side: one fused unpack+dequant+axpy kernel per leaf; every
        # union replica advances with the rolled payload so rep{s} keeps
        # tracking roll(X, s) through every round (under drops: through every
        # *delivered* round — a dropped edge freezes the replica)
        X_cur = dec_axpy(tdef, payload, X_cur, 1.0)
        for s in union:
            rep = dec_axpy(tdef, _roll(payload, s), aux_d[f"rep{s:+d}"], 1.0)
            if masks is not None:
                rep = _select_delivered(masks[s], rep, aux_d[f"rep{s:+d}"])
            aux_d[f"rep{s:+d}"] = rep
        return X_cur, aux_d

    def _ecd_round(rnd, enc_step, carry, upd):
        X_cur, aux_d = carry
        aux_d = dict(aux_d)
        with jax.named_scope("encode"):
            s_t = (enc_step + 1).astype(jnp.float32)
        tildes = {s: aux_d[f"tilde{s:+d}"] for s in rnd.shift_list}
        if drop is None:
            masks = None
            X_mix = _mix(rnd, aux_d["tilde_self"], tildes)
        else:
            masks = _round_masks(enc_step, union)
            aux_d = _advance_freshness(aux_d, masks)
            X_mix = _mix_gated(rnd, aux_d["tilde_self"], tildes,
                               _gates(aux_d, masks, rnd.shift_list))
        X_next = apply_updates(X_mix, upd) if upd is not None else X_mix
        with jax.named_scope("encode"):
            Z = jax.tree.map(lambda a, b: (1.0 - 0.5 * s_t) * a + 0.5 * s_t * b,
                             X_cur, X_next)
        tdef, payload, aux_d = encode_tree(Z, enc_step, salt=3, aux=aux_d)
        with jax.named_scope("decode"):
            est_decay = 1.0 - 2.0 / s_t
            blend = 2.0 / s_t
        # est_decay*tilde + blend*decode in ONE fused pass per leaf: the decay
        # scale rides the kernel's acc_weight operand, so no pre-scaled
        # f32 accumulator is ever written to HBM
        aux_d["tilde_self"] = dec_axpy(tdef, payload, aux_d["tilde_self"],
                                       blend, est_decay)
        for s in union:
            est = dec_axpy(tdef, _roll(payload, s), aux_d[f"tilde{s:+d}"],
                           blend, est_decay)
            if masks is not None:
                est = _select_delivered(masks[s], est, aux_d[f"tilde{s:+d}"])
            aux_d[f"tilde{s:+d}"] = est
        return X_next, aux_d

    def _choco_round(rnd, enc_step, carry, upd):
        # CHOCO-SGD (Koloskova et al.): gossip happens on the compressed
        # consensus estimates x-hat, never on X itself, so ANY contractive
        # compressor (biased sign/top-k included) keeps the fixed point.
        X_cur, aux_d = carry
        aux_d = dict(aux_d)
        if drop is None:
            masks = None
        else:
            masks = _round_masks(enc_step, union)
            aux_d = _advance_freshness(aux_d, masks)
        X_half = apply_updates(X_cur, upd) if upd is not None else X_cur
        with jax.named_scope("encode"):
            Z = jax.tree.map(lambda a, b: a - b, X_half, aux_d["hat_self"])
        tdef, payload, aux_d = encode_tree(Z, enc_step, salt=4, aux=aux_d)
        # every node decodes the SAME words it sent, so hat_self stays equal
        # to every neighbor's hat{s} of this node — the shared-estimate
        # invariant ``hat{s} == roll(hat_self, s)`` is tested (drop-free)
        hat_self = dec_axpy(tdef, payload, aux_d["hat_self"], 1.0)
        aux_d["hat_self"] = hat_self
        for s in union:
            hat = dec_axpy(tdef, _roll(payload, s), aux_d[f"hat{s:+d}"], 1.0)
            if masks is not None:
                hat = _select_delivered(masks[s], hat, aux_d[f"hat{s:+d}"])
            aux_d[f"hat{s:+d}"] = hat
        hats = {s: aux_d[f"hat{s:+d}"] for s in rnd.shift_list}
        if masks is None:
            mixed = _mix(rnd, hat_self, hats)
        else:
            mixed = _mix_gated(rnd, hat_self, hats,
                               _gates(aux_d, masks, rnd.shift_list))
        with jax.named_scope("mix"):
            X_new = jax.tree.map(
                lambda x, m, h: (x + gamma * (m - h)).astype(x.dtype),
                X_half, mixed, hat_self)
        return X_new, aux_d

    def _deepsqueeze_round(rnd, enc_step, carry, upd):
        # DeepSqueeze, wire-honest form: compress the error-compensated MODEL
        # value V = X + E (the paper's actual wire quantity) and apply the
        # consensus displacement on decoded payloads only,
        # X <- X_half + sum_j W_ij D_j - D_self, so the receive side is
        # stateless (no replicas, nothing to desync) and the dense model
        # never rides a permute — only wire containers do (the analyzer's
        # old allow_dense exemption is gone).  At identity compression with
        # E = 0 this is exactly X_half W (D-PSGD); the residual keeps
        # whatever the codec dropped on the sender, and a dropped edge just
        # renormalizes the round like D-PSGD.
        X_cur, aux_d = carry
        aux_d = dict(aux_d)
        X_half = apply_updates(X_cur, upd) if upd is not None else X_cur
        with jax.named_scope("encode"):
            V = jax.tree.map(lambda x, e: x + e, X_half, aux_d["err_self"])
        tdef, payload, aux_d = encode_tree(V, enc_step, salt=5, aux=aux_d)
        aux_d["err_self"] = dec_axpy(tdef, payload, V, -1.0)
        with jax.named_scope("decode"):
            zero = jax.tree.map(jnp.zeros_like, X_half)
        d_self = dec_axpy(tdef, payload, zero, 1.0)
        nbrs = {s: dec_axpy(tdef, _roll(payload, s), zero, 1.0)
                for s in rnd.shift_list}
        if drop is None:
            mixed = _mix(rnd, d_self, nbrs)
        else:
            mixed = _mix_gated(rnd, d_self, nbrs,
                               _round_masks(enc_step, rnd.shift_list))
        with jax.named_scope("mix"):
            X_new = jax.tree.map(lambda x, m, d: (x + (m - d)).astype(x.dtype),
                                 X_half, mixed, d_self)
        return X_new, aux_d

    round_fn = {"dpsgd": _dpsgd_round, "naive": _naive_round,
                "dcd": _dcd_round, "ecd": _ecd_round,
                "choco": _choco_round,
                "deepsqueeze": _deepsqueeze_round}.get(algo)

    def step(state: DistState, batch: Any) -> Tuple[DistState, Dict[str, jax.Array]]:
        with jax.named_scope("fwd_bwd"):
            (losses, metrics), grads = grad_fn(state.params, batch)
        with jax.named_scope("optimizer"):
            lr = lr_schedule(state.step)
        updates, opt_state = opt.update(grads, state.opt, state.params, lr)
        X, aux = state.params, dict(state.aux)

        if algo == "cpsgd":
            # AllReduce baseline: identical replicas apply the node-mean update.
            with jax.named_scope("mix"):
                mean_upd = jax.tree.map(
                    lambda u: jnp.broadcast_to(jnp.mean(u, axis=0, keepdims=True),
                                               u.shape),
                    updates)
            X_new = apply_updates(X, mean_upd)

        elif time_varying:
            # one round per step, selected by the traced step counter; every
            # branch updates the same (X, union-aux) structure, and the
            # gradient rides every step (each step IS one algorithm step with
            # the time-varying W_t = rounds[t % period])
            X_new, aux = jax.lax.switch(
                state.step % n_rounds,
                [functools.partial(round_fn, rnd, state.step, upd=updates)
                 for rnd in rounds],
                (X, aux))

        else:
            # all rounds inside this one step: the effective (dense) W at
            # sum(round.degree) permutes.  dpsgd/naive apply the update AFTER
            # the rounds (X W_eff - lr G — one stacked step with the effective
            # W); dcd/ecd thread it into round 0 (the stacked equivalent is
            # their reference step chained with zero gradients after round 0)
            grad_round = 0 if algo in ("dcd", "ecd", "choco",
                                       "deepsqueeze") else None
            carry = (X, aux)
            for r_idx, rnd in enumerate(rounds):
                carry = round_fn(rnd, state.step * n_rounds + r_idx, carry,
                                 updates if r_idx == grad_round else None)
            X_new, aux = carry
            if grad_round is None:
                X_new = apply_updates(X_new, updates)

        with jax.named_scope("step_metrics"):
            consensus = sum(
                jnp.sum((l - jnp.mean(l, axis=0, keepdims=True)) ** 2)
                for l in jax.tree.leaves(X_new))
            out_metrics = {
                "loss": jnp.mean(losses),
                "lr": lr,
                "consensus": consensus,
                **{k: jnp.mean(v) for k, v in metrics.items()},
            }
        return DistState(params=X_new, opt=opt_state, aux=aux,
                         step=state.step + 1), out_metrics

    return step


# ------------------------------------------------------- deprecated spellings

def gossip_shifts(topology: str, n: int) -> Tuple[float, Dict[int, float]]:
    """Deprecated: use :func:`repro.distributed.gossip.make_gossip_plan`.

    Returns the old ``(self_weight, {shift: weight})`` view of the compiled
    plan (uniform-weight topologies only)."""
    warnings.warn("gossip_shifts is deprecated; use make_gossip_plan(topology, n)",
                  DeprecationWarning, stacklevel=2)
    plan = make_gossip_plan(topology, n)
    assert plan.uniform, f"{topology!r} compiles to per-node weights; use the plan"
    return plan.self_weight, dict(plan.shifts)


_DEPRECATED = {
    "WireCodec": "QuantWire",
    "SparseWireCodec": "SparseWire",
}


def __getattr__(name: str):
    if name in _DEPRECATED:
        from repro.distributed import wire as _wire

        new = _DEPRECATED[name]
        warnings.warn(
            f"repro.distributed.decentralized.{name} is deprecated; use "
            f"repro.distributed.wire.{new}", DeprecationWarning, stacklevel=2)
        return getattr(_wire, new)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
