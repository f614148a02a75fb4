"""Failure-injection tests: deterministic edge drops + degraded-mode gossip.

The contract under test, layer by layer:

- ``edge_drop_mask`` is a pure PCG function of (n, shift, step, DropSpec) —
  the runtime, the stacked :class:`~repro.core.algorithms.GossipReference`,
  and netsim's :func:`~repro.netsim.failure_trace` all consume the SAME masks,
  so one failure trace explains every layer.
- Every *realized* per-round mixing matrix stays row-stochastic to 1e-12: the
  self weight absorbs exactly the dropped neighbor mass (renormalization on
  the fly, never a phantom contribution).
- The sharded runtime under drops matches the stacked reference to atol 1e-5
  for {dcd, ecd, dpsgd} x {quant:4, sparse:0.25} x drop {0.0, 0.2, 0.5},
  with bit-identical wire words (same wire object, same (step, salt, leaf)
  seeds).
- ``drop_rate == 0`` is not merely close to the pre-failure-injection
  runtime — it IS the same program: ``make_drop_spec(0.0)`` normalizes to
  ``None`` and every drop branch is statically absent, asserted bitwise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.algorithms import GossipReference
from repro.distributed.decentralized import init_dist_state, make_dist_train_step
from repro.distributed.failures import (
    DropSpec,
    edge_drop_mask,
    fresh_key,
    make_drop_spec,
    update_freshness,
)
from repro.distributed.gossip import (
    gated_weights,
    make_gossip_plan,
    plan_mix_gated,
    realized_mixing_matrix,
)
from repro.distributed.wire import QuantWire, SparseWire
from repro.launch.mesh import node_mesh
from repro.optim import sgd
from repro.optim.schedules import constant


def _toy_loss(params, batch):
    pred = batch["A"] @ params
    loss = 0.5 * jnp.mean((pred - batch["b"]) ** 2)
    return loss, {"xent": loss}


def _toy_batch(key, n, m=16, d=8):
    kA, kb = jax.random.split(key)
    return {"A": jax.random.normal(kA, (n, m, d)),
            "b": jax.random.normal(kb, (n, m))}


def _grads_for(params, batch):
    return jax.vmap(lambda p, A, b: jax.grad(
        lambda q: 0.5 * jnp.mean((A @ q - b) ** 2))(p))(
        params, batch["A"], batch["b"])


# ------------------------------------------------------------------ DropSpec

def test_make_drop_spec_parsing_and_zero_normalization():
    assert make_drop_spec(None) is None
    assert make_drop_spec(0.0) is None           # rate 0 => the old program
    assert make_drop_spec("0.0:7:0.25") is None
    spec = make_drop_spec(0.2)
    assert spec == DropSpec(rate=0.2)
    assert make_drop_spec("0.3:5") == DropSpec(rate=0.3, salt=5)
    assert make_drop_spec("0.3:5:0.25") == DropSpec(rate=0.3, salt=5, decay=0.25)
    assert make_drop_spec(spec) is spec           # idempotent passthrough
    assert make_drop_spec(0.4, salt=9).salt == 9
    with pytest.raises(AssertionError):
        make_drop_spec(1.0)                       # rate must stay < 1
    with pytest.raises(AssertionError):
        DropSpec(rate=0.5, decay=0.0)             # decay in (0, 1]


def test_edge_drop_mask_deterministic_and_unbiased():
    """Same (n, shift, step, spec) => identical mask; the delivery fraction
    over many draws matches 1 - rate; distinct steps/shifts/salts decorrelate."""
    spec = make_drop_spec(0.3)
    m1 = np.asarray(edge_drop_mask(8, 1, 5, spec))
    m2 = np.asarray(edge_drop_mask(8, 1, 5, spec))
    np.testing.assert_array_equal(m1, m2)
    assert m1.shape == (8,) and set(np.unique(m1)) <= {0.0, 1.0}

    draws = np.stack([np.asarray(edge_drop_mask(64, 1, t, spec))
                      for t in range(200)])
    assert abs(draws.mean() - 0.7) < 0.02
    # a different shift, step, or salt is a different stream
    assert not np.array_equal(draws[0], np.asarray(edge_drop_mask(64, 2, 0, spec)))
    assert not np.array_equal(draws[0], draws[1])
    spec2 = make_drop_spec("0.3:9")
    assert not np.array_equal(draws[0], np.asarray(edge_drop_mask(64, 1, 0, spec2)))


def test_edge_drop_mask_agrees_with_netsim_failure_trace():
    """netsim replays the exact runtime masks: one failure trace, all layers."""
    from repro.netsim import failure_trace

    for topo in ("ring", "exp"):
        plan = make_gossip_plan(topo, 8)
        trace = failure_trace(plan, "0.3:5", n_steps=4)
        spec = make_drop_spec("0.3:5")
        for t, round_masks in enumerate(trace):
            assert round_masks, (topo, t)
            for (enc_step, shift), mask in round_masks.items():
                np.testing.assert_array_equal(
                    mask, np.asarray(edge_drop_mask(8, shift, enc_step, spec)))


def test_update_freshness_dynamics():
    """Freshness halves (x decay) on a miss, recovers one doubling per
    delivery, capped at 1 — the stale-replica down-weight is bounded."""
    f = jnp.ones((4,))
    miss = jnp.zeros((4,))
    hit = jnp.ones((4,))
    f = update_freshness(f, miss, 0.5)
    np.testing.assert_allclose(np.asarray(f), 0.5)
    f = update_freshness(f, miss, 0.5)
    np.testing.assert_allclose(np.asarray(f), 0.25)
    f = update_freshness(f, hit, 0.5)
    np.testing.assert_allclose(np.asarray(f), 0.5)
    f = update_freshness(f, hit, 0.5)
    np.testing.assert_allclose(np.asarray(f), 1.0)
    f = update_freshness(f, hit, 0.5)              # capped
    np.testing.assert_allclose(np.asarray(f), 1.0)


# --------------------------------------------------- renormalization algebra

@pytest.mark.parametrize("topo", ["ring", "chain", "torus", "full_logn", "exp",
                                  "exp_any"])
def test_realized_mixing_matrix_row_stochastic_under_masks(topo):
    """Acceptance: every realized per-round W under deterministic drop masks
    is row-stochastic to 1e-12 — dropped mass lands on the self weight."""
    n = 8 if topo != "torus" else 16
    sched_or_plan = make_gossip_plan(topo, n)
    rounds = getattr(sched_or_plan, "rounds", (sched_or_plan,))
    spec = make_drop_spec("0.4:3")
    for step in range(6):
        for rnd in rounds:
            gates = {s: edge_drop_mask(n, s, step, spec)
                     for s in rnd.shift_list}
            W = np.asarray(realized_mixing_matrix(rnd, gates), np.float64)
            np.testing.assert_allclose(W.sum(axis=1), 1.0, atol=1e-12)
            assert W.min() >= 0.0
            # dropped directed edge i <- i-s carries exactly zero weight
            for s, g in gates.items():
                g = np.asarray(g)
                for i in range(n):
                    if g[i] == 0.0:
                        assert W[i, (i - s) % n] == 0.0 or (i - s) % n == i


def test_plan_mix_gated_matches_realized_matrix():
    """plan_mix_gated == realized W applied to the stacked leaves: the gossip
    kernel and the matrix view are the same operator."""
    n, d = 8, 32
    plan = make_gossip_plan("torus2d", n)
    X = {"w": jax.random.normal(jax.random.key(0), (n, d)),
         "b": jax.random.normal(jax.random.key(1), (n,))}
    spec = make_drop_spec(0.5)
    gates = {s: edge_drop_mask(n, s, 2, spec) for s in plan.shift_list}
    nbrs = {s: jax.tree.map(lambda l: jnp.roll(l, s, axis=0), X)
            for s in plan.shift_list}
    mixed = plan_mix_gated(plan, X, nbrs, gates)
    W = np.asarray(realized_mixing_matrix(plan, gates), np.float64)
    for k in X:
        want = W @ np.asarray(X[k], np.float64).reshape(n, -1)
        np.testing.assert_allclose(
            np.asarray(mixed[k], np.float64).reshape(n, -1), want, atol=1e-6)
    # the gated self/neighbor weights conserve mass exactly
    self_w, w_gated = gated_weights(plan, gates)
    total = np.asarray(self_w, np.float64).copy()
    for s, w in w_gated.items():
        total += np.asarray(w, np.float64)
    np.testing.assert_allclose(total, 1.0, atol=1e-12)


# ------------------------------------------------------- differential tier

_WIRES = {
    "quant4": lambda: QuantWire(bits=4, block=128),
    "sparse25": lambda: SparseWire(p=0.25, block=128),
    "none": lambda: None,
}
_CASES = [(a, w) for a in ("dcd", "ecd") for w in ("quant4", "sparse25")] \
    + [("dpsgd", "none")]


@pytest.mark.parametrize("rate", [0.0, 0.2, 0.5])
@pytest.mark.parametrize("algo,wire_case", _CASES,
                         ids=[f"{a}-{w}" for a, w in _CASES])
def test_dist_step_matches_reference_under_drops(algo, wire_case, rate):
    """Acceptance: sharded {dcd, ecd, dpsgd} x {quant:4, sparse:0.25} x
    drop {0.0, 0.2, 0.5} == stacked GossipReference (atol 1e-5) on identical
    masks, with bit-identical wire words (same object, same seeds)."""
    n, d = 8, 256
    plan = make_gossip_plan("ring", n)
    wire = _WIRES[wire_case]()
    drop = make_drop_spec(rate, salt=4)

    dist_step = jax.jit(make_dist_train_step(
        _toy_loss, algo, sgd(), wire, plan, constant(0.05), drop=drop))
    dist_state = init_dist_state(algo, jnp.zeros((d,)), plan, sgd(), drop=drop)

    ref = GossipReference(name=algo, plan=plan, wire=wire, drop=drop)
    ref_step = jax.jit(ref.step_fn())
    ref_state = ref.init(jnp.zeros((d,)))

    for t in range(4):
        batch = _toy_batch(jax.random.key(t), n, d=d)
        grads = _grads_for(ref_state.params, batch)
        ref_state = ref_step(ref_state, grads, jnp.asarray(t), jnp.float32(0.05))
        dist_state, _ = dist_step(dist_state, batch)
        np.testing.assert_allclose(np.asarray(dist_state.params),
                                   np.asarray(ref_state.params), atol=1e-5)
    if wire is not None:
        # wire words bit for bit: eager vs jit on the same tree/seeds
        key = "codes" if wire_case == "quant4" else "idx"
        salt = {"dcd": 2, "ecd": 3}.get(algo, 1)
        _, pe = wire.encode_tree(dist_state.params, jnp.asarray(2, jnp.int32), salt)
        pj = jax.jit(lambda tr, st: wire.encode_tree(tr, st, salt)[1])(
            dist_state.params, jnp.asarray(2, jnp.int32))
        np.testing.assert_array_equal(np.asarray(pe[0][key]), np.asarray(pj[0][key]))


@pytest.mark.parametrize("spec", ["full_logn", "exp", "exp_any"])
@pytest.mark.parametrize("algo", ["dcd", "ecd"])
def test_dist_schedule_matches_reference_under_drops(algo, spec):
    """Multi-round and time-varying schedules under drops: the per-round
    encode counters (step*period + round) seed the SAME masks in the runtime
    and the reference, so the degraded trajectories agree to atol 1e-5."""
    n, d = 8, 256
    sched = make_gossip_plan(spec, n)
    wire = QuantWire(bits=4, block=128)
    drop = make_drop_spec("0.3:5")

    dist_step = jax.jit(make_dist_train_step(
        _toy_loss, algo, sgd(), wire, sched, constant(0.05), drop=drop))
    dist_state = init_dist_state(algo, jnp.zeros((d,)), sched, sgd(), drop=drop)

    ref = GossipReference(name=algo, plan=sched, wire=wire, drop=drop)
    ref_step = jax.jit(ref.step_fn())
    ref_state = ref.init(jnp.zeros((d,)))

    n_steps = 2 * sched.period if sched.time_varying else 3
    for t in range(n_steps):
        batch = _toy_batch(jax.random.key(t), n, d=d)
        grads = _grads_for(ref_state.params, batch)
        ref_state = ref_step(ref_state, grads, jnp.asarray(t), jnp.float32(0.05))
        dist_state, _ = dist_step(dist_state, batch)
        np.testing.assert_allclose(np.asarray(dist_state.params),
                                   np.asarray(ref_state.params), atol=1e-5)


@pytest.mark.parametrize("algo", ["dcd", "ecd", "dpsgd"])
def test_drop_rate_zero_bit_identical_to_undropped_runtime(algo):
    """Acceptance: drop_rate == 0.0 is the SAME program as the pre-PR runtime
    — make_drop_spec normalizes to None, so every failure branch is statically
    absent and all state leaves stay bitwise equal."""
    n, d = 16, 64
    plan = make_gossip_plan("torus", n)
    wire = QuantWire(bits=4, block=128) if algo != "dpsgd" else None
    drop = make_drop_spec("0.0:7:0.25")
    assert drop is None

    s_old = jax.jit(make_dist_train_step(_toy_loss, algo, sgd(), wire, plan,
                                         constant(0.05)))
    s_new = jax.jit(make_dist_train_step(_toy_loss, algo, sgd(), wire, plan,
                                         constant(0.05), drop=drop))
    st_old = init_dist_state(algo, jnp.zeros((d,)), plan, sgd())
    st_new = init_dist_state(algo, jnp.zeros((d,)), plan, sgd(), drop=drop)
    for t in range(3):
        batch = _toy_batch(jax.random.key(t), n, d=d)
        st_old, m_old = s_old(st_old, batch)
        st_new, m_new = s_new(st_new, batch)
    for a, b in zip(jax.tree.leaves(st_old), jax.tree.leaves(st_new)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(m_old["loss"]) == float(m_new["loss"])


def test_degraded_dcd_freezes_replicas_and_still_converges():
    """Degraded mode end to end: under 20% drops the DCD replica trees freeze
    on missed rounds (no phantom updates — replicas only ever hold genuinely
    delivered decodes), freshness stays in (0, 1], and the loss still drops.
    The bar is deliberately modest: stale replicas cost DCD real accuracy
    under drops (the compare_compression failure sweep quantifies it) — the
    degraded mode's promise is bounded error, not unharmed convergence."""
    n, d = 8, 16
    key = jax.random.key(0)
    A = jax.random.normal(key, (n, 64, d))
    b = jnp.einsum("nmd,d->nm", A, jnp.ones((d,)))
    batch = {"A": A, "b": b}
    drop = make_drop_spec(0.2, salt=1)
    step = jax.jit(make_dist_train_step(_toy_loss, "dcd", sgd(),
                                        QuantWire(bits=8, block=128), 8,
                                        constant(0.1), drop=drop))
    state = init_dist_state("dcd", jnp.zeros((d,)), 8, sgd(), drop=drop)
    assert fresh_key(1, 1) in state.aux and fresh_key(-1, 1) in state.aux

    prev_rep = {s: np.asarray(state.aux[f"rep{s:+d}"]) for s in (1, -1)}
    first = None
    froze = 0
    for t in range(120):
        state, m = step(state, batch)
        first = first or float(m["loss"])
        for s in (1, -1):
            mask = np.asarray(edge_drop_mask(n, s, t, drop))
            rep = np.asarray(state.aux[f"rep{s:+d}"])
            # dropped rows are frozen at the previous replica, bit for bit
            for i in np.flatnonzero(mask == 0.0):
                np.testing.assert_array_equal(rep[i], prev_rep[s][i])
                froze += 1
            prev_rep[s] = rep
            f = np.asarray(state.aux[fresh_key(s, 1)])
            assert (f > 0).all() and (f <= 1).all()
    assert froze > 50                      # drops actually happened
    assert float(m["loss"]) < 0.5 * first


def test_cpsgd_refuses_drop_spec():
    """AllReduce assumes the reliable datacenter fabric: injecting drops into
    cpsgd is a configuration error, not a silent no-op."""
    with pytest.raises(AssertionError):
        make_dist_train_step(_toy_loss, "cpsgd", sgd(), None, 8, constant(0.05),
                             drop=make_drop_spec(0.2))


# ---------------------------------------------------------- 8-device mesh

@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 devices (CI multidevice job forces "
                           "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
@pytest.mark.parametrize("algo", ["dcd", "ecd"])
def test_sharded_mesh_drop_matches_stacked_reference(algo):
    """Acceptance (CI multidevice job): the mesh-sharded fused-decode step at
    drop_rate=0.2 produces the same degraded trajectory as the stacked
    GossipReference (atol 1e-5) — the drop mask rides the shard_map path."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n, d = 8, 256
    plan = make_gossip_plan("ring", n)
    wire = QuantWire(bits=3, block=128)
    drop = make_drop_spec(0.2, salt=4)
    mesh = node_mesh(jax.devices()[:8])
    step_mesh = make_dist_train_step(_toy_loss, algo, sgd(), wire, plan,
                                     constant(0.05), mesh=mesh, drop=drop)
    state_m = init_dist_state(algo, jnp.zeros((d,)), plan, sgd(), drop=drop)
    ref = GossipReference(name=algo, plan=plan, wire=wire, drop=drop)
    ref_step = jax.jit(ref.step_fn())
    ref_state = ref.init(jnp.zeros((d,)))
    sh = jax.tree.map(
        lambda l: NamedSharding(mesh, P(*(("node",) + (None,) * (l.ndim - 1))))
        if l.ndim else NamedSharding(mesh, P()), state_m)
    with mesh:
        jstep_m = jax.jit(step_mesh, in_shardings=(sh, None), out_shardings=(sh, None))
        for t in range(3):
            batch = _toy_batch(jax.random.key(t), n, d=d)
            grads = _grads_for(ref_state.params, batch)
            ref_state = ref_step(ref_state, grads, jnp.asarray(t), jnp.float32(0.05))
            state_m, _ = jstep_m(state_m, batch)
            np.testing.assert_allclose(np.asarray(state_m.params),
                                       np.asarray(ref_state.params), atol=1e-5)
