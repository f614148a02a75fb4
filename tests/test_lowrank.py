"""PowerGossip low-rank wire format (``lowrank:<r>[:warm]``).

The contract under test, layer by layer:

- The factor kernels hold exact word equality against the jnp oracles for
  rank >= 2 (the grid tiles only output rows, the contraction is unsplit, and
  ``_factor_matmul`` is literally shared, so every output element reduces in
  the same order).  Rank 1 is the documented carve-out: XLA FMA-contracts the
  single-multiply "dot" into the axpy epilogue on the oracle path — 1 ulp.
- The codec's fused ``decode_axpy`` produces the same words as the kernel and
  the oracle (three-way invariant), and a per-shard ``(1, m, n)`` slab
  encodes bit-identically to its row of the stacked ``(nodes, m, n)`` leaf —
  the basis of the sharded==stacked differential contract.
- ``wire_bits_per_element`` is measured off the real factor containers via
  eval_shape and comes out exactly ``32·r·(m+n)/(m·n)`` for matrix leaves
  (fp16 fallthrough for 1-D).
- The sharded dcd runtime on a matrix-leaf model matches the stacked
  GossipReference to atol 1e-5 on {ring, torus, full_logn}, cold and warm,
  with bit-identical wire words across calls of the compiled encode (eager
  vs jit holds to 1 ulp — factor payloads are f32 matmul outputs).
- Warm mode's factor aux rides the DistState checkpoint: bit-exact factors
  after restore, resumed runs continue the exact trajectory, and restoring
  into a different rank KeyErrors (the aux key embeds the rank).
- One more power iteration per round (warm, ``full_logn``'s multi-round
  schedule) monotonically shrinks the reconstruction error, ending below the
  cold codec's i.i.d.-per-round floor — the PowerGossip claim.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore, save
from repro.core.algorithms import GossipReference
from repro.distributed.decentralized import init_dist_state, make_dist_train_step
from repro.distributed.gossip import make_gossip_plan
from repro.distributed.wire import LowRankWire, make_wire_format, wire_spec
from repro.kernels.lowrank import lowrank_axpy_2d, lowrank_project_2d
from repro.kernels.ref import (
    lowrank_axpy_2d_ref,
    lowrank_orthonormalize_ref,
    lowrank_project_2d_ref,
)
from repro.optim import sgd
from repro.optim.schedules import constant

N = 8
DM, DN = 16, 128     # matrix-leaf dims; DN on the 128-lane kernel contract


def _mat_loss(params, batch):
    pred = batch["A"] @ params["proj"] + params["bias"]
    loss = 0.5 * jnp.mean((pred - batch["b"]) ** 2)
    return loss, {"xent": loss}


def _mat_batch(key, n, m=8):
    kA, kb = jax.random.split(key)
    return {"A": jax.random.normal(kA, (n, m, DM)),
            "b": jax.random.normal(kb, (n, m, DN))}


def _mat_params():
    return {"bias": jnp.zeros((DN,)), "proj": jnp.zeros((DM, DN))}


def _mat_grads(params, batch):
    def node_loss(p, A, b):
        return 0.5 * jnp.mean((A @ p["proj"] + p["bias"] - b) ** 2)
    return jax.vmap(lambda p, A, b: jax.grad(node_loss)(p, A, b))(
        params, batch["A"], batch["b"])


def _assert_tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------- kernel/oracle parity

def _fused_axpy_ref(p, v, acc, **weights):
    """The decode-axpy oracle evaluated as one jitted program, like the
    interpret-mode kernel body.  Evaluated op by op instead, it rounds the
    product before the add where the fused program contracts the two into one
    FMA, and the words differ by 1 ulp."""
    return jax.jit(lambda p_, v_, a_: lowrank_axpy_2d_ref(p_, v_, a_, **weights))(
        p, v, acc)


@pytest.mark.parametrize("rank", [2, 4])
def test_lowrank_kernel_oracle_word_equality(rank):
    """Project and decode-axpy kernels == jnp oracles, exact words (rank >= 2;
    48 rows exercises the padding path against the picked block size)."""
    rows, n = 48, 256
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    m = jax.random.normal(k1, (rows, n))
    v = jax.random.normal(k2, (n, rank))
    np.testing.assert_array_equal(
        np.asarray(lowrank_project_2d(m, v, interpret=True)),
        np.asarray(lowrank_project_2d_ref(m, v)))

    p = lowrank_orthonormalize_ref(lowrank_project_2d_ref(m, v))
    acc = jax.random.normal(k3, (rows, n))
    got = lowrank_axpy_2d(p, v, acc, weight=0.7, acc_weight=0.9,
                          interpret=True)
    want = _fused_axpy_ref(p, v, acc, weight=0.7, acc_weight=0.9)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lowrank_rank1_carveout_one_ulp():
    """Rank 1 is the documented exception: the single-multiply contraction
    FMA-fuses into the oracle's axpy epilogue — 1 ulp, not word equality."""
    rows, n = 32, 128
    k1, k2, k3 = jax.random.split(jax.random.key(1), 3)
    p = jax.random.normal(k1, (rows, 1))
    v = jax.random.normal(k2, (n, 1))
    acc = jax.random.normal(k3, (rows, n))
    got = lowrank_axpy_2d(p, v, acc, weight=0.7, acc_weight=0.9,
                          interpret=True)
    want = lowrank_axpy_2d_ref(p, v, acc, weight=0.7, acc_weight=0.9)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("rank", [2, 4])
def test_lowrank_three_way_codec_invariant(rank):
    """Codec ``decode_axpy`` (fused receive path) == kernel == oracle, exact
    words at matching batching: the codec folds the node axis and vmaps the
    2-D kernel, and a vmapped dot_general reassociates against the unbatched
    one by 1 ulp — so codec == vmap(kernel) and kernel == oracle are each
    exact, while codec vs the UNBATCHED kernel is the documented 1-ulp."""
    wire = LowRankWire(rank=rank)
    leaf = jax.random.normal(jax.random.key(2), (1, 48, DN))
    payload = wire.encode(leaf, jnp.zeros((), jnp.uint32))
    assert set(payload) == {"p", "v"}
    assert payload["p"].shape == (1, 48, rank)
    assert payload["v"].shape == (1, DN, rank)

    acc = jax.random.normal(jax.random.key(3), (1, 48, DN))
    got = wire.decode_axpy(payload, acc, 0.7, acc_weight=0.9)
    vkern = jax.vmap(lambda p, v, a: lowrank_axpy_2d(
        p, v, a, weight=0.7, acc_weight=0.9, interpret=True))(
        payload["p"], payload["v"], acc)
    kern = lowrank_axpy_2d(payload["p"][0], payload["v"][0], acc[0],
                           weight=0.7, acc_weight=0.9, interpret=True)
    want = _fused_axpy_ref(payload["p"][0], payload["v"][0], acc[0],
                           weight=0.7, acc_weight=0.9)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(vkern))
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(want))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_lowrank_slab_stacked_word_equality():
    """A per-shard ``(1, m, n)`` slab encodes bit-identically to its row of
    the stacked ``(nodes, m, n)`` leaf: the cold factor init depends only on
    (n, seed) — never the node axis — so sharded and stacked runs put the
    same words on the wire."""
    wire = LowRankWire(rank=2)
    M = jax.random.normal(jax.random.key(4), (N, 48, DN))
    seed = jnp.asarray(0xABCD, jnp.uint32)
    full = wire.encode(M, seed)
    for i in (0, 3, 7):
        slab = wire.encode(M[i:i + 1], seed)
        np.testing.assert_array_equal(np.asarray(full["p"][i]),
                                      np.asarray(slab["p"][0]))
        np.testing.assert_array_equal(np.asarray(full["v"][i]),
                                      np.asarray(slab["v"][0]))


# ------------------------------------------------------------- wire accounting

@pytest.mark.parametrize("rank", [1, 2, 4])
@pytest.mark.parametrize("m,n", [(64, 128), (32, 256), (128, 128)])
def test_lowrank_measured_bits_match_budget(rank, m, n):
    """Acceptance: bits/element measured off the real factor containers
    (eval_shape — nothing executes) == 32·r·(m+n)/(m·n), exactly."""
    wire = LowRankWire(rank=rank)
    assert abs(wire.wire_bits_per_element((1, m, n))
               - 32.0 * rank * (m + n) / (m * n)) < 1e-9
    # the 2-D form is the same matrix leaf un-stacked
    assert abs(wire.wire_bits_per_element((m, n))
               - wire.wire_bits_per_element((1, m, n))) < 1e-12
    # 1-D leaves fall through to the fp16 container
    assert abs(wire.wire_bits_per_element((4096,)) - 16.0) < 1e-9


def test_lowrank_spec_roundtrip():
    assert make_wire_format("lowrank:2") == LowRankWire(rank=2)
    assert make_wire_format("lowrank:4:warm") == LowRankWire(rank=4, warm=True)
    for w in (LowRankWire(rank=2), LowRankWire(rank=3, warm=True)):
        assert make_wire_format(wire_spec(w)) == w
    assert LowRankWire(rank=2, warm=True).aux_name == "wire_lowrank:2"
    assert not LowRankWire(rank=2).stateful
    assert LowRankWire(rank=2, warm=True).stateful


# ------------------------------------------------------- differential tier

_LR_CASES = [(w, t)
             for w in ("lowrank:2", "lowrank:2:warm")
             for t in ("ring", "torus", "full_logn")]


@pytest.mark.parametrize("spec,topo", _LR_CASES,
                         ids=[f"{w}-{t}" for w, t in _LR_CASES])
def test_lowrank_dist_matches_reference(spec, topo):
    """Acceptance: sharded dcd on a matrix-leaf model with the lowrank wire
    (cold AND warm) == stacked GossipReference (atol 1e-5) on {ring, torus,
    full_logn}, with bit-identical wire words across calls of the compiled
    encode.  (Eager vs jit agrees to 1 ulp, not bit-exactly: factor payloads
    are f32 matmul outputs, and XLA may reassociate a dot differently across
    compilations — unlike the integer code streams of quant/sign/sparse.)"""
    wire = make_wire_format(spec)
    plan = make_gossip_plan(topo, N)

    dist_step = jax.jit(make_dist_train_step(
        _mat_loss, "dcd", sgd(), wire, plan, constant(0.05)))
    dist_state = init_dist_state("dcd", _mat_params(), plan, sgd(), wire=wire)

    ref = GossipReference(name="dcd", plan=plan, wire=wire)
    ref_step = jax.jit(ref.step_fn())
    ref_state = ref.init(_mat_params())

    for t in range(3):
        batch = _mat_batch(jax.random.key(t), N)
        grads = _mat_grads(ref_state.params, batch)
        ref_state = ref_step(ref_state, grads, jnp.asarray(t),
                             jnp.float32(0.05))
        dist_state, _ = dist_step(dist_state, batch)
        for la, lb in zip(jax.tree.leaves(dist_state.params),
                          jax.tree.leaves(ref_state.params)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       atol=1e-5)

    # wire words bit for bit: eager vs jit on the same tree/seeds/aux
    step_c = jnp.asarray(2, jnp.int32)
    if wire.stateful:
        aux = dist_state.aux[wire.aux_name]
        enc = lambda tr, st: wire.encode_tree_stateful(tr, st, 2, aux)[1]
    else:
        enc = lambda tr, st: wire.encode_tree(tr, st, 2)[1]
    enc_j = jax.jit(enc)
    p1 = enc_j(dist_state.params, step_c)
    p2 = enc_j(dist_state.params, step_c)
    pe = enc(dist_state.params, step_c)
    mat_1 = next(p for p in p1 if "p" in p)
    mat_2 = next(p for p in p2 if "p" in p)
    mat_e = next(p for p in pe if "p" in p)
    for k in ("p", "v"):
        np.testing.assert_array_equal(np.asarray(mat_1[k]),
                                      np.asarray(mat_2[k]))
        np.testing.assert_allclose(np.asarray(mat_1[k]),
                                   np.asarray(mat_e[k]),
                                   rtol=2e-6, atol=2e-7)


# ------------------------------------------------- warm factor aux lifecycle

def test_lowrank_warm_checkpoint_roundtrip_and_resume(tmp_path):
    """Acceptance: the warm-start factor aux (``wire_lowrank:2``) rides the
    DistState checkpoint bit-exactly — factors restore identical to what was
    saved — and a resumed run continues the exact trajectory."""
    wire = make_wire_format("lowrank:2:warm")
    plan = make_gossip_plan("ring", N)
    step = jax.jit(make_dist_train_step(
        _mat_loss, "dcd", sgd(), wire, plan, constant(0.05)))
    state = init_dist_state("dcd", _mat_params(), plan, sgd(), wire=wire)
    init_factors = jax.tree.map(lambda x: x, state.aux["wire_lowrank:2"])
    for t in range(3):
        state, _ = step(state, _mat_batch(jax.random.key(t), N))
    assert "wire_lowrank:2" in state.aux
    # the factors actually advanced (power iteration ran, aux is live state)
    moved = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))),
        state.aux["wire_lowrank:2"], init_factors)
    assert max(jax.tree.leaves(moved)) > 0.0

    ckpt = str(tmp_path / "ckpt")
    save(ckpt, 3, state)
    like = init_dist_state("dcd", _mat_params(), plan, sgd(), wire=wire)
    restored, _ = restore(ckpt, like, 3)
    _assert_tree_equal(state, restored)

    batch = _mat_batch(jax.random.key(99), N)
    cont, _ = step(state, batch)
    cont_r, _ = step(restored, batch)
    _assert_tree_equal(cont, cont_r)


def test_lowrank_mismatched_rank_restore_keyerror(tmp_path):
    """Acceptance: restoring warm factor aux into a DIFFERENT rank fails
    loudly — the aux key embeds the rank (``wire_lowrank:<r>``), so the
    structure-driven restore KeyErrors instead of silently splicing rank-2
    factors into a rank-4 codec."""
    plan = make_gossip_plan("ring", N)
    state = init_dist_state("dcd", _mat_params(), plan, sgd(),
                            wire=make_wire_format("lowrank:2:warm"))
    ckpt = str(tmp_path / "ckpt")
    save(ckpt, 1, state)
    like4 = init_dist_state("dcd", _mat_params(), plan, sgd(),
                            wire=make_wire_format("lowrank:4:warm"))
    with pytest.raises(KeyError, match="wire_lowrank"):
        restore(ckpt, like4, 1)


# ------------------------------------------------- multi-round convergence

def test_lowrank_warm_error_decreases_with_rounds():
    """The PowerGossip claim on ``full_logn``'s multi-round schedule: each of
    the period's rounds is one more power iteration on the carried factors, so
    the warm reconstruction error is (near-)monotone decreasing across rounds
    and ends strictly below the cold codec — whose error is i.i.d. per round
    because it re-seeds V0 from the (step, salt, leaf) counter every time."""
    sched = make_gossip_plan("full_logn", N)
    kA, kB, kN = jax.random.split(jax.random.key(7), 3)
    # decaying spectrum (effective rank ~4 + noise floor): rank-2 warm factors
    # converge to the top-2 subspace within a couple of schedule periods
    M = (jax.random.normal(kA, (1, 64, 4)) @ jax.random.normal(kB, (1, 4, DN))
         + 0.01 * jax.random.normal(kN, (1, 64, DN)))
    tree = {"proj": M}
    warm = make_wire_format("lowrank:2:warm")
    cold = make_wire_format("lowrank:2")
    aux = warm.init_aux(tree)
    norm = float(jnp.linalg.norm(M))

    warm_errs, cold_errs = [], []
    for t in range(3):
        for rnd in range(sched.period):
            enc_step = jnp.asarray(t * sched.period + rnd, jnp.int32)
            _, pw, aux = warm.encode_tree_stateful(tree, enc_step, 2, aux)
            _, pc = cold.encode_tree(tree, enc_step, 2)
            warm_errs.append(
                float(jnp.linalg.norm(warm.decode(pw[0], M[0]) - M)) / norm)
            cold_errs.append(
                float(jnp.linalg.norm(cold.decode(pc[0], M[0]) - M)) / norm)

    # near-monotone decrease round over round (float noise tolerance), a
    # strict drop overall, and a final error below every cold round
    assert all(b <= a + 1e-4 for a, b in zip(warm_errs, warm_errs[1:])), \
        warm_errs
    assert warm_errs[-1] < warm_errs[0] - 1e-3, warm_errs
    assert warm_errs[-1] < min(cold_errs), (warm_errs[-1], min(cold_errs))
