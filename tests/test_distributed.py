"""Distributed runtime tests.

The sharded step must agree with the stacked-simulator semantics; the gossip
invariants (replicas == true neighbor models) must hold; and the dry-run must
lower+compile on a small fake-device mesh.  Multi-device tests run in a
subprocess so XLA_FLAGS can force a fake device count without polluting the
main test process (which must keep seeing 1 device).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.testbed import make_problem
from repro.distributed.decentralized import init_dist_state, make_dist_train_step
from repro.distributed.gossip import make_gossip_plan
from repro.distributed.wire import QuantWire, SparseWire
from repro.launch.mesh import node_mesh
from repro.optim import sgd
from repro.optim.schedules import constant

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"subprocess failed:\n{out.stdout}\n{out.stderr}"
    return out.stdout


# ------------------------------------------------------------ single process

def _toy_loss(params, batch):
    """Least-squares on a per-node batch; params is a flat vector."""
    pred = batch["A"] @ params
    loss = 0.5 * jnp.mean((pred - batch["b"]) ** 2)
    return loss, {"xent": loss}


def _toy_batch(key, n, m=16, d=8):
    kA, kb = jax.random.split(key)
    return {"A": jax.random.normal(kA, (n, m, d)),
            "b": jax.random.normal(kb, (n, m))}


def test_dist_dcd_replica_invariant():
    """After every DCD step, rep_l == roll(X, +1) and rep_r == roll(X, -1)."""
    n, d = 8, 8
    step = make_dist_train_step(_toy_loss, "dcd", sgd(), QuantWire(bits=8, block=128),
                                n, constant(0.05))
    state = init_dist_state("dcd", jnp.zeros((d,)), n, sgd())
    for t in range(5):
        state, _ = jax.jit(step)(state, _toy_batch(jax.random.key(t), n))
        np.testing.assert_allclose(np.asarray(state.aux["rep+1"]),
                                   np.roll(np.asarray(state.params), 1, axis=0),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(state.aux["rep-1"]),
                                   np.roll(np.asarray(state.params), -1, axis=0),
                                   rtol=1e-6)


def test_dist_dpsgd_matches_core_simulator():
    """Sharded-form dpsgd (identity wire) == core stacked simulator with ring W."""
    from repro.core import make_algorithm

    n, d = 8, 8
    algo = make_algorithm("dpsgd", n, "ring")
    core_step = algo.step_fn()
    core_state = algo.init(jnp.zeros((d,)))

    dist_step = make_dist_train_step(_toy_loss, "dpsgd", sgd(), None, n, constant(0.05))
    dist_state = init_dist_state("dpsgd", jnp.zeros((d,)), n, sgd())

    for t in range(5):
        batch = _toy_batch(jax.random.key(t), n)
        grads = jax.vmap(lambda p, A, b: jax.grad(
            lambda q: 0.5 * jnp.mean((A @ q - b) ** 2))(p))(
            core_state.params, batch["A"], batch["b"])
        core_state = core_step(core_state, grads, jax.random.key(100 + t),
                               jnp.float32(0.05))
        dist_state, _ = jax.jit(dist_step)(dist_state, batch)
        np.testing.assert_allclose(np.asarray(dist_state.params),
                                   np.asarray(core_state.params), atol=1e-5)


def test_dist_cpsgd_keeps_replicas_identical():
    n, d = 4, 8
    step = make_dist_train_step(_toy_loss, "cpsgd", sgd(momentum=0.9), None, n,
                                constant(0.05))
    state = init_dist_state("cpsgd", jnp.ones((d,)), n, sgd(momentum=0.9))
    for t in range(3):
        state, _ = jax.jit(step)(state, _toy_batch(jax.random.key(t), n))
    X = np.asarray(state.params)
    assert np.allclose(X, X[0])


def test_dist_dcd_converges_on_quadratic():
    """Full sharded DCD (8-bit wire codec) drives a least-squares loss down."""
    n, d = 8, 16
    key = jax.random.key(0)
    A = jax.random.normal(key, (n, 64, d))
    x_true = jnp.ones((d,))
    b = jnp.einsum("nmd,d->nm", A, x_true)
    batch = {"A": A, "b": b}
    step = make_dist_train_step(_toy_loss, "dcd", sgd(), QuantWire(bits=8, block=128),
                                n, constant(0.1))
    state = init_dist_state("dcd", jnp.zeros((d,)), n, sgd())
    jstep = jax.jit(step)
    first = None
    for t in range(120):   # loss ratio ~2e-8 by then; 0.01 leaves huge margin
        state, m = jstep(state, batch)
        first = first or float(m["loss"])
    assert float(m["loss"]) < 0.01 * first
    xbar = np.asarray(jax.tree.map(lambda l: jnp.mean(l, 0), state.params))
    np.testing.assert_allclose(xbar, np.asarray(x_true), atol=0.05)


def test_wire_codec_roundtrip_and_format():
    codec = QuantWire(bits=8, block=128)
    tree = {"w": jax.random.normal(jax.random.key(0), (4, 33, 7)),
            "b": jax.random.normal(jax.random.key(1), (4, 5))}
    tdef, payload = codec.encode_tree(tree, jnp.asarray(3, jnp.int32), salt=1)
    for p in payload:
        assert p["codes"].dtype == jnp.int8
        assert p["codes"].shape[0] == 4          # node axis preserved
    out = codec.decode_tree(tdef, payload, tree)
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)))
    assert err < 0.1   # within one 8-bit bin of the per-block scale


# ------------------------------------------------------------ multi-device

@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 devices (CI multidevice job forces "
                           "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
@pytest.mark.parametrize("topo_spec", ["ring", "full_logn", "exp"])
@pytest.mark.parametrize("codec", [QuantWire(bits=3, block=128),
                                   SparseWire(p=0.25, block=128)],
                         ids=["quant3", "sparse25"])
@pytest.mark.parametrize("algo", ["dcd", "ecd"])
def test_sharded_gossip_decode_matches_inline(algo, codec, topo_spec):
    """Numeric check of the shard_map decode path on a real (forced-host)
    8-device node mesh: the mesh-wrapped fused decode produces the same
    trajectory as the inline single-process fused decode — for the flat ring
    plan AND the multi-round / time-varying schedules (full_logn iterates its
    rounds inside the sharded step; exp switches rounds per step).  This is
    the path the subprocess tests only *lower*; under the CI multidevice job
    it runs."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n, d = 8, 256
    plan = make_gossip_plan(topo_spec, n)
    mesh = node_mesh(jax.devices()[:8])
    step_mesh = make_dist_train_step(_toy_loss, algo, sgd(), codec, plan,
                                     constant(0.05), mesh=mesh)
    step_inline = jax.jit(make_dist_train_step(_toy_loss, algo, sgd(), codec,
                                               plan, constant(0.05)))
    state_m = init_dist_state(algo, jnp.zeros((d,)), plan, sgd())
    state_i = init_dist_state(algo, jnp.zeros((d,)), plan, sgd())
    sh = jax.tree.map(
        lambda l: NamedSharding(mesh, P(*(("node",) + (None,) * (l.ndim - 1))))
        if l.ndim else NamedSharding(mesh, P()), state_m)
    with mesh:
        jstep_m = jax.jit(step_mesh, in_shardings=(sh, None), out_shardings=(sh, None))
        for t in range(3):
            batch = _toy_batch(jax.random.key(t), n, d=d)
            state_m, mm = jstep_m(state_m, batch)
            state_i, mi = step_inline(state_i, batch)
            np.testing.assert_allclose(np.asarray(state_m.params),
                                       np.asarray(state_i.params), atol=1e-5)
    assert float(mm["loss"]) == pytest.approx(float(mi["loss"]), rel=1e-5)


@pytest.mark.slow
def test_analyzer_sweep_reproduces_hlo_guarantees():
    """The jaxpr/HLO analyzer (repro.analysis.jaxpr_checks) is the single
    source of truth for every guarantee the legacy subprocess-HLO asserts
    made: s8 codes ride the permute at quant:8, packed u32 words at every
    sub-byte width and for the sparse containers (chain and torus2d plans
    included), the dense f32 stacked leaf never rides a permute for a
    compressing wire, the fused kernels decode under shard_map, and the
    fused-kernel call count equals decode_sites x kernels/site (whose
    replica share is sched.replica_payloads) across the acceptance block
    {ring, torus, full_logn} x {quant:4, sign, adaptive}."""
    out = run_subprocess("""
        import itertools
        from repro.analysis import jaxpr_checks as jc

        reports = jc.run_sweep(require_hlo=True)
        bad = [r.describe() + ": " + "; ".join(r.violations)
               for r in reports if not r.ok]
        assert not bad, bad
        by = {(r.algo, r.topology, r.wire, r.drop): r for r in reports}

        # legacy: int8 codes ride the collective-permute at quant:8
        assert "s8" in by[("dcd", "ring", "quant:8", 0.0)].permute_dtypes
        # legacy: packed u32 words at 4/3-bit and for the sparse idx
        # containers, whatever the plan graph
        for case in (("dcd", "ring", "quant:4", 0.0),
                     ("dcd", "ring", "quant:3", 0.0),
                     ("dcd", "chain", "quant:4", 0.0),
                     ("dcd", "torus2d", "sparse:0.25", 0.0)):
            assert "u32" in by[case].permute_dtypes, case

        # acceptance block: exact fused-kernel call counts + wire words on
        # the permute for every {topology} x {wire} cell
        for topo, wire in itertools.product(
                ("ring", "torus", "full_logn"),
                ("quant:4", "sign", jc._ADAPTIVE_SPEC)):
            r = by[("dcd", topo, wire, 0.0)]
            assert r.kernel_calls == r.expected_kernels > 0, r.describe()
            assert "u32" in r.permute_dtypes, r.describe()
        # the adaptive small leaf rides fp16 halves on the same permute set
        assert "f16" in by[("dcd", "ring", jc._ADAPTIVE_SPEC, 0.0)].permute_dtypes
        print("ANALYZER_SWEEP_OK", len(reports))
    """)
    assert "ANALYZER_SWEEP_OK" in out


@pytest.mark.slow
def test_dryrun_smoke_tiny_mesh():
    """dryrun machinery end-to-end on an 8-device mesh with a reduced config."""
    out = run_subprocess("""
        import jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.launch.mesh import derive_train_mesh
        from repro.launch.specs import InputShape, train_input_specs, params_specs
        from repro.distributed.decentralized import init_dist_state, make_dist_train_step
        from repro.distributed.wire import QuantWire
        from repro.distributed.sharding import batch_shardings, params_shardings
        from repro.launch import analysis
        from repro.optim import sgd
        from repro.optim.schedules import constant
        import numpy as np

        cfg = get_config("granite-3-2b").reduced()
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2), ("node", "fsdp", "model"))
        n = 2
        from repro.distributed.wire import QuantWire
        from repro.models.api import build_model
        model = build_model(cfg)
        opt = sgd()
        step = make_dist_train_step(lambda p, b: model.loss(p, b, remat=True),
                                    "dcd", opt, QuantWire(bits=8, block=128), n,
                                    constant(1e-2))
        p_sds = params_specs(cfg)
        state_sds = jax.eval_shape(lambda ps: init_dist_state("dcd", ps, n, opt), p_sds)
        shape = InputShape("tiny", "train", 64, 8)
        batch_sds = train_input_specs(cfg, shape, n)
        from repro.launch.dryrun import _state_shardings
        ssh = _state_shardings(state_sds, mesh, None)
        bsh = batch_shardings(batch_sds, mesh, node_axis=True)
        with mesh:
            compiled = jax.jit(step, in_shardings=(ssh, bsh),
                               out_shardings=(ssh, None)).lower(state_sds, batch_sds).compile()
        roof = analysis.analyze(compiled, model_flops_global=1e9, n_chips=8,
                                jaxpr_flops_global=analysis.count_fn_flops(
                                    step, state_sds, batch_sds))
        assert roof.flops_per_chip > 0
        assert roof.collective_bytes_per_chip > 0
        print("OK", roof.bottleneck)
    """)
    assert "OK" in out


def test_analysis_trip_count_parsing():
    """jaxpr flop counter multiplies scan bodies by length."""
    from repro.launch.analysis import count_fn_flops

    L, d = 7, 32
    W = jnp.zeros((L, d, d))
    x = jnp.zeros((d, d))

    def f(w, x):
        def body(h, wi):
            return h @ wi, None
        h, _ = jax.lax.scan(body, x, w)
        return h

    flops = count_fn_flops(f, W, x)
    assert flops == pytest.approx(L * 2 * d**3)


def test_analysis_shape_bytes():
    from repro.launch.analysis import _shape_bytes

    assert _shape_bytes("f32[2,3]") == 24
    assert _shape_bytes("s8[1,128,1024]{2,1,0}") == 131072
    assert _shape_bytes("(f32[4], bf16[8])") == 32


def test_wire_codec_int4_packing_halves_bytes():
    """Packed 4-bit wire: 8 codes per uint32 word, roundtrip within one bin."""
    c8 = QuantWire(bits=8, block=128)
    c4 = QuantWire(bits=4, block=128)
    assert not c8.packed and c4.packed
    tree = {"w": jax.random.normal(jax.random.key(0), (2, 64, 256))}
    _, p8 = c8.encode_tree(tree, jnp.asarray(1, jnp.int32), salt=0)
    tdef, p4 = c4.encode_tree(tree, jnp.asarray(1, jnp.int32), salt=0)
    assert p4[0]["codes"].dtype == jnp.uint32
    assert p4[0]["codes"].nbytes * 2 == p8[0]["codes"].nbytes
    out = c4.decode_tree(tdef, p4, tree)
    scale = float(jnp.max(jnp.abs(tree["w"])))
    assert float(jnp.max(jnp.abs(out["w"] - tree["w"]))) <= scale / 7 * 1.05
    assert c4.wire_bits_per_element() < 0.6 * c8.wire_bits_per_element()


def test_wire_codec_packed_measured_bits_per_element():
    """Acceptance: bits=4, block=1024 — the stacked payload the ring step rolls
    ships <= 4.1 bits/element, measured from the payload containers."""
    codec = QuantWire(bits=4, block=1024)
    tree = {"w": jnp.zeros((8, 64, 4096)), "b": jnp.zeros((8, 2048))}
    n_elem = sum(l.size for l in jax.tree.leaves(tree))
    tdef, payload = codec.encode_tree(tree, jnp.asarray(0, jnp.int32), salt=0)
    measured = 8.0 * sum(p["codes"].nbytes + p["scale"].nbytes for p in payload) / n_elem
    assert measured <= 4.1
    # the shape-only accounting used by the dryrun must agree exactly
    assert codec.wire_nbytes(tree) == \
        sum(p["codes"].nbytes + p["scale"].nbytes for p in payload)
    assert codec.wire_bits_per_element() == pytest.approx(4.03125)
    # 2-bit packs 16 codes/word
    c2 = QuantWire(bits=2, block=1024)
    assert 8.0 * c2.wire_nbytes(tree) / n_elem <= 2.1


@pytest.mark.parametrize("algo", ["dcd", "ecd"])
def test_packed_codec_steps_match_unpacked(algo):
    """Packing is lossless: the packed 4-bit codec produces bit-identical codes
    to the int8-container codec (same PCG seeds), so DCD/ECD trajectories agree
    to float rounding (XLA fuses the two programs differently, so bit-equality
    of the *trajectory* is not guaranteed — the codes are, asserted first)."""
    n, d = 8, 8
    cp, cu = QuantWire(bits=4, block=128), QuantWire(bits=4, block=128, pack=False)
    tree = {"w": jax.random.normal(jax.random.key(0), (n, 40))}
    tdp, pp = cp.encode_tree(tree, jnp.asarray(2, jnp.int32), salt=3)
    tdu, pu = cu.encode_tree(tree, jnp.asarray(2, jnp.int32), salt=3)
    from repro.kernels.ref import unpack_codes
    np.testing.assert_array_equal(
        np.asarray(unpack_codes(pp[0]["codes"], bits=4)), np.asarray(pu[0]["codes"]))
    np.testing.assert_array_equal(np.asarray(cp.decode_tree(tdp, pp, tree)["w"]),
                                  np.asarray(cu.decode_tree(tdu, pu, tree)["w"]))

    sp = make_dist_train_step(_toy_loss, algo, sgd(), cp, n, constant(0.05))
    su = make_dist_train_step(_toy_loss, algo, sgd(), cu, n, constant(0.05))
    stp = init_dist_state(algo, jnp.zeros((d,)), n, sgd())
    stu = init_dist_state(algo, jnp.zeros((d,)), n, sgd())
    jp, ju = jax.jit(sp), jax.jit(su)
    for t in range(4):
        batch = _toy_batch(jax.random.key(t), n)
        stp, mp = jp(stp, batch)
        stu, mu = ju(stu, batch)
        np.testing.assert_allclose(np.asarray(stp.params), np.asarray(stu.params),
                                   rtol=1e-6, atol=1e-8)
    assert float(mp["loss"]) == pytest.approx(float(mu["loss"]), rel=1e-6)


def test_dist_dcd_converges_packed_4bit():
    """Full sharded DCD with the packed 4-bit wire codec still converges."""
    n, d = 8, 16
    key = jax.random.key(0)
    A = jax.random.normal(key, (n, 64, d))
    x_true = jnp.ones((d,))
    b = jnp.einsum("nmd,d->nm", A, x_true)
    batch = {"A": A, "b": b}
    step = make_dist_train_step(_toy_loss, "dcd", sgd(), QuantWire(bits=4, block=128),
                                n, constant(0.1))
    state = init_dist_state("dcd", jnp.zeros((d,)), n, sgd())
    jstep = jax.jit(step)
    first = None
    for t in range(120):
        state, m = jstep(state, batch)
        first = first or float(m["loss"])
    assert float(m["loss"]) < 0.05 * first
    xbar = np.asarray(jax.tree.map(lambda l: jnp.mean(l, 0), state.params))
    np.testing.assert_allclose(xbar, np.asarray(x_true), atol=0.1)


# ------------------------------------------------- differential test tier
#
# The sharded DCD/ECD runtime must agree *numerically* with the stacked
# semantic reference in core/algorithms.py.  The compressor view of the SAME
# wire object (compressor_for) feeds the reference steps the same
# deterministic PCG compression (seeded by step/salt/leaf), so the two runs
# produce bit-identical payloads and the trajectories match to float rounding
# — for every wire width (odd 3/5-bit stream packing included), for the
# sparse value+index format, and for every circulant-representable topology
# plan ({chain, torus} x {quant 4-bit, sparse p=0.25} below).

@pytest.mark.parametrize("algo", ["dcd", "ecd"])
@pytest.mark.parametrize("bits", [2, 3, 4, 5, 8])
def test_dist_step_matches_stacked_reference(algo, bits):
    from repro.core import make_algorithm
    from repro.core.compression import compressor_for

    n, d = 8, 256   # d >= 128 so the packed widths exercise the fused kernel
    codec = QuantWire(bits=bits, block=128)
    comp = compressor_for(codec, salt=2 if algo == "dcd" else 3)
    core = make_algorithm(algo, n, "ring", compressor=comp)
    core_step = jax.jit(core.step_fn())   # jit: the eager PCG encode dominates
    # align the reference's step counter with the runtime's 0-based counter
    # (ECD's extrapolation weights are functions of s = step + 1)
    core_state = core.init(jnp.zeros((d,)))._replace(step=jnp.asarray(0, jnp.int32))

    dist_step = jax.jit(make_dist_train_step(
        _toy_loss, algo, sgd(), codec, n, constant(0.05)))
    dist_state = init_dist_state(algo, jnp.zeros((d,)), n, sgd())

    for t in range(4):
        batch = _toy_batch(jax.random.key(t), n, d=d)
        grads = jax.vmap(lambda p, A, b: jax.grad(
            lambda q: 0.5 * jnp.mean((A @ q - b) ** 2))(p))(
            core_state.params, batch["A"], batch["b"])
        # the view reads the key slot as the step counter for seed derivation
        core_state = core_step(core_state, grads, jnp.asarray(t), jnp.float32(0.05))
        dist_state, _ = dist_step(dist_state, batch)
        np.testing.assert_allclose(np.asarray(dist_state.params),
                                   np.asarray(core_state.params), atol=1e-5)


@pytest.mark.parametrize("algo", ["dcd", "ecd"])
@pytest.mark.parametrize("p", [0.1, 0.25, 0.5])
def test_dist_step_matches_stacked_reference_sparse(algo, p):
    """Acceptance: the sharded DCD/ECD step with the sparse value+index codec
    matches the stacked reference (atol 1e-5) for p in {0.1, 0.25, 0.5}, with
    bit-identical packed index words between the two runs (asserted on the
    encoded payload the reference derives from the same step/salt seeds)."""
    from repro.core import make_algorithm
    from repro.core.compression import compressor_for

    n, d = 8, 256   # d >= 128: blocks meet the fused kernel's lane contract
    salt = 2 if algo == "dcd" else 3
    codec = SparseWire(p=p, block=128, mode="randk")
    comp = compressor_for(codec, salt=salt)
    core = make_algorithm(algo, n, "ring", compressor=comp)
    core_step = jax.jit(core.step_fn())
    core_state = core.init(jnp.zeros((d,)))._replace(step=jnp.asarray(0, jnp.int32))

    dist_step = jax.jit(make_dist_train_step(
        _toy_loss, algo, sgd(), codec, n, constant(0.05)))
    dist_state = init_dist_state(algo, jnp.zeros((d,)), n, sgd())

    for t in range(4):
        batch = _toy_batch(jax.random.key(t), n, d=d)
        grads = jax.vmap(lambda p_, A, b: jax.grad(
            lambda q: 0.5 * jnp.mean((A @ q - b) ** 2))(p_))(
            core_state.params, batch["A"], batch["b"])
        core_state = core_step(core_state, grads, jnp.asarray(t), jnp.float32(0.05))
        dist_state, _ = dist_step(dist_state, batch)
        np.testing.assert_allclose(np.asarray(dist_state.params),
                                   np.asarray(core_state.params), atol=1e-5)
        # indices bit-for-bit: both runs encode the same tree with the same
        # (step, salt, leaf) seeds — jit and eager must agree word for word
        _, pe = codec.encode_tree(dist_state.params, jnp.asarray(t, jnp.int32), salt=salt)
        pj = jax.jit(lambda tr, s: codec.encode_tree(tr, s, salt=salt)[1])(
            dist_state.params, jnp.asarray(t, jnp.int32))
        np.testing.assert_array_equal(np.asarray(pe[0]["idx"]),
                                      np.asarray(pj[0]["idx"]))


@pytest.mark.parametrize("mode", ["randk", "topk"])
def test_dist_step_uses_fused_sparse_kernel(mode):
    """The sparse sharded step decodes through the fused sparse_scatter_axpy
    Pallas kernel (one VMEM pass), asserted via the analyzer's jaxpr kernel
    accounting; leaves below the 128-lane kernel contract stay on the jnp
    reference path (expected count 0 — the analyzer measures eligibility by
    tracing the wire itself)."""
    from repro.analysis.jaxpr_checks import expected_kernel_calls, kernel_call_counts

    n, d = 8, 256
    wire = SparseWire(p=0.25, block=128, mode=mode)
    plan = make_gossip_plan("ring", n)
    step = make_dist_train_step(_toy_loss, "dcd", sgd(), wire, plan, constant(0.05))
    state = init_dist_state("dcd", jnp.zeros((d,)), n, sgd())
    batch = _toy_batch(jax.random.key(0), n, d=d)
    counts = kernel_call_counts(str(jax.make_jaxpr(step)(state, batch)))
    # one fused call per decode site: self + 2 neighbors on the ring
    assert counts["sparse_scatter_axpy"] == \
        expected_kernel_calls("dcd", plan, wire, state.params) == 3

    small = init_dist_state("dcd", jnp.zeros((8,)), n, sgd())
    counts_s = kernel_call_counts(str(jax.make_jaxpr(step)(
        small, _toy_batch(jax.random.key(0), n, d=8))))
    assert counts_s["sparse_scatter_axpy"] == \
        expected_kernel_calls("dcd", plan, wire, small.params) == 0


def test_dist_dcd_converges_sparse_topk():
    """Full sharded DCD with the top-k sparse wire codec still converges."""
    n, d = 8, 16
    key = jax.random.key(0)
    A = jax.random.normal(key, (n, 64, d))
    x_true = jnp.ones((d,))
    b = jnp.einsum("nmd,d->nm", A, x_true)
    batch = {"A": A, "b": b}
    step = make_dist_train_step(_toy_loss, "dcd", sgd(),
                                SparseWire(p=0.5, block=128, mode="topk"),
                                n, constant(0.1))
    state = init_dist_state("dcd", jnp.zeros((d,)), n, sgd())
    jstep = jax.jit(step)
    first = None
    for t in range(120):
        state, m = jstep(state, batch)
        first = first or float(m["loss"])
    assert float(m["loss"]) < 0.05 * first
    xbar = np.asarray(jax.tree.map(lambda l: jnp.mean(l, 0), state.params))
    np.testing.assert_allclose(xbar, np.asarray(x_true), atol=0.1)


@pytest.mark.parametrize("algo", ["dcd", "ecd"])
def test_dist_step_uses_fused_axpy_kernel(algo):
    """The packed sharded step decodes through the fused unpack_dequant_axpy
    Pallas kernel (one VMEM pass), asserted via the analyzer's jaxpr kernel
    accounting; the unpacked 8-bit codec keeps the jnp reference path (no
    packed words to unpack), and leaves below the 128-lane kernel contract
    also stay on the jnp path — both show up as expected count 0 because the
    analyzer traces the wire itself rather than re-modeling eligibility."""
    from repro.analysis.jaxpr_checks import expected_kernel_calls, kernel_call_counts

    n, d = 8, 256   # d >= 128: the leaf's block meets the kernel lane contract
    wire = QuantWire(bits=3, block=128)
    plan = make_gossip_plan("ring", n)
    step = make_dist_train_step(_toy_loss, algo, sgd(), wire, plan, constant(0.05))
    state = init_dist_state(algo, jnp.zeros((d,)), n, sgd())
    batch = _toy_batch(jax.random.key(0), n, d=d)
    counts = kernel_call_counts(str(jax.make_jaxpr(step)(state, batch)))
    # one fused call per decode site: self + one per neighbor shift
    assert counts["unpack_dequant_axpy"] == \
        expected_kernel_calls(algo, plan, wire, state.params) == 3

    wire8 = QuantWire(bits=8, block=128)
    step8 = make_dist_train_step(_toy_loss, algo, sgd(), wire8, plan, constant(0.05))
    counts8 = kernel_call_counts(str(jax.make_jaxpr(step8)(state, batch)))
    assert counts8["unpack_dequant_axpy"] == \
        expected_kernel_calls(algo, plan, wire8, state.params) == 0

    # a tiny leaf (block 32 < 128 lanes) must NOT reach the kernel
    small = init_dist_state(algo, jnp.zeros((8,)), n, sgd())
    counts_s = kernel_call_counts(str(jax.make_jaxpr(step)(
        small, _toy_batch(jax.random.key(0), n, d=8))))
    assert counts_s["unpack_dequant_axpy"] == \
        expected_kernel_calls(algo, plan, wire, small.params) == 0


@pytest.mark.parametrize("algo", ["dcd", "ecd"])
def test_decode_stats_counts_traced_leaves_by_path(algo):
    """``decode_stats`` counts each leaf a traced step decodes, at every
    decode site: the 2048-wide leaf through the fused kernel over its row
    view (``rows``; the interpret kernel on the CPU), the 8-wide one, below
    the 128-lane gate, on the jnp path."""
    from repro.analysis.jaxpr_checks import decode_sites
    from repro.distributed.wire import decode_stats

    n, d = 4, 2048
    plan = make_gossip_plan("ring", n)

    def loss(p, batch):
        return _toy_loss(p["w"], batch)[0] + jnp.mean(p["b"]), {}

    step = make_dist_train_step(loss, algo, sgd(), QuantWire(bits=4, block=1024),
                                plan, constant(0.05))
    state = init_dist_state(algo, {"w": jnp.zeros((d,)), "b": jnp.zeros((8,))},
                            plan, sgd())
    before = decode_stats()
    jax.make_jaxpr(step)(state, _toy_batch(jax.random.key(0), n, d=d))
    after = decode_stats()
    sites = decode_sites(algo, plan)
    assert sites >= 2
    assert {k: after[k] - before[k] for k in after} == {"rows": sites, "jnp": sites}


def test_wire_codec_3bit_measured_bits_per_element():
    """Acceptance: bits=3, block=1024 — the stacked payload the ring step rolls
    ships <= 3.2 wire bits/element, measured from real payload nbytes."""
    codec = QuantWire(bits=3, block=1024)
    tree = {"w": jnp.zeros((8, 64, 4096)), "b": jnp.zeros((8, 2048))}
    n_elem = sum(l.size for l in jax.tree.leaves(tree))
    tdef, payload = codec.encode_tree(tree, jnp.asarray(0, jnp.int32), salt=0)
    measured = 8.0 * sum(p["codes"].nbytes + p["scale"].nbytes for p in payload) / n_elem
    assert measured <= 3.2
    assert codec.wire_nbytes(tree) == \
        sum(p["codes"].nbytes + p["scale"].nbytes for p in payload)
    assert codec.wire_bits_per_element() == pytest.approx(3.03125)
    # roundtrip within one 3-bit bin (levels = 3)
    tree2 = {"w": jax.random.normal(jax.random.key(0), (2, 16, 1024))}
    tdef2, p2 = codec.encode_tree(tree2, jnp.asarray(1, jnp.int32), salt=0)
    out = codec.decode_tree(tdef2, p2, tree2)
    scale = float(jnp.max(jnp.abs(tree2["w"])))
    assert float(jnp.max(jnp.abs(out["w"] - tree2["w"]))) <= scale / 3 * 1.05


def test_quantize_nd_preserves_leading_dims():
    """Shard-local blocking: codes keep the leaf's leading dims intact."""
    from repro.distributed.wire import _dequantize_nd, _quantize_nd

    x = jax.random.normal(jax.random.key(0), (3, 5, 300))
    codes, scale = _quantize_nd(x, jnp.uint32(7), bits=8, block=128)
    assert codes.shape == (3, 5, 3, 128)      # 300 -> 3 blocks of 128 (padded)
    assert scale.shape == (3, 5, 3, 1)
    out = _dequantize_nd(codes, scale, bits=8, orig_last=300, dtype=x.dtype)
    assert out.shape == x.shape
    bin_w = float(jnp.max(scale)) / 127
    assert float(jnp.max(jnp.abs(out - x))) <= bin_w * 1.05


def test_quantize_nd_unbiased():
    from repro.distributed.wire import _dequantize_nd, _quantize_nd

    x = jax.random.normal(jax.random.key(1), (1, 512))
    acc = jnp.zeros_like(x)
    n = 200          # tolerance below scales with 1/sqrt(n); margin is ~3x
    for s in range(n):
        codes, scale = _quantize_nd(x, jnp.uint32(s), bits=4, block=128)
        acc = acc + _dequantize_nd(codes, scale, bits=4, orig_last=512, dtype=x.dtype)
    bin_w = float(jnp.max(jnp.abs(x))) / 7
    tol = 6 * bin_w / (n ** 0.5) + 1e-3
    assert float(jnp.max(jnp.abs(acc / n - x))) < 3 * tol


def test_torus_gossip_plan():
    plan = make_gossip_plan("torus", 16)              # 4x4 circulant torus
    assert plan.self_weight == pytest.approx(0.2)
    assert set(plan.shift_list) == {1, -1, 4, -4}
    assert plan.uniform and plan.degree == 4
    assert plan.self_weight + sum(w for _, w in plan.shifts) == pytest.approx(1.0)
    # small n falls back to the ring
    assert set(make_gossip_plan("torus", 4).shift_list) == {1, -1}


def test_torus_dpsgd_matches_core_simulator():
    """Sharded torus gossip == stacked simulator with the matching circulant W."""
    from repro.core.algorithms import Algorithm
    from repro.core import topology as topo

    n, d = 16, 8
    W = np.zeros((n, n))
    for i in range(n):                    # circulant: jumps {+-1, +-4}, self 1/5
        W[i, i] = 0.2
        for k in (1, -1, 4, -4):
            W[i, (i + k) % n] += 0.2
    topo.check_mixing_matrix(W)           # valid symmetric doubly stochastic
    algo = Algorithm(name="dpsgd", W=W)
    core_step = algo.step_fn()
    core_state = algo.init(jnp.zeros((d,)))

    plan = make_gossip_plan("torus", n)
    np.testing.assert_allclose(plan.mixing_matrix(), W, atol=1e-12)
    dist_step = make_dist_train_step(_toy_loss, "dpsgd", sgd(), None, plan,
                                     constant(0.05))
    dist_state = init_dist_state("dpsgd", jnp.zeros((d,)), plan, sgd())

    for t in range(5):
        batch = _toy_batch(jax.random.key(t), n)
        grads = jax.vmap(lambda p, A, b: jax.grad(
            lambda q: 0.5 * jnp.mean((A @ q - b) ** 2))(p))(
            core_state.params, batch["A"], batch["b"])
        core_state = core_step(core_state, grads, jax.random.key(t), jnp.float32(0.05))
        dist_state, _ = jax.jit(dist_step)(dist_state, batch)
        np.testing.assert_allclose(np.asarray(dist_state.params),
                                   np.asarray(core_state.params), atol=1e-5)


def test_torus_dcd_replica_invariants_and_convergence():
    """DCD on a 4x4 torus: all four replicas track their neighbors; loss drops."""
    n, d = 16, 16
    key = jax.random.key(0)
    A = jax.random.normal(key, (n, 64, d))
    b = jnp.einsum("nmd,d->nm", A, jnp.ones((d,)))
    batch = {"A": A, "b": b}
    plan = make_gossip_plan("torus", n)
    step = jax.jit(make_dist_train_step(_toy_loss, "dcd", sgd(),
                                        QuantWire(bits=8, block=128), plan,
                                        constant(0.1)))
    state = init_dist_state("dcd", jnp.zeros((d,)), plan, sgd())
    first = None
    for t in range(120):
        state, m = step(state, batch)
        first = first or float(m["loss"])
    for k in (1, -1, 4, -4):
        np.testing.assert_allclose(
            np.asarray(state.aux[f"rep{k:+d}"]),
            np.roll(np.asarray(state.params), k, axis=0), rtol=1e-5)
    assert float(m["loss"]) < 0.05 * first


# ------------------------------------------- plan-compiled topologies (tier)
#
# Acceptance for the GossipPlan redesign: the sharded runtime on a compiled
# plan must match the stacked reference running the plan's OWN mixing matrix,
# for non-trivial topologies — chain (banded, per-node masked weights) and the
# circulant torus (4 uniform shifts) — across both wire formats.

def _plan_wire(case):
    return {"quant4": QuantWire(bits=4, block=128),
            "sparse25": SparseWire(p=0.25, block=128)}[case]


@pytest.mark.parametrize("topo_name", ["chain", "torus"])
@pytest.mark.parametrize("wire_case", ["quant4", "sparse25"])
@pytest.mark.parametrize("algo", ["dcd", "ecd"])
def test_dist_step_matches_stacked_reference_on_plan(topo_name, wire_case, algo):
    """Sharded DCD/ECD on a compiled GossipPlan == stacked core/algorithms
    reference with W = plan.mixing_matrix() (atol 1e-5), for
    {chain, torus} x {quant 4-bit, sparse p=0.25} — and the wire words both
    runs put on the permute are bit-identical (same wire object, same
    (step, salt, leaf) seeds; asserted eager vs jit on the same tree)."""
    from repro.core.algorithms import Algorithm
    from repro.core.compression import compressor_for

    n, d = 16, 256
    plan = make_gossip_plan(topo_name, n)
    wire = _plan_wire(wire_case)
    salt = 2 if algo == "dcd" else 3
    comp = compressor_for(wire, salt=salt)
    assert comp.wire == wire              # one object, one implementation path
    core = Algorithm(name=algo, W=plan.mixing_matrix(), compressor=comp)
    core_step = jax.jit(core.step_fn())
    core_state = core.init(jnp.zeros((d,)))._replace(step=jnp.asarray(0, jnp.int32))

    dist_step = jax.jit(make_dist_train_step(
        _toy_loss, algo, sgd(), wire, plan, constant(0.05)))
    dist_state = init_dist_state(algo, jnp.zeros((d,)), plan, sgd())

    for t in range(3):
        batch = _toy_batch(jax.random.key(t), n, d=d)
        grads = jax.vmap(lambda p_, A, b: jax.grad(
            lambda q: 0.5 * jnp.mean((A @ q - b) ** 2))(p_))(
            core_state.params, batch["A"], batch["b"])
        core_state = core_step(core_state, grads, jnp.asarray(t), jnp.float32(0.05))
        dist_state, _ = dist_step(dist_state, batch)
        np.testing.assert_allclose(np.asarray(dist_state.params),
                                   np.asarray(core_state.params), atol=1e-5)
    # wire words bit-for-bit: the runtime and the reference encode through the
    # SAME wire object with the same seeds — jit and eager must agree word for
    # word on the packed containers (codes or idx)
    key = "codes" if wire_case == "quant4" else "idx"
    _, pe = wire.encode_tree(dist_state.params, jnp.asarray(2, jnp.int32), salt)
    pj = jax.jit(lambda tr, st: wire.encode_tree(tr, st, salt)[1])(
        dist_state.params, jnp.asarray(2, jnp.int32))
    np.testing.assert_array_equal(np.asarray(pe[0][key]), np.asarray(pj[0][key]))


def test_chain_dcd_replica_invariant_and_endpoint_weights():
    """DCD on a chain plan: replicas still track roll(X, +-1) globally, and the
    plan's masked weight vectors zero the wrap-around edges (endpoints have
    one neighbor)."""
    n, d = 8, 16
    plan = make_gossip_plan("chain", n)
    assert not plan.uniform and plan.degree == 2
    w_plus = dict(plan.shifts)[1]
    assert w_plus[0] == 0.0               # node 0 has no left neighbor
    step = jax.jit(make_dist_train_step(_toy_loss, "dcd", sgd(),
                                        QuantWire(bits=8, block=128), plan,
                                        constant(0.05)))
    state = init_dist_state("dcd", jnp.zeros((d,)), plan, sgd())
    for t in range(4):
        state, _ = step(state, _toy_batch(jax.random.key(t), n, d=d))
    for s in (1, -1):
        np.testing.assert_allclose(np.asarray(state.aux[f"rep{s:+d}"]),
                                   np.roll(np.asarray(state.params), s, axis=0),
                                   rtol=1e-5)


# ------------------------------------- schedule differential tier (tentpole)
#
# Multi-round GossipSchedules must agree with the stacked core/algorithms
# reference run round-by-round: a per-step schedule (full_logn) is the
# reference step chained once per round inside each training step (gradients
# ride round 0 for dcd/ecd, the whole-step update for dpsgd), a time-varying
# schedule (exp) is the reference step with W cycling per training step.  The
# encode counter is step * period + round (== step for flat plans), so both
# runs derive identical (step, salt, leaf) seeds and the wire words match bit
# for bit.


def _chained_reference(algo, sched, comp, d, lr=0.05):
    """A stacked-reference runner equivalent to the sharded schedule step."""
    from repro.core.algorithms import Algorithm

    round_steps = [
        Algorithm(name=algo, W=r.mixing_matrix(), compressor=comp).step_fn()
        for r in sched.rounds]
    state = Algorithm(
        name=algo, W=sched.rounds[0].mixing_matrix(), compressor=comp,
    ).init(jnp.zeros((d,)))._replace(step=jnp.asarray(0, jnp.int32))
    zeros = [None]

    def run_step(state, t, grads):
        if zeros[0] is None:
            zeros[0] = jax.tree.map(jnp.zeros_like, grads)
        if sched.time_varying:
            return round_steps[t % sched.period](
                state, grads, jnp.asarray(t), jnp.float32(lr))
        for r_idx, rstep in enumerate(round_steps):
            g = grads if r_idx == 0 else zeros[0]
            state = rstep(state, g, jnp.asarray(t * sched.period + r_idx),
                          jnp.float32(lr))
        return state

    return state, run_step


@pytest.mark.parametrize("spec", ["full_logn", "exp"])
@pytest.mark.parametrize("wire_case", ["quant4", "sparse25"])
@pytest.mark.parametrize("algo", ["dcd", "ecd"])
def test_dist_step_matches_stacked_reference_on_schedule(spec, wire_case, algo):
    """Acceptance: the sharded multi-round DCD/ECD step matches the stacked
    core/algorithms reference (atol 1e-5) on {full_logn, exp} x {quant:4,
    sparse:0.25} — with bit-identical wire words (same wire object, same
    step*period+round seeds; asserted eager vs jit on the same tree)."""
    from repro.core.compression import compressor_for

    n, d = 8, 256
    sched = make_gossip_plan(spec, n)
    wire = _plan_wire(wire_case)
    salt = 2 if algo == "dcd" else 3
    comp = compressor_for(wire, salt=salt)
    core_state, run_ref = _chained_reference(algo, sched, comp, d)

    dist_step = jax.jit(make_dist_train_step(
        _toy_loss, algo, sgd(), wire, sched, constant(0.05)))
    dist_state = init_dist_state(algo, jnp.zeros((d,)), sched, sgd())

    n_steps = 2 * sched.period if sched.time_varying else 3
    for t in range(n_steps):
        batch = _toy_batch(jax.random.key(t), n, d=d)
        grads = jax.vmap(lambda p_, A, b: jax.grad(
            lambda q: 0.5 * jnp.mean((A @ q - b) ** 2))(p_))(
            core_state.params, batch["A"], batch["b"])
        core_state = run_ref(core_state, t, grads)
        dist_state, _ = dist_step(dist_state, batch)
        np.testing.assert_allclose(np.asarray(dist_state.params),
                                   np.asarray(core_state.params), atol=1e-5)
    # wire words bit for bit at a mid-schedule round counter
    key = "codes" if wire_case == "quant4" else "idx"
    enc_step = jnp.asarray(1 * sched.period + 1, jnp.int32)
    _, pe = wire.encode_tree(dist_state.params, enc_step, salt)
    pj = jax.jit(lambda tr, st: wire.encode_tree(tr, st, salt)[1])(
        dist_state.params, enc_step)
    np.testing.assert_array_equal(np.asarray(pe[0][key]), np.asarray(pj[0][key]))


def test_schedule_dpsgd_matches_effective_dense_w():
    """Full-precision gossip on the full_logn schedule == ONE stacked step
    with the effective W = J/n (the schedule-equivalence claim, runtime
    edition): sequential sparse rounds realize the dense average exactly."""
    from repro.core.algorithms import Algorithm

    n, d = 8, 64
    sched = make_gossip_plan("full_logn", n)
    algo = Algorithm(name="dpsgd", W=sched.effective_mixing_matrix())
    core_step, core_state = algo.step_fn(), algo.init(jnp.zeros((d,)))
    dist_step = jax.jit(make_dist_train_step(_toy_loss, "dpsgd", sgd(), None,
                                             sched, constant(0.05)))
    dist_state = init_dist_state("dpsgd", jnp.zeros((d,)), sched, sgd())
    for t in range(4):
        batch = _toy_batch(jax.random.key(t), n, d=d)
        grads = jax.vmap(lambda p, A, b: jax.grad(
            lambda q: 0.5 * jnp.mean((A @ q - b) ** 2))(p))(
            core_state.params, batch["A"], batch["b"])
        core_state = core_step(core_state, grads, jax.random.key(t),
                               jnp.float32(0.05))
        dist_state, _ = dist_step(dist_state, batch)
        np.testing.assert_allclose(np.asarray(dist_state.params),
                                   np.asarray(core_state.params), atol=1e-5)


def test_schedule_dcd_replica_invariant_and_aux_keys():
    """DCD on full_logn: aux holds ONE replica per union shift ({1,2,4} at
    n=8), and every replica still tracks roll(X, s) after multi-round steps;
    on exp the same union serves the cycling one-peer rounds."""
    n, d = 8, 128
    for spec in ("full_logn", "exp"):
        sched = make_gossip_plan(spec, n)
        step = jax.jit(make_dist_train_step(_toy_loss, "dcd", sgd(),
                                            QuantWire(bits=8, block=128),
                                            sched, constant(0.05)))
        state = init_dist_state("dcd", jnp.zeros((d,)), sched, sgd())
        assert set(state.aux) == {"rep+1", "rep+2", "rep+4"}
        for t in range(4):
            state, _ = step(state, _toy_batch(jax.random.key(t), n, d=d))
            for s in (1, 2, 4):
                np.testing.assert_allclose(
                    np.asarray(state.aux[f"rep{s:+d}"]),
                    np.roll(np.asarray(state.params), s, axis=0),
                    rtol=1e-5, atol=1e-8)


@pytest.mark.parametrize("drop", [None, 0.2])
@pytest.mark.parametrize("algo", ["dpsgd", "naive", "dcd", "ecd", "choco",
                                  "deepsqueeze"])
def test_gossip_state_layout_is_one_decision(algo, drop):
    """init_dist_state, rekey_dist_state and the stacked GossipReference lay
    out one aux: the same keys and, at step 0 (every node holds the same
    model, so a resync changes no value), bit-identical leaves — on a torus
    plan, with and without drops, and with a stateful wire's own entry."""
    from repro.core.algorithms import GossipReference
    from repro.distributed.decentralized import FAMILIES, rekey_dist_state
    from repro.distributed.wire import make_wire_format

    plan = make_gossip_plan("torus", 16)
    kw, kb = jax.random.split(jax.random.key(0))
    params = {"w": jax.random.normal(kw, (4, 8)), "b": jax.random.normal(kb, (8,))}
    wires = ["quant:4", "lowrank:2:warm"] if FAMILIES[algo].encodes else [None]
    for wire in wires:
        aux = init_dist_state(algo, params, plan, sgd(), drop=drop, wire=wire).aux
        rekeyed = rekey_dist_state(
            init_dist_state(algo, params, plan, sgd(), drop=drop, wire=wire),
            algo, plan, drop=drop, wire=wire).aux
        ref = GossipReference(name=algo, plan=plan, wire=wire, drop=drop).init(params).aux
        for other in (rekeyed, ref):
            assert set(other) == set(aux)
            jax.tree.map(np.testing.assert_array_equal, other, aux)
        if wire == "lowrank:2:warm":
            assert make_wire_format(wire).aux_name in aux


def test_schedule_degree_vs_dense_plan_permute_count():
    """The whole point of the schedule: a full_logn step encodes/permutes 3
    rounds at n=8 (vs 7 for the dense full plan), visible as fused-kernel
    call counts in the jaxpr; exp pays exactly ONE round per step."""
    from repro.analysis.jaxpr_checks import expected_kernel_calls, kernel_call_counts

    n, d = 8, 256
    wire = QuantWire(bits=4, block=128)
    sched = make_gossip_plan("full_logn", n)
    step = make_dist_train_step(_toy_loss, "dcd", sgd(), wire, sched,
                                constant(0.05))
    state = init_dist_state("dcd", jnp.zeros((d,)), sched, sgd())
    batch = _toy_batch(jax.random.key(0), n, d=d)
    counts = kernel_call_counts(str(jax.make_jaxpr(step)(state, batch)))
    # per round: 1 self decode + |union| replica decodes = 4 -> 12 total;
    # the |union| rolled-payload decodes per round are exactly what
    # GossipPlan/GossipSchedule.replica_payloads (and netsim's
    # decentralized_lp charge) count — decode_sites() is that same formula
    assert counts["unpack_dequant_axpy"] == \
        expected_kernel_calls("dcd", sched, wire, state.params) == \
        sched.period * (1 + len(sched.shift_union))
    assert sched.replica_payloads == sched.period * len(sched.shift_union) == 9

    dense = make_gossip_plan("full", n)
    step_d = make_dist_train_step(_toy_loss, "dcd", sgd(), wire, dense,
                                  constant(0.05))
    state_d = init_dist_state("dcd", jnp.zeros((d,)), dense, sgd())
    counts_d = kernel_call_counts(str(jax.make_jaxpr(step_d)(state_d, batch)))
    # dense: 1 round, 1 self + 7 replica decodes — more aux, more permutes
    assert counts_d["unpack_dequant_axpy"] == \
        expected_kernel_calls("dcd", dense, wire, state_d.params) == \
        1 + dense.degree
    assert dense.degree == n - 1 > sched.degree


@pytest.mark.slow
@pytest.mark.parametrize("spec", ["full_logn", "exp"])
def test_dist_dcd_converges_on_schedule(spec):
    """Long multi-round convergence: sharded DCD on the schedule drives the
    quadratic loss down and the node average reaches the optimum."""
    n, d = 8, 16
    key = jax.random.key(0)
    A = jax.random.normal(key, (n, 64, d))
    x_true = jnp.ones((d,))
    b = jnp.einsum("nmd,d->nm", A, x_true)
    batch = {"A": A, "b": b}
    sched = make_gossip_plan(spec, n)
    step = jax.jit(make_dist_train_step(_toy_loss, "dcd", sgd(),
                                        QuantWire(bits=4, block=128), sched,
                                        constant(0.1)))
    state = init_dist_state("dcd", jnp.zeros((d,)), sched, sgd())
    first = None
    for t in range(120):
        state, m = step(state, batch)
        first = first or float(m["loss"])
    assert float(m["loss"]) < 0.05 * first
    xbar = np.asarray(jax.tree.map(lambda l: jnp.mean(l, 0), state.params))
    np.testing.assert_allclose(xbar, np.asarray(x_true), atol=0.1)


@pytest.mark.slow
def test_plan_gossip_lowering_wire_payload_only():
    """Acceptance HLO check for the plan redesign, now phrased on the
    analyzer API: on an 8-device node mesh, every collective-permute the
    {chain, torus2d} x {quant4, sparse} step emits moves only wire
    containers — uint32 packed words plus the tiny per-block f32
    scales/values — never the dense f32 stacked leaf.  The u32 words must
    be on the permute for every topology (the payload is identical
    whatever the graph; only the shift set changes)."""
    out = run_subprocess("""
        from repro.analysis.jaxpr_checks import analyze_case

        for topo_name in ("chain", "torus2d"):
            for wire in ("quant:4", "sparse:0.25"):
                r = analyze_case("dcd", topo_name, wire, n=8, hlo=True)
                assert r.ok, (topo_name, wire, r.violations)
                assert "u32" in r.permute_dtypes, \\
                    (topo_name, wire, "u32 words must ride the permute")
                print("OK", topo_name, wire, r.describe())
        print("ALL_OK")
    """)
    assert "ALL_OK" in out
