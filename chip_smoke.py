#!/usr/bin/env python3
"""Bring-up smoke of the compressed-gossip trainer on a TPU.

Run from the repository root, in one process that owns the chip:

    python chip_smoke.py               # one chip: phases A and B
    python chip_smoke.py --four-chips  # four chips: phase C only

Phase A runs each fused decode kernel once on the chip at the shapes that the
main path's largest leaf hands it, and compares it with its
``kernels/ref.py`` oracle.  Phase B trains mamba2-370m at its published widths,
cut to 24 of its 48 layers, through ``repro.launch.train.run_training``: two
gossip nodes stacked on one chip, DCD over a ring with the packed 4-bit wire,
AdamW, a few steps on seeded random weights.  Phase C trains four nodes one per
chip on a ``node`` mesh and again stacked on one of the chips, with SGD, and
compares the params; the mesh's collective-permutes must carry only wire
containers.

Every check that fails exits non-zero.  The last line of a run that passes is
``{"ok": true, "device": {"platform", "kind", "count"}}``; nothing of the kind
is printed when no TPU is present.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Main path: the published widths of mamba2-370m (d_model 1024, d_inner 2048,
# d_state 128, 32 heads, chunk 256, vocab 50,280), cut from 48 to 24 layers,
# recomputing each layer in the backward pass as a real job at these widths
# does.  Two such nodes at 2 x 2048 tokens each compile to 13.2 GB on a v5e
# (memory_analysis: 8.4 GB of state, 4.8 GB of temporaries); the four nodes
# of phase C fit one chip at 1 layer and 1 x 1024 tokens each.
#
# Phase C compares under SGD, as the differential tier does.  A node's
# gradient on a TPU depends in its last bits on how many nodes one program
# stacks (a v5e measured differences up to 6.1e-5 between a 4-node and a
# 1-node vmap), and AdamW divides each element by its own RMS, so where a
# gradient is near zero those bits become lr-sized updates: after 3 steps
# AdamW's sharded and stacked params were 5.3e-4 apart, with the identity
# wire as with quant:4.
ARCH = "mamba2-370m"
MAIN = dict(n_layers=24, n_nodes=2, seq_len=2048, seqs_per_node=2,
            optimizer="adamw")
FOUR = dict(n_layers=1, n_nodes=4, seq_len=1024, seqs_per_node=1,
            optimizer="sgd")
STEPS = 3
WIRE, TOPOLOGY, ALGO = "quant:4", "ring", "dcd"
PARAM_ATOL = 1e-5        # the sharded == stacked differential tier's atol
MIN_PEAK_BYTES = 8e9     # phase B's training state fills half the chip, as a job's does


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def require_tpu(count: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU present (JAX's first device is a "
              f"{devices[0].platform!r} device); nothing was run",
              file=sys.stderr)
        sys.exit(2)
    if len(devices) < count:
        print(f"chip_smoke: needs {count} TPU chips, JAX sees {len(devices)}",
              file=sys.stderr)
        sys.exit(2)
    return devices


def arch_config(n_layers: int):
    from repro.configs import get_config

    return dataclasses.replace(get_config(ARCH), n_layers=n_layers)


def train_config(shape: dict):
    from repro.launch.train import TrainConfig

    return TrainConfig(algo=ALGO, wire=WIRE, topology=TOPOLOGY,
                       n_nodes=shape["n_nodes"], seq_len=shape["seq_len"],
                       global_batch=shape["n_nodes"] * shape["seqs_per_node"],
                       optimizer=shape["optimizer"], steps=STEPS, log_every=1, reduced=False, remat=True)


# --------------------------------------------------------------- phase A

def _compare(name, got, want, *, rtol, atol, words_equal):
    got, want = jnp.asarray(got), jnp.asarray(want)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
    diff = jnp.abs(got - want)
    n_neq = int(jnp.sum(got != want))
    n_out = int(jnp.sum(diff > atol + rtol * jnp.abs(want)))
    finite = bool(jnp.all(jnp.isfinite(got)))
    rule = "word-equal" if words_equal else f"rtol={rtol} atol={atol}"
    ok = finite and (n_neq == 0 if words_equal else n_out == 0)
    print(f"A {name:28s} shape={tuple(got.shape)} max|diff|={float(jnp.max(diff)):.3e} "
          f"unequal_words={n_neq} outside_tol={n_out} [{rule}] "
          f"{'OK' if ok else 'FAIL'}", flush=True)
    check(ok, f"{name} disagrees with its oracle ({rule})")


def _by_row_chunks(fn, *arrays, max_rows: int = 8192):
    """``fn`` over row chunks of its operands, in one jitted ``lax.map``.

    The sparse scatter oracle compares every lane against every index, a
    (rows, k, cols) intermediate that does not fit the chip at a full leaf."""
    rows = arrays[0].shape[0]
    c = max(d for d in range(1, min(rows, max_rows) + 1) if rows % d == 0)
    split = [a.reshape(rows // c, c, *a.shape[1:]) for a in arrays]
    out = jax.jit(lambda *xs: jax.lax.map(lambda t: fn(*t), xs))(*split)
    return out.reshape(rows, *out.shape[2:])


def phase_kernels(cfg, n_nodes: int) -> None:
    """Each fused decode kernel at the (rows, block) view the main path's
    largest leaf hands it, against its ``kernels/ref.py`` oracle."""
    from repro.kernels import ref
    from repro.kernels.lowrank import lowrank_axpy_2d
    from repro.kernels.ops import interpret_kernels
    from repro.kernels.quant import (
        sparse_geometry,
        sparse_scatter_axpy_2d,
        unpack_dequant_axpy_2d,
        unpack_sign_axpy_2d,
    )
    from repro.models.api import build_model

    params = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
    leaf = max(jax.tree.leaves(params), key=lambda l: l.size)
    shape = (n_nodes,) + tuple(leaf.shape)          # stacked on the node axis
    print(f"A largest leaf {shape} ({int(np.prod(shape)) / 1e6:.1f}M elements)",
          flush=True)
    kx, ka, kv = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(kx, shape, jnp.float32)
    acc = jax.random.normal(ka, shape, jnp.float32)
    seed = jnp.asarray([7], jnp.uint32)
    w, aw = 0.7, 0.9
    interp = interpret_kernels()

    # the wires block the last dim (quant and sign by 1024, sparse by 128)
    wide, narrow = min(1024, shape[-1]), min(128, shape[-1])
    for bits in (4, 3):
        x2, a2 = x.reshape(-1, wide), acc.reshape(-1, wide)
        packed, scale = jax.jit(ref.quantize_pack_2d_ref, static_argnames="bits")(
            x2, seed, bits=bits)
        _compare(f"unpack_dequant_axpy_2d@{bits}b",
                 unpack_dequant_axpy_2d(packed, scale, a2, bits=bits, weight=w,
                                        acc_weight=aw, interpret=interp),
                 ref.unpack_dequant_axpy_2d_ref(packed, scale, a2, bits=bits,
                                                weight=w, acc_weight=aw),
                 rtol=1e-5, atol=1e-6, words_equal=False)

    # sparse wire defaults: p=0.25 of 128-element blocks, random-k
    x2, a2 = x.reshape(-1, narrow), acc.reshape(-1, narrow)
    k = sparse_geometry(narrow, 0.25)[0]
    vals, idx = jax.jit(ref.sparse_select_pack_2d_ref, static_argnames=("p", "mode"))(
        x2, seed, p=0.25, mode="randk")
    _compare("sparse_scatter_axpy_2d",
             sparse_scatter_axpy_2d(vals, idx, a2, weight=w, acc_weight=aw,
                                    interpret=interp),
             _by_row_chunks(lambda v_, i_, a_: ref.sparse_scatter_axpy_2d_ref(
                 v_, i_, a_, k=k, weight=w, acc_weight=aw), vals, idx, a2),
             rtol=0.0, atol=1e-6, words_equal=False)

    x2, a2 = x.reshape(-1, wide), acc.reshape(-1, wide)
    packed, scale = jax.jit(ref.sign_pack_2d_ref)(x2)
    _compare("unpack_sign_axpy_2d",
             unpack_sign_axpy_2d(packed, scale, a2, weight=w, acc_weight=aw,
                                 interpret=interp),
             ref.unpack_sign_axpy_2d_ref(packed, scale, a2, weight=w,
                                         acc_weight=aw),
             rtol=1e-5, atol=1e-6, words_equal=False)

    # lowrank wire: the leaf's first (m, n) matrix slab as rank-2 factors
    m, a1 = x.reshape(-1, *shape[-2:])[0], acc.reshape(-1, *shape[-2:])[0]
    v = jax.random.normal(kv, (m.shape[-1], 2), jnp.float32)
    p = jax.jit(lambda m_, v_: ref.lowrank_orthonormalize_ref(
        ref.lowrank_project_2d_ref(m_, v_)))(m, v)
    _compare("lowrank_axpy_2d@r2",
             lowrank_axpy_2d(p, v, a1, weight=w, acc_weight=aw, interpret=interp),
             jax.jit(lambda p_, v_, a_: ref.lowrank_axpy_2d_ref(
                 p_, v_, a_, weight=w, acc_weight=aw))(p, v, a1),
             rtol=1e-6, atol=1e-6, words_equal=False)


# --------------------------------------------------------------- phase B

def _custom_calls(hlo_text: str) -> int:
    return hlo_text.count('custom_call_target="tpu_custom_call"')


def _check_losses(tag: str, hist: dict) -> None:
    losses = hist["loss"]
    check(len(losses) == STEPS, f"{tag}: {len(losses)} losses for {STEPS} steps")
    check(all(np.isfinite(losses)), f"{tag}: non-finite loss in {losses}")


def phase_train(devices) -> None:
    from repro.analysis.jaxpr_checks import expected_kernel_calls
    from repro.distributed.gossip import make_gossip_plan
    from repro.distributed.wire import make_wire_format
    from repro.launch.train import run_training

    cfg, tc = arch_config(MAIN["n_layers"]), train_config(MAIN)
    print(f"B {ARCH} n_layers={cfg.n_layers} d_model={cfg.d_model} "
          f"d_inner={cfg.ssm.d_inner} d_state={cfg.ssm.d_state} "
          f"heads={cfg.ssm.n_heads} chunk={cfg.ssm.chunk} vocab={cfg.vocab}; "
          f"{tc.n_nodes} nodes x {tc.global_batch // tc.n_nodes} x {tc.seq_len} "
          f"tokens, {ALGO} {WIRE} {TOPOLOGY}", flush=True)
    t0 = time.time()
    hist = run_training(cfg, tc, devices=devices[:1])
    wall = time.time() - t0
    _check_losses("B", hist)
    print(f"B losses {hist['loss']}", flush=True)

    compiled = hist["compiled_steps"][0]
    mem = compiled.memory_analysis()
    print(f"B memory_analysis argument={mem.argument_size_in_bytes} "
          f"output={mem.output_size_in_bytes} alias={mem.alias_size_in_bytes} "
          f"temp={mem.temp_size_in_bytes}", flush=True)
    stats = devices[0].memory_stats()
    check(stats is not None, "the device reports no memory stats")
    peak = stats["peak_bytes_in_use"]
    print(f"B peak_bytes_in_use={peak} memory_stats={stats}", flush=True)
    check(peak >= MIN_PEAK_BYTES, f"peak {peak} bytes: the main path no longer "
          f"fills the chip as a real job does (>= {MIN_PEAK_BYTES:.0e})")

    params = hist["state"].params
    expected = expected_kernel_calls(ALGO, make_gossip_plan(TOPOLOGY, tc.n_nodes),
                                     make_wire_format(WIRE), params)
    found = _custom_calls(compiled.as_text())
    print(f"B tpu_custom_call={found} expected_fused_kernels={expected}", flush=True)
    check(found == expected, f"compiled step has {found} tpu_custom_calls, "
          f"the analyzer expects {expected}")
    print(f"B smoke timing: compile_s={hist['compile_s'][0]:.1f} "
          f"run_training_wall_s={wall:.1f} steps_wall_s={hist['wall_s']:.1f}",
          flush=True)


# --------------------------------------------------------------- phase C

def phase_four_chips(devices) -> None:
    from repro.analysis.jaxpr_checks import (
        check_permute_payload_whitelist,
        permute_operands,
    )
    from repro.distributed.wire import make_wire_format
    from repro.launch.train import run_training

    cfg, tc = arch_config(FOUR["n_layers"]), train_config(FOUR)
    print(f"C {ARCH} n_layers={cfg.n_layers}; {tc.n_nodes} nodes x "
          f"{tc.global_batch // tc.n_nodes} x {tc.seq_len} tokens, "
          f"{ALGO} {WIRE} {TOPOLOGY} {tc.optimizer}", flush=True)

    sharded = run_training(cfg, tc, devices=devices[:4])
    _check_losses("C sharded", sharded)
    hlo = sharded["compiled_steps"][0].as_text()
    params_sharded = jax.device_get(sharded["state"].params)
    print(f"C sharded losses {sharded['loss']} "
          f"compile_s={sharded['compile_s'][0]:.1f}", flush=True)
    del sharded

    stacked = run_training(cfg, tc, devices=devices[:1])
    _check_losses("C stacked", stacked)
    params_stacked = jax.device_get(stacked["state"].params)
    print(f"C stacked losses {stacked['loss']} "
          f"compile_s={stacked['compile_s'][0]:.1f}", flush=True)

    perms = permute_operands(hlo)
    violations = check_permute_payload_whitelist(
        hlo, make_wire_format(WIRE), stacked["state"].params, n_devices=4)
    print(f"C collective-permute operands={len(perms)} "
          f"dtypes={sorted({p.dtype for p in perms})} "
          f"whitelist_violations={violations}", flush=True)
    check(bool(perms) and not violations,
          "the sharded step's permutes carry more than wire containers")

    diffs = jax.tree.leaves(jax.tree.map(
        lambda a, b: float(np.max(np.abs(np.asarray(a) - np.asarray(b)))),
        params_sharded, params_stacked))
    n_neq = sum(int(np.sum(np.asarray(a) != np.asarray(b))) for a, b in zip(
        jax.tree.leaves(params_sharded), jax.tree.leaves(params_stacked)))
    print(f"C params sharded vs stacked: max|diff|={max(diffs):.3e} "
          f"unequal={n_neq} atol={PARAM_ATOL}", flush=True)
    check(max(diffs) <= PARAM_ATOL,
          f"sharded and stacked params differ by {max(diffs):.3e}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded-vs-stacked phase")
    args = ap.parse_args()
    devices = require_tpu(4 if args.four_chips else 1)

    from repro.launch.train import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    print(f"devices: {len(devices)} x {devices[0].device_kind}", flush=True)
    try:
        if args.four_chips:
            phase_four_chips(devices)
        else:
            phase_kernels(arch_config(MAIN["n_layers"]), MAIN["n_nodes"])
            phase_train(devices)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        sys.exit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
