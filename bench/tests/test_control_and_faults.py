"""The comparison that decides ``correct`` fails what it must fail.

At the program's reduced widths on the CPU, against each cell's own limits,
for every cell ``tiny.cells`` rehearses:
the control (the plain reference with float8 matmul operands, put in the
program's place) and the faults a training cell can have, planted in the
program underneath a whole run: a step that returns its state unchanged;
half of each node's batch left out, the mean taken over the rest; the gossip
exchange left out (between stacked nodes and between chips); and the feed's
rows altered where they are produced (another node's rows, labels that are
not shifted, one token changed).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.reference.model import Numerics
from bench.reference.train import ReferenceRun
from bench.tests import tiny
from bench.tests.test_cells_cpu import ROOT, run_small

STACKED = tiny.cells(chips=1)


@pytest.mark.parametrize("workload", STACKED)
def test_control_in_the_programs_place_is_not_correct(monkeypatch, workload):
    real = harness.first_steps

    def control_steps(cell, step, seed, n_checked):
        state, _, rows = real(cell, step, seed, n_checked)
        ctl = ReferenceRun(cell.cfg, cell.traffic, cell.devices,
                           numerics=Numerics(fp8=True)).run(seed, rows)
        return state, ctl, rows

    monkeypatch.setattr(harness, "first_steps", control_steps)
    res = run_small(monkeypatch, workload, False)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload", STACKED)
def test_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch, workload):
    def unchanged(step):
        def broken(state, batch):
            _, met = step(jax.tree.map(jnp.copy, state), batch)
            return state, met
        return broken

    res = run_small(monkeypatch, workload, False, step_wrapper=unchanged)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload", STACKED)
def test_half_of_the_batch_left_out_is_not_correct(monkeypatch, workload):
    import bench.program as program

    real = program.build_model

    def half_batch_model(arch):
        model = real(arch)

        def loss(params, batch, remat=False):
            half = {k: v[: max(v.shape[0] // 2, 1)] if v.shape[0] > 1
                    else v[:, : v.shape[1] // 2] for k, v in batch.items()}
            return model.loss(params, half, remat=remat)

        return model.__class__(**{**model.__dict__, "loss": loss})

    monkeypatch.setattr(program, "build_model", half_batch_model)
    res = run_small(monkeypatch, workload, False)
    assert not res["correct"], res["checks"]


NO_EXCHANGE = r"""
import sys, time, json
sys.path.insert(0, sys.argv[1]); sys.path.insert(0, sys.argv[1] + "/src")
import jax
import repro.distributed.decentralized as dec
dec._roll = lambda tree, shift: tree          # every permute left out
from bench import harness, spec
from bench.tests import tiny
w = sys.argv[2]
bench, cfg, arch, traffic = tiny.small(w)
spec.cell = tiny.patched_cell(w, traffic)
res = harness.run_cell(w, 2 ** 33 + 9, 1.0, False, t_start=time.perf_counter(),
                       bench=bench, devices=jax.devices()[:4], cfg=cfg, arch=arch)
print(json.dumps(res))
"""


@pytest.mark.parametrize("workload", STACKED + tiny.cells(chips=4))
def test_exchange_between_chips_left_out_is_not_correct(monkeypatch, workload):
    if workload in STACKED:
        import repro.distributed.decentralized as dec

        monkeypatch.setattr(dec, "_roll", lambda tree, shift: tree)
        res = run_small(monkeypatch, workload, False)
        assert not res["correct"], res["checks"]
        return
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", NO_EXCHANGE, ROOT, workload], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert not res["correct"], res["checks"]


def _other_node(b):
    return {k: jnp.roll(v, 1, axis=0) for k, v in b.items()}


def _unshifted(b):
    return dict(b, labels=b["tokens"])


def _one_token(b):
    tok = b["tokens"][0, 0, b["tokens"].shape[-1] // 2]
    return dict(b, tokens=b["tokens"].at[0, 0, b["tokens"].shape[-1] // 2].set(
        jnp.where(tok > 0, tok - 1, tok + 1)))


@pytest.mark.parametrize("fault", [_other_node, _unshifted, _one_token])
def test_feed_altered_where_it_is_produced_is_not_correct(monkeypatch, fault):
    import bench.program as program

    real = program.Cell.batch
    monkeypatch.setattr(program.Cell, "batch",
                        lambda self, seed, t: fault(real(self, seed, t)))
    res = run_small(monkeypatch, STACKED[0], False)
    assert not res["correct"], res["checks"]
