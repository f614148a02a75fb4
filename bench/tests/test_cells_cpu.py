"""CPU rehearsal of every cell's harness path at the program's reduced widths.

Every cell of BENCHMARK.json is rehearsed, and each rehearsal-only cell of
``tiny.REHEARSED``.  The stacked cells run here; the four-chip cells run on four virtual
CPU devices in a child process (the device count is fixed when JAX starts).
"""
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from bench import harness, spec
from bench.reference.model import family, init_params
from bench.tests import tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEED = 2 ** 33 + 5          # over 32 bits, as the driver's seeds are
STACKED = tiny.cells(chips=1)
FOUR_CHIP = tiny.cells(chips=4)


def run_small(monkeypatch, workload, traced, devices=None, **kw):
    bench, cfg, arch, traffic = tiny.small(workload)
    monkeypatch.setattr(spec, "cell", tiny.patched_cell(workload, traffic))
    monkeypatch.setattr(spec, "peaks", lambda kind: {"bf16_flops_per_s": 1e12,
                                                     "hbm_bytes_per_s": 1e11})
    devices = devices or jax.devices()[:1]
    return harness.run_cell(workload, SEED, 1.0, traced, t_start=time.perf_counter(),
                            bench=bench, devices=devices, cfg=cfg, arch=arch, **kw)


@pytest.mark.parametrize("workload", STACKED)
def test_reference_init_matches_the_program(workload):
    from repro.models.api import build_model

    _, cfg, arch, _ = tiny.small(workload)
    key = jax.random.key(SEED)
    prog = build_model(arch).init(key)
    ref = init_params(cfg, key)
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("workload", STACKED)
@pytest.mark.parametrize("traced", [False, True])
def test_stacked_cell_runs_and_is_correct(monkeypatch, workload, traced):
    res = run_small(monkeypatch, workload, traced)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["device"]["count"] == 1 and res["failed"] == 0
    if traced:
        # the CPU's trace has no per-instruction device events: the kernel
        # readers find nothing there and are checked on synthetic events
        assert "hbm_gb" in res["metrics"] and "mfu" in res["metrics"]
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert res["metrics"]["tokens_per_s_per_chip"]["value"] > 0
        assert res["metrics"]["setup_s"]["value"] > 0


FOUR = r"""
import sys, time, json
sys.path.insert(0, sys.argv[1]); sys.path.insert(0, sys.argv[1] + "/src")
import jax
from bench import harness, spec
from bench.tests import tiny
w = sys.argv[3]
bench, cfg, arch, traffic = tiny.small(w)
spec.cell = tiny.patched_cell(w, traffic)
spec.peaks = lambda kind: {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
res = harness.run_cell(w, 2 ** 33 + 7, 1.0, sys.argv[2] == "1", t_start=time.perf_counter(),
                       bench=bench, devices=jax.devices()[:4], cfg=cfg, arch=arch)
print(json.dumps(res))
"""


@pytest.mark.parametrize("workload", FOUR_CHIP)
@pytest.mark.parametrize("traced", ["0", "1"])
def test_four_chip_cell_on_four_cpu_devices(traced, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", FOUR, ROOT, traced, workload], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    # one node per device: the permutes carry wire containers only
    assert res["checks"]["permute_dense_bytes"]["value"] == 0


@pytest.mark.parametrize("workload", tiny.accepted(chips=1) + tiny.accepted(chips=4))
def test_accepted_cell_files_resolve_and_parse(workload):
    """Every file a cell of BENCHMARK.json is found by: its configuration,
    traffic and limits (through ``spec.cell``, as a run reads them), its
    family's reference and FLOPs count, its metrics' readers, and its limits
    at the tests' small sizes."""
    bench = spec.load_benchmark()
    c = spec.cell(bench, workload)
    cfg, traffic = c["config"], c["traffic"]
    assert set(c["limits"]) >= {"grad_norm_gap", "change_norm_gap"}
    assert set(cfg["reduced"]) <= set(cfg["program_fields"])
    assert traffic["nodes"] % c["chips"] == 0
    assert spec.flops_per_token(cfg, traffic) > 0
    assert hasattr(family(cfg), "block") or hasattr(family(cfg), "sequence_loss")
    for m in c["end_to_end"] + c["per_layer"]:
        assert os.path.exists(spec.metric_path(m["name"])), m["name"]
    assert set(tiny.small_limits(workload)) >= {"feed_mismatch", "grad_norm_gap"}
