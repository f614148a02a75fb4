"""The granite cell (``granite-L4.dcd-q4.stack2``): its files, its check at a
small size, and the reading of its attention scope."""
import json
import os
import time
import types

import jax
import pytest

from bench import attention, harness, scopes, spec
from bench import trace as T
from bench.tests import tiny

SEED = 2 ** 33 + 15         # over 32 bits, as a benchmark run's seeds may be
CELL = "granite-L4.dcd-q4.stack2"
MAMBA = "mamba2-L24.dcd-q4.stack2"


def _json(path):
    with open(os.path.join(spec.ROOT, path)) as f:
        return json.load(f)


def test_limits_lie_between_the_readings_of_the_calibration():
    """Each limit lies over every program reading and under every float8
    control reading; every planted fault but the one the regeneration cannot
    see reads over some limit on every seed."""
    lim = _json(os.path.join("bench", "limits", f"{CELL}.json"))
    limits, r = lim["limits"], lim["readings"]
    assert "feed_mismatch" in limits
    for key, limit in limits.items():
        prog = r["program"][key] + r["program_runs"].get(key, [])
        assert len(prog) >= 3 and max(prog) < limit, key
        control = r["upper"]["control"].get(key)
        if control:
            assert limit < min(control), key
    for fault, readings in r["upper"].items():
        if fault in ("control", "feed_one_token"):
            continue
        n = len(next(iter(readings.values())))
        over = [any(v[i] > limits[k] for k, v in readings.items() if k in limits)
                for i in range(n)]
        assert n == 3 and all(over), fault


def test_program_and_reference_agree_at_a_small_size(monkeypatch):
    """The benchmark's own files, shrunk as ``tiny.small`` shrinks the
    rehearsal, through the cell's check under the small limits."""
    _, cfg, arch, small_traffic = tiny.small(CELL)
    real = spec.cell(spec.load_benchmark(), CELL)
    assert {"attention_ms_per_step", "attention_core_roofline"} <= \
        {m["name"] for m in real["per_layer"]}
    limits = tiny.small_limits(CELL)
    traffic = dict(real["traffic"], **{k: small_traffic[k] for k in
                                       ("seq_len", "seqs_per_node", "warm_steps",
                                        "trace_steps")})
    monkeypatch.setattr(spec, "cell", lambda bench, name: dict(real, traffic=traffic,
                                                               limits=limits))
    monkeypatch.setattr(spec, "peaks", lambda kind: {"bf16_flops_per_s": 1e12,
                                                     "hbm_bytes_per_s": 1e11})
    res = harness.run_cell(CELL, SEED, 1.0, True, t_start=time.perf_counter(),
                           devices=jax.devices()[:1], cfg=cfg, arch=arch)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == set(limits)
    # the CPU's trace has no per-instruction events: the attention readers
    # find nothing there and say so by reading nothing
    assert "attention_ms_per_step" not in res["metrics"]
    assert "mfu" in res["metrics"]


def _compiled_names(workload):
    from bench.program import Cell

    _, cfg, arch, traffic = tiny.small(workload)
    cell = Cell(cfg, traffic, jax.devices()[:1], arch=arch)
    text = cell.compile().as_text()
    return scopes.op_names(text)


@pytest.mark.parametrize("workload,named", [(CELL, True), (MAMBA, False)])
def test_compiled_step_names_attention_only_where_it_runs(workload, named):
    names = _compiled_names(workload)
    under = [p for p in names.values() if attention.in_scope(p)]
    assert bool(under) == named
    if named:
        # the scope lies inside the model: the step's ops under it (not the
        # reducers' bodies, which have no path of their own) are in the
        # forward, backward and recomputed passes; the causal mask, which
        # depends on no input, JAX traces outside the model's passes
        step = [p for p in under if p.startswith("jit(")]
        phases = {scopes.phase_of(p) for p in step}
        assert {"forward", "backward", "remat"} <= phases <= {"forward", "backward",
                                                               "remat", "unscoped"}


STEP_HLO = """HloModule jit_step, is_scheduled=true

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, metadata={op_name="jit(step)/fwd_bwd/vmap(jvp())/while/body/closed_call/attention/dot_general"}
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %fusion.1), kind=kLoop, metadata={op_name="jit(step)/fwd_bwd/vmap(jvp())/while/body/closed_call/dot_general"}
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %fusion.2), kind=kLoop, metadata={op_name="jit(step)/fwd_bwd/vmap(transpose(jvp()))/while/body/closed_call/checkpoint/rematted_computation/attention/exp"}
  %fusion.4 = f32[8]{0} fusion(f32[8]{0} %fusion.3), kind=kLoop, metadata={op_name="jit(step)/fwd_bwd/vmap(transpose(jvp()))/while/body/closed_call/checkpoint/attention/dot_general"}
  %fusion.5 = f32[8]{0} fusion(f32[8]{0} %fusion.4), kind=kLoop, metadata={op_name="jit(step)/fwd_bwd/vmap(transpose(jvp()))/while/body/closed_call/checkpoint/rematted_computation/mul"}
  %while.6 = f32[8]{0} while(f32[8]{0} %fusion.5), body=%body.1, metadata={op_name="jit(step)/fwd_bwd/attention/while"}
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %while.6), kind=kLoop, metadata={op_name="jit(step)/encode/attention_like/mul"}
  ROOT %fusion.8 = f32[8]{0} fusion(f32[8]{0} %fusion.7), kind=kLoop, metadata={op_name="jit(step)/optimizer/add"}
}
"""


def _trace():
    # the input program 0-10, the step 10-60 and 70-100, the window 0-95
    ops = [("fusion.1", "fusion", 2, 8),          # in the input module: never joins
           ("fusion.1", "fusion", 10, 14),        # attention, forward 4
           ("fusion.2", "fusion", 14, 20),        # forward, not attention
           ("fusion.3", "fusion", 20, 25),        # attention, remat 5
           ("fusion.4", "fusion", 25, 32),        # attention, backward 7
           ("fusion.5", "fusion", 32, 35),        # remat, not attention
           ("while.6", "while", 35, 45),          # holds a body: no work of its own
           ("fusion.7", "fusion", 45, 50),        # a component named otherwise
           ("fusion.8", "fusion", 50, 60),
           ("fusion.4", "fusion", 90, 100)]       # attention, clipped to 5
    modules = [("jit__node_batches(1)", 0, 10), ("jit_step(2)", 10, 60),
               ("jit_step(2)", 70, 100)]
    dev = T.Device([T.Op(n, oc, s, e) for n, oc, s, e in ops], [], modules)
    return T.Trace(devices=[dev, dev], host=[], window=(0.0, 95.0))


def _ctx(trace, hlo, steps=2):
    return types.SimpleNamespace(trace=trace, hlo_text=hlo, traced_steps=steps)


def test_attention_time_sums_the_scope_over_forward_backward_and_remat():
    tr = _trace()
    ms = attention.ms_per_step(_ctx(tr, STEP_HLO))
    assert ms == pytest.approx((4 + 5 + 7 + 5) * 1e-6 / 2)
    split = scopes.split(tr, STEP_HLO)
    model = split["forward"] + split["backward"] + split["remat"]
    assert ms * 2 * 1e6 <= model


def test_attention_reads_nothing_without_the_scope_or_a_trace():
    plain = STEP_HLO.replace("/attention/", "/")
    assert attention.ms_per_step(_ctx(_trace(), plain)) is None
    assert attention.ms_per_step(_ctx(None, STEP_HLO)) is None
    assert attention.ms_per_step(_ctx(_trace(), STEP_HLO, steps=0)) is None


def test_core_count_at_the_cell_shapes():
    c = spec.cell(spec.load_benchmark(), CELL)
    cfg, traffic = c["config"], c["traffic"]
    # 2 products x 2 FLOPs x 32 heads x 64 x 2048 x 2049 / 2, forward and a
    # backward twice its size, x 4 layers x 2 sequences on the chip
    assert attention.core_flops(cfg, traffic, 2) == 2 * 2 * 32 * 64 * 2048 * 2049 / 2 * 3 * 8
    assert attention.core_flops(cfg, traffic, 2) == pytest.approx(412.5e9, rel=1e-3)
    # q, o, dO, o, dq over 32 heads; k, v, dk, dv over 8; bf16
    assert attention.core_bytes(cfg, traffic, 2) == (5 * 32 + 4 * 8) * 2048 * 64 * 2 * 8
    peaks = spec.peaks("TPU v5 lite")
    least = attention.least_s(cfg, traffic, 2, peaks)
    assert least == pytest.approx(412.5e9 / 197e12, rel=1e-3)      # bound by FLOPs


def test_roofline_reads_the_least_time_over_the_measured_time():
    from bench.metrics import attention_core_roofline

    c = spec.cell(spec.load_benchmark(), CELL)
    peaks = spec.peaks("TPU v5 lite")
    least_ms = attention.least_s(c["config"], c["traffic"], 2, peaks) * 1e3
    ctx = types.SimpleNamespace(cfg=c["config"], traffic=c["traffic"], nodes_per_device=2,
                                peaks=peaks, trace=None, traced_steps=8, hlo_text="")
    assert attention_core_roofline.read(ctx) is None
    ctx.trace, ctx.hlo_text = _trace(), STEP_HLO
    ms = attention.ms_per_step(ctx)
    assert attention_core_roofline.read(ctx) == pytest.approx(100 * least_ms / ms)
