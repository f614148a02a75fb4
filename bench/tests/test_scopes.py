"""The per-phase split of the step's device time, on synthetic events and on
the HLO of a tiny step compiled here."""
import dataclasses

import jax
import pytest

from bench import harness, scopes, spec
from bench import trace as T

STEP_HLO = """HloModule jit_step, is_scheduled=true

%body.1 (p: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, metadata={op_name="jit(step)/fwd_bwd/vmap(jvp(model))/while/body/mul" source_file="m.py"}
  ROOT %fusion.2 = f32[8]{0} fusion(f32[8]{0} %fusion.1), kind=kLoop, metadata={op_name="jit(step)/fwd_bwd/vmap(transpose(jvp(model)))/while/body/mul"}
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, metadata={op_name="jit(step)/fwd_bwd/vmap(transpose(jvp(model)))/checkpoint/rematted_computation/tanh"}
  %while.4 = f32[8]{0} while(f32[8]{0} %fusion.3), body=%body.1, metadata={op_name="jit(step)/fwd_bwd/vmap(jvp(model))/while"}
  %copy.5 = f32[8]{0} copy(f32[8]{0} %while.4), metadata={op_name="jit(step)/decode/permute/jit(_roll_static)/concatenate"}
  %custom-call.6 = f32[8]{0} custom-call(f32[8]{0} %copy.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/decode/unpack_dequant_axpy"}
  %collective-permute-start.7 = (f32[8]{0}, f32[8]{0}) collective-permute-start(f32[8]{0} %copy.5), metadata={op_name="jit(step)/permute/jit(_roll_static)/concatenate"}
  %fusion.8 = f32[8]{0} fusion(f32[8]{0} %custom-call.6), kind=kLoop, metadata={op_name="jit(step)/optimizer/add"}
  %copy.9 = f32[8]{0} copy(f32[8]{0} %fusion.8)
  ROOT %fusion.10 = f32[] fusion(f32[8]{0} %copy.9), kind=kLoop, metadata={op_name="jit(step)/step_metrics/reduce_sum"}
}
"""


def _dev(ops, modules):
    return T.Device([T.Op(n, oc, s, e) for n, oc, s, e in ops], [], list(modules))


def _synthetic():
    # the input program 0-10 (its own fusion.1), the step 10-60 and 70-100,
    # the window 0-95
    ops = [("fusion.1", "fusion", 2, 8),                       # input module: never joins
           ("fusion.3", "fusion", 10, 14),                     # remat 4
           ("while.4", "while", 14, 30),                       # holds the body: dropped
           ("fusion.1", "fusion", 14, 20),                     # forward 6
           ("fusion.2", "fusion", 20, 30),                     # backward 10
           ("copy.5", "copy", 30, 33),                         # permute 3 (innermost)
           ("custom-call.6", "custom-call", 33, 40),           # decode 7
           ("collective-permute-start.7", "collective-permute-start", 40, 41),
           ("collective-permute-done.7", "collective-permute-done", 41, 45),
           ("fusion.8", "fusion", 45, 52),                     # optimizer 7
           ("copy.9", "copy", 52, 55),                         # no op_name: unscoped 3
           ("fusion.11", "fusion", 55, 57),                    # not in the HLO: unscoped 2
           ("fusion.10", "fusion", 57, 65),                    # step_metrics, clipped to 3
           ("fusion.3", "fusion", 70, 98)]                     # remat, clipped to 25
    modules = [("jit__node_batches(1)", 0, 10), ("jit_step(2)", 10, 60),
               ("jit_step(2)", 70, 100)]
    return T.Trace(devices=[_dev(ops, modules)], host=[], window=(0.0, 95.0))


@pytest.mark.parametrize("op_name,phase", [
    ("jit(step)/decode/permute/jit(_roll_static)/slice", "permute"),
    ("jit(step)/permute/decode/mul", "decode"),
    ("jit(step)/encode/jit(clip)/max", "encode"),
    ("jit(step)/step_metrics/reduce_sum", "step_metrics"),
    ("jit(step)/fwd_bwd/vmap(jvp(model))/while/body/dot_general", "forward"),
    ("jit(step)/fwd_bwd/vmap(transpose(jvp(fwd_bwd)))/while/body/dot_general", "backward"),
    ("jit(step)/fwd_bwd/vmap(transpose(jvp(fwd_bwd)))/vmap(jvp())/checkpoint/"
     "rematted_computation/dot_general", "remat"),
    ("jit(step)/fwd_bwd/optimizer/mul", "optimizer"),
    ("jit(step)/vmap(transpose(jvp(model)))/mul", "unscoped"),
    ("jit(step)/add", "unscoped"),
    ("jit(step)/mixer/add", "unscoped"),
    (None, "unscoped"),
])
def test_phase_of_takes_the_innermost_scope(op_name, phase):
    assert scopes.phase_of(op_name) == phase


def test_hlo_parse():
    names = scopes.op_names(STEP_HLO)
    assert scopes.module_name(STEP_HLO) == "jit_step"
    assert names["fusion.1"].endswith("vmap(jvp(model))/while/body/mul")
    assert names["fusion.10"] == "jit(step)/step_metrics/reduce_sum"
    assert "copy.9" not in names and "body.1" not in names
    assert scopes.scoped(names)
    assert not scopes.scoped({"fusion.1": "jit(step)/mul"})


def test_split_on_synthetic_events():
    got = scopes.split(_synthetic(), STEP_HLO)
    assert got == dict(forward=6, backward=10, remat=4 + 25, optimizer=7, encode=0,
                       permute=3, decode=7, mix=0, step_metrics=3, unscoped=3 + 2)


def _ctx(trace, hlo_text, steps):
    ctx = harness.Context(cfg={}, traffic={}, chips=len(trace.devices), tokens_per_step=1,
                          flops_per_token=1.0, device_kind="x", nodes_per_device=2,
                          param_shapes=[], compiled=None, trace=trace, traced_steps=steps)
    ctx._hlo = hlo_text
    return ctx


def _phase_metrics():
    return [m for m in spec.load_benchmark()["per_layer"]
            if m["name"] in {f"{p}_ms_per_step" for p in scopes.PHASES}]


def test_readers_are_ms_per_step_mean_over_chips():
    tr = _synthetic()
    two = T.Trace(devices=tr.devices * 2, host=[], window=tr.window)
    metrics = _phase_metrics()
    assert len(metrics) == 10
    got = {k: v["value"] for k, v in harness.read_metrics(_ctx(two, STEP_HLO, 2),
                                                          metrics).items()}
    # nothing encoded or mixed here: those two read nothing
    assert set(got) == {f"{p}_ms_per_step" for p in scopes.PHASES} - {
        "encode_ms_per_step", "mix_ms_per_step"}
    assert got["remat_ms_per_step"] == pytest.approx(29e-6 / 2)
    assert sum(got.values()) == pytest.approx(70e-6 / 2)


def test_a_phase_fused_into_another_reads_zero():
    """The mix's add, fused into an optimizer-rooted fusion, names the phase
    but roots no op that ran: the mix reads 0 ms, and encode, which no
    instruction names, still reads nothing."""
    fused = STEP_HLO.replace(
        "ENTRY %main.9",
        "%fused_computation.1 (q: f32[8]) -> f32[8] {\n"
        '  %add.7 = f32[8]{0} add(f32[8]{0} %q, f32[8]{0} %q), '
        'metadata={op_name="jit(step)/mix/add"}\n'
        '  ROOT %multiply.8 = f32[8]{0} multiply(f32[8]{0} %add.7, f32[8]{0} %q), '
        'metadata={op_name="jit(step)/optimizer/mul"}\n'
        "}\n\nENTRY %main.9")
    got = {k: v["value"] for k, v in harness.read_metrics(_ctx(_synthetic(), fused, 2),
                                                          _phase_metrics()).items()}
    assert got["mix_ms_per_step"] == 0.0
    assert "encode_ms_per_step" not in got
    assert got["optimizer_ms_per_step"] == pytest.approx(7e-6 / 2)
    assert sum(got.values()) == pytest.approx(70e-6 / 2)


def test_readers_read_nothing_from_a_program_without_scopes():
    plain = STEP_HLO.replace("fwd_bwd", "model_").replace("/decode", "").replace(
        "/permute", "").replace("/optimizer", "").replace("/step_metrics", "")
    got = harness.read_metrics(_ctx(_synthetic(), plain, 2), _phase_metrics())
    assert got == {}
    assert harness.read_metrics(_ctx(_synthetic(), STEP_HLO, 0), _phase_metrics()) == {}


def test_tiny_step_compiled_here_splits_into_every_phase():
    """Every instruction of a small DCD step's optimized HLO runs once, 1 ns
    each, inside the step module; the ten metrics add up to all of it."""
    from repro.configs import get_config
    from repro.data import DataConfig, stacked_node_batches
    from repro.distributed.decentralized import init_dist_state, make_dist_train_step
    from repro.distributed.gossip import make_gossip_plan
    from repro.models.api import build_model
    from repro.optim import make_optimizer
    from repro.optim.schedules import linear_warmup_cosine

    arch = dataclasses.replace(get_config("mamba2-370m").reduced(), n_layers=1)
    model, opt, plan = build_model(arch), make_optimizer("adamw"), make_gossip_plan("ring", 2)
    state = jax.eval_shape(lambda k: init_dist_state("dcd", model.init(k), plan, opt,
                                                     wire="quant:4"), jax.random.key(0))
    dc = DataConfig(vocab=arch.vocab, seq_len=16, global_batch=2, n_shards=2, seed=0)
    batch = jax.eval_shape(lambda: stacked_node_batches(dc, 0, arch))
    step = make_dist_train_step(lambda p, b: model.loss(p, b, remat=True), "dcd", opt,
                                "quant:4", plan, linear_warmup_cosine(1e-3, 2, 10))
    text = jax.jit(step).lower(state, batch).compile().as_text()

    names = scopes.op_names(text)
    ops = [T.Op(n, "fusion", 100 + i, 101 + i) for i, n in enumerate(sorted(names))]
    ops.append(T.Op("while.999", "while", 100, 100 + len(ops)))
    n = len(names)
    modules = [(f"{scopes.module_name(text)}(7)", 100, 100 + n),
               ("jit__node_batches(8)", 0, 100)]
    ops.append(T.Op("fusion.1", "fusion", 10, 20))       # the input module's
    tr = T.Trace(devices=[T.Device(ops, [], modules)], host=[], window=(0.0, 1e6))
    got = {k: v["value"] for k, v in harness.read_metrics(_ctx(tr, text, 1),
                                                          _phase_metrics()).items()}
    # every phase a DCD step runs is there, and nothing else reads
    assert set(got) == {f"{p}_ms_per_step" for p in scopes.PHASES}
    assert sum(got.values()) == pytest.approx(n * 1e-6)
    by_phase = {p: sum(scopes.phase_of(v) == p for v in names.values()) for p in scopes.PHASES}
    for p, count in by_phase.items():
        assert got[f"{p}_ms_per_step"] == pytest.approx(count * 1e-6)
