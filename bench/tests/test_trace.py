"""The trace reduction, on synthetic events and on a trace recorded here."""
import jax
import jax.numpy as jnp
import pytest

from bench import trace as T


def _dev(ops, async_ops=(), modules=()):
    return T.Device([T.Op(n, oc, s, e) for n, oc, s, e in ops],
                    [T.Op(n, oc, s, e) for n, oc, s, e in async_ops], list(modules))


def test_union_and_length_merge_overlaps():
    u = T.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    assert u == [(0, 3), (5, 8)]
    assert T.length(u) == 6


def test_subtract_and_gaps():
    assert T.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert T.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert T.gaps([(1, 2), (4, 5)], (0, 6)) == [(0, 1), (2, 4), (5, 6)]


@pytest.mark.parametrize("text,name,opcode", [
    ("%fusion.12 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p), kind=kLoop", "fusion.12",
     "fusion"),
    ("%copy-start = (f32[4096,4096]{1,0:T(8,128)S(1)}, f32[4096,4096]{1,0:T(8,128)}, "
     "u32[]{:S(2)}) copy-start(f32[4096,4096]{1,0:T(8,128)} %a.1)", "copy-start",
     "copy-start"),
    ("ROOT %unpack_dequant_axpy.1 = f32[4096,1024]{1,0:T(8,128)} custom-call(%c, %p), "
     "custom_call_target=\"tpu_custom_call\"", "unpack_dequant_axpy.1", "custom-call"),
    ("%collective-permute-start.3 = (u32[1,8]{1,0}, u32[1,8]{1,0}) "
     "collective-permute-start(u32[1,8]{1,0} %w)", "collective-permute-start.3",
     "collective-permute-start"),
])
def test_parse_instruction(text, name, opcode):
    assert T.parse_instruction(text) == (name, opcode)


def _synthetic():
    # a 100 ns window: compute 0-40 and 60-70, an async permute in flight
    # 30-65, a sync permute 80-90, the kernel twice, an input module
    dev = _dev(ops=[("fusion.1", "fusion", 0, 40), ("unpack_dequant_axpy.2", "custom-call", 60, 70),
                    ("collective-permute-start.1", "collective-permute-start", 30, 31),
                    ("collective-permute-done.1", "collective-permute-done", 40, 65),
                    ("collective-permute.2", "collective-permute", 80, 90),
                    ("unpack_dequant_axpy.3", "custom-call", 92, 95)],
               async_ops=[("collective-permute-start.1", "collective-permute-start", 30, 65)],
               modules=[("jit__node_batches(1)", 0, 20), ("jit_step(2)", 20, 100)])
    host = [("dispatch_step", 0, 45), ("wait_step", 45, 100)]
    return T.Trace(devices=[dev], host=host, window=(0.0, 100.0))


def test_device_readings_on_synthetic_events():
    tr = _synthetic()
    dev = tr.devices[0]
    assert T.busy(dev, tr.window) == [(0, 70), (80, 90), (92, 95)]
    assert T.compute(dev, tr.window) == [(0, 40), (60, 70), (92, 95)]
    # in flight 30-65 and 80-90; compute covers 30-40 and 60-65
    assert T.exposed(dev, tr.window, "collective-permute") == 20 + 10
    assert T.op_time(dev, tr.window, r"unpack_dequant_axpy(\.\d+)?$") == 13
    assert T.module_time(dev, tr.window, r"jit__node_batches\b") == 20
    gaps = T.idle_gaps(tr)
    assert gaps[0] == ["wait_step", 10e-9] and len(gaps) == 3
    names = [n for n, _ in T.top_ops(tr)]
    assert names[0] == "fusion.1 fusion"
    assert "collective-permute-start.1 collective-permute-start" not in names


def test_clip_to_window():
    tr = _synthetic()
    assert T.op_time(tr.devices[0], (65.0, 93.0), r"unpack") == 5 + 1


def test_trace_recorded_on_cpu(tmp_path):
    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    a = jnp.ones((256, 256))
    f(a).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("dispatch_step"):
                r = f(a)
            with jax.profiler.TraceAnnotation("wait_step"):
                r.block_until_ready()
    jax.profiler.stop_trace()
    tr = T.load(str(tmp_path))
    assert tr.window_s > 0
    names = [h[0] for h in tr.host]
    assert names.count("dispatch_step") == 3 and names.count("wait_step") == 3
    for n, s, e in tr.host:
        assert tr.window[0] <= s <= e <= tr.window[1]
    assert T.host_span_at(tr, tr.host[0][1] + 1) in ("dispatch_step", "wait_step")


def test_metric_readers_on_synthetic_events(monkeypatch):
    from bench import harness, spec

    monkeypatch.setattr(spec, "peaks", lambda kind: {"bf16_flops_per_s": 1e12,
                                                     "hbm_bytes_per_s": 1e11})
    ctx = harness.Context(cfg={}, traffic={"nodes": 2, "wire_bits": 4}, chips=1,
                          tokens_per_step=1000, flops_per_token=1e3, device_kind="x",
                          nodes_per_device=2, param_shapes=[(8, 1024)], compiled=None,
                          trace=_synthetic(), traced_steps=2)
    metrics = [{"name": n, "unit": "u"} for n in (
        "device_idle_pct", "input_ms_per_step", "decode_axpy_ms_per_step",
        "unpack_dequant_axpy_roofline", "mfu")]
    got = {k: v["value"] for k, v in harness.read_metrics(ctx, metrics).items()}
    assert got["device_idle_pct"] == pytest.approx(100 * (1 - 83 / 100))
    assert got["input_ms_per_step"] == pytest.approx(20e-6 / 2)
    assert got["decode_axpy_ms_per_step"] == pytest.approx(13e-6 / 2)
    # 2 nodes x (1 + 1 ring neighbour) decodes of 8 x 1024 elements: 4 B
    # payload + 8 B per element, plus 8 f32 scales, at 1e11 B/s
    work = 2 * 2 * (8 * 512 + 8 * 4 + 8 * 8 * 1024)
    assert got["unpack_dequant_axpy_roofline"] == pytest.approx(
        100 * work / 1e11 / (13e-9 / 2))
    assert got["mfu"] == pytest.approx(100 * 1e3 * 2000 / 100e-9 / 1e12)
