"""One run of one cell: set-up, the measured (or traced) window, the check.

The set-up builds the cell's compiled step and its state once, drives them
from the seed through the first steps with the window's own call and feed
(reading what ``bench/check.py`` compares), and warms up.  The window then
keeps one step queued: it dispatches step t and blocks on step t-1's loss,
recording each completion on the host clock.  Nothing may compile inside it.
After the window the program's state is freed and the plain reference
follows the same first steps on the same devices.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, hlo, spec
from bench import trace as T


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class CompiledInWindow(RuntimeError):
    """Something traced or compiled inside the measured window."""


def tpu_devices(chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (its first device is {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX finds {len(devices)}")
    return devices[:chips]


def use_cache_dir():
    """JAX's persistent compilation cache, at a fixed path in the checkout;
    every program is kept, so a cell's second run compiles nothing."""
    jax.config.update("jax_compilation_cache_dir", spec.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts JAX's tracing and backend-compile events while ``armed``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.armed, self.count, self.seen = False, 0, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.count += 1
            self.seen.append(event)


@dataclasses.dataclass
class Context:
    """What the metric readers under ``bench/metrics`` read."""
    cfg: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int
    tokens_per_step: int
    flops_per_token: float
    device_kind: str
    nodes_per_device: int
    param_shapes: List[tuple]
    compiled: Any
    setup_s: float = 0.0
    window: Optional[Dict[str, Any]] = None
    trace: Optional[T.Trace] = None
    traced_steps: int = 0
    _hlo: Optional[str] = None

    @property
    def peaks(self) -> Dict[str, float]:
        return spec.peaks(self.device_kind)

    @property
    def hlo_text(self) -> str:
        if self._hlo is None:
            self._hlo = self.compiled.as_text()
        return self._hlo


def _load(path: str, name: str):
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {name} at {path}")
    s = importlib.util.spec_from_file_location(f"bench_{name}", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def read_metrics(ctx: Context, metrics: List[Dict[str, Any]]) -> Dict[str, Dict]:
    out = {}
    for m in metrics:
        value = _load(spec.metric_path(m["name"]), m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def loop(step, state, batch_fn, first: int, *, seconds: float = 0.0, steps: int = 0):
    """Dispatch step t, then wait for step t-1's loss.  Runs until ``seconds``
    have passed (then finishes the queued step) or ``steps`` steps are done.
    Returns (state, t0, completion times, losses)."""
    completions, losses, pending = [], [], None
    t, t0 = first, time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("dispatch_input"):
            batch = batch_fn(t)
        with jax.profiler.TraceAnnotation("dispatch_step"):
            state, met = step(state, batch)
        t += 1
        if pending is not None:
            with jax.profiler.TraceAnnotation("wait_step"):
                pending.block_until_ready()
            completions.append(time.perf_counter())
            losses.append(pending)
        pending = met["loss"]
        done = len(completions) + 1
        if (seconds and time.perf_counter() - t0 >= seconds) or (steps and done >= steps):
            break
    with jax.profiler.TraceAnnotation("wait_step"):
        pending.block_until_ready()
    completions.append(time.perf_counter())
    losses.append(pending)
    return state, t0, completions, losses


def first_steps(cell, step, seed: int, n_checked: int):
    """Drive the fresh state through the checked steps with the window's own
    call and feed.  Returns the state and the program's readings."""
    from bench.program import node_norms

    key = jax.random.key(seed)
    state = cell.init_state(seed)
    b1 = cell.traffic["b1"]
    moment_norms = jax.jit(lambda st: node_norms(cell.first_moment(st)))
    change_norms = jax.jit(lambda params, k: node_norms(jax.tree.map(
        lambda p, p0: p - p0[None], params, cell.initial_params(k))))
    losses, rows, grad_norms = [], [], None
    for t in range(n_checked):
        batch = cell.batch(seed, t)
        rows.append({k: np.asarray(v) for k, v in batch.items()})
        state, met = step(state, batch)
        losses.append(met["loss"])
        if t == 0:
            grad_norms = jax.tree.map(lambda a: np.asarray(a, np.float64) / (1 - b1),
                                      moment_norms(state))
    change = change_norms(state.params, key)
    flat = lambda tree: {jax.tree_util.keystr(p): np.asarray(v, np.float64)
                         for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}
    readings = {"losses": [float(l) for l in losses], "grad_norms": flat(grad_norms),
                "change_norms": flat(change)}
    _check_rows_differ(rows)
    return state, readings, rows


def _check_rows_differ(rows):
    seqs = np.concatenate([r["tokens"].reshape(-1, r["tokens"].shape[-1]) for r in rows])
    if len({s.tobytes() for s in seqs}) != len(seqs):
        raise RuntimeError("the checked steps' input rows are not all different")


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             t_start: float, bench: Optional[Dict] = None, devices=None,
             cfg: Optional[Dict] = None, arch=None,
             step_wrapper: Optional[Callable] = None) -> Dict[str, Any]:
    """One run of ``workload``; returns the result line as a dict.

    ``devices`` defaults to the TPU chips the cell asks for (``NoChip``
    without them).  ``cfg``/``arch`` replace the configuration (the tests'
    small sizes); ``step_wrapper`` wraps the compiled step (the tests' planted
    faults)."""
    from bench.program import Cell
    from bench.reference.train import ReferenceRun

    bench = bench or spec.load_benchmark()
    cell_spec = spec.cell(bench, workload)
    cfg = cfg or cell_spec["config"]
    traffic, limits, chips = cell_spec["traffic"], cell_spec["limits"], cell_spec["chips"]
    if devices is None:
        devices = tpu_devices(chips)
    counter = CompileCounter()

    marks = [("start", t_start)]
    cell = Cell(cfg, traffic, devices, arch=arch)
    marks.append(("build", time.perf_counter()))
    step = cell.compile()
    marks.append(("compile", time.perf_counter()))
    compiled = step
    if step_wrapper is not None:
        step = step_wrapper(step)
    n_checked = traffic["checked_steps"]
    state, prog, rows = first_steps(cell, step, seed, n_checked)
    marks.append(("checked_steps", time.perf_counter()))
    feed = lambda t: cell.batch(seed, t)  # noqa: E731
    state, _, _, _ = loop(step, state, feed, n_checked, steps=traffic["warm_steps"])
    marks.append(("warm_up", time.perf_counter()))
    first = n_checked + traffic["warm_steps"]
    shapes = [tuple(l.shape[1:]) for l in jax.tree.leaves(cell.state_shape.params)]
    ctx = Context(cfg=cfg, traffic=traffic, chips=len(devices),
                  tokens_per_step=traffic["nodes"] * traffic["seqs_per_node"]
                  * traffic["seq_len"],
                  flops_per_token=spec.flops_per_token(cfg, traffic),
                  device_kind=devices[0].device_kind,
                  nodes_per_device=traffic["nodes"] // len(devices),
                  param_shapes=shapes, compiled=compiled)

    counter.armed = True
    ctx.setup_s = time.perf_counter() - t_start
    if traced:
        with tempfile.TemporaryDirectory() as tdir:
            jax.profiler.start_trace(tdir)
            with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
                state, t0, done, losses = loop(step, state, feed, first,
                                               steps=traffic["trace_steps"])
            jax.profiler.stop_trace()
            counter.armed = False
            ctx.trace = T.load(tdir)
        ctx.traced_steps = len(done)
    else:
        state, t0, done, losses = loop(step, state, feed, first, seconds=seconds)
        counter.armed = False
        ctx.window = {"t0": t0, "completions": done, "steps": len(done)}
    if counter.count:
        raise CompiledInWindow(f"{counter.count} trace/compile events inside the "
                               f"window: {sorted(set(counter.seen))}")
    losses = np.asarray(jax.device_get(jnp.stack(losses)))
    failed = int(np.sum(~np.isfinite(losses)))
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    metrics = read_metrics(ctx, cell_spec["per_layer" if traced else "end_to_end"])
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "device_kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result = {"correct": False, "attempted": len(losses), "failed": failed,
              "metrics": metrics, "device": device}
    if traced:
        tr = ctx.trace
        busy = [T.length(T.busy(d, tr.window)) for d in tr.devices]
        device["busy_s"] = sum(busy) / max(len(busy), 1) * 1e-9
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": T.top_ops(tr), "idle_gaps": T.idle_gaps(tr)}

    dense = None
    if "permute_dense_bytes" in limits:
        dense = check.permute_dense_bytes(hlo.permute_operands(ctx.hlo_text))
    # free the program's state before the reference runs on the same chips
    del state, compiled, step, cell, ctx
    t_ref = time.perf_counter()
    ref = ReferenceRun(cfg, traffic, devices).run(seed, rows)
    print("set-up s: " + ", ".join(f"{b[0]} {b[1] - a[1]:.2f}" for a, b in zip(marks, marks[1:]))
          + f"; reference s: {time.perf_counter() - t_ref:.2f}", file=sys.stderr)
    values = check.readings(prog, ref)
    if dense is not None:
        values["permute_dense_bytes"] = dense
    ok, checks = check.judge(values, limits)
    result["correct"] = ok and failed == 0
    result["checks"] = checks
    return result


def print_result(result: Dict[str, Any]) -> None:
    for name, c in result["checks"].items():
        print(f"{name} {c['value']:.6g} limit {c['limit']:.6g}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
