"""End-to-end decentralized training driver.

On one device every gossip node is a slice of one stacked program.  With
several devices and ``n_nodes`` a multiple of their count, the same program
runs with the node axis sharded over a 1-D ``node`` mesh, one node (or an
equal share of nodes) per chip, so each gossip shift is a real
collective-permute.  Used by examples/train_lm.py and ``chip_smoke.py``.
``--no-reduced`` trains the published widths.

The gossip wire format and topology are specs, not flags-per-codec:
``--wire quant:8`` / ``--wire sparse:0.25:topk`` / ``--wire fp16`` /
``--wire adaptive:4096:small=fp16:large=quant:4`` pick any registered
:class:`~repro.distributed.wire.WireFormat`; ``--topology`` picks any
:func:`~repro.distributed.gossip.make_gossip_plan` name (ring, chain,
torus, torus2d, star, full — or the round schedules ``full_logn``, the dense
average at O(log n) permutes per step, and ``exp``, the time-varying one-peer
exponential graph at ONE permute per step).

``--phase-plan "0@exp@sign;150@full_logn@quant:8"`` overrides both with a
step-indexed schedule (:class:`~repro.netsim.controller.PhasePlan` — emit one
with :func:`~repro.netsim.controller.plan_phases`): the jitted step is rebuilt
at each boundary and the gossip aux trees resync to the new plan/wire via
:func:`~repro.distributed.decentralized.rekey_dist_state`.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import time
from typing import Any, Dict, Optional, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.checkpoint import latest_step, restore, save
from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.data import DataConfig, pipeline_stats, stacked_node_batches
from repro.distributed.decentralized import (
    DistState,
    FAMILIES,
    init_dist_state,
    make_dist_train_step,
    rekey_dist_state,
)
from repro.analysis.jaxpr_checks import jit_compile_count
from repro.distributed.failures import make_drop_spec
from repro.distributed.gossip import make_gossip_plan
from repro.distributed.wire import decode_stats, make_wire_format
from repro.launch.mesh import node_mesh
from repro.models.api import build_model
from repro.models.attention import attention_stats
from repro.optim import make_optimizer
from repro.optim.schedules import linear_warmup_cosine


@dataclasses.dataclass
class TrainConfig:
    arch: Optional[str] = None          # assigned arch id, or None for custom cfg
    algo: str = "dcd"                   # cpsgd | dpsgd | naive | dcd | ecd | choco | deepsqueeze
    wire: str = "quant:8"               # gossip wire-format spec (make_wire_format)
    gamma: float = 0.5                  # CHOCO consensus stepsize, in (0, 1]
    topology: str = "ring"              # gossip plan name (make_gossip_plan)
    phase_plan: Optional[str] = None    # "start@topology@wire;..." overrides wire+topology
    n_nodes: int = 8
    seq_len: int = 256
    global_batch: int = 32
    steps: int = 300
    lr: float = 3e-3
    warmup: int = 20
    optimizer: str = "adamw"
    drop_rate: float = 0.0              # per-edge gossip drop probability (0 = reliable)
    drop_salt: int = 0                  # stream salt for the deterministic drop mask
    seed: int = 0
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 100
    log_every: int = 10
    reduced: bool = True                # width-cut config (CPU-scale); --no-reduced for published widths
    remat: bool = False                 # recompute each layer in the backward pass (published widths)


# Persistent compilation cache when JAX_COMPILATION_CACHE_DIR is unset: a fixed
# directory in the checkout (git-ignored), since the cache key includes it.
_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set here; otherwise the cache is the checkout's
    ``.jax_cache/``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_CACHE_DIR))
    return str(_CACHE_DIR)


def run_training(cfg: ArchConfig, tc: TrainConfig,
                 devices: Optional[Sequence[Any]] = None) -> Dict[str, Any]:
    """Train ``cfg`` under ``tc``; returns the history dict.

    ``devices`` (default: every device of this process): with more than one,
    and ``tc.n_nodes`` a multiple of their count, the node axis is sharded over
    a 1-D ``node`` mesh of them; otherwise all nodes run stacked on the first.
    Besides the logged metrics the history holds ``compile_s`` and
    ``compiled_steps`` (one AOT-compiled step per phase segment: memory
    analysis and HLO) and the final ``state``."""
    from repro.netsim.controller import Phase, PhasePlan

    model = build_model(cfg)
    opt = make_optimizer(tc.optimizer, **({"weight_decay": 0.01} if tc.optimizer == "adamw" else {}))
    sched = linear_warmup_cosine(tc.lr, tc.warmup, tc.steps)
    drop = make_drop_spec(tc.drop_rate, salt=tc.drop_salt)
    loss_fn = lambda p, b: model.loss(p, b, remat=tc.remat)

    devices = list(jax.devices() if devices is None else devices)
    mesh = node_mesh(devices) \
        if len(devices) > 1 and tc.n_nodes % len(devices) == 0 else None
    if mesh is None:
        node_sh = rep_sh = SingleDeviceSharding(devices[0])
    else:
        node_sh, rep_sh = NamedSharding(mesh, P("node")), NamedSharding(mesh, P())

    def shardings(tree):
        # stacked leaves split over the node axis; scalars are replicated
        return jax.tree.map(
            lambda l: node_sh if l.ndim and l.shape[0] == tc.n_nodes else rep_sh,
            tree)

    def wire_of(phase: Phase):
        return make_wire_format(phase.wire) if FAMILIES[tc.algo].encodes else None

    # one static {topology, wire} is just a one-phase plan — the phase loop
    # below IS the old single-segment loop in that case
    pplan = PhasePlan.parse(tc.phase_plan) if tc.phase_plan \
        else PhasePlan((Phase(0, tc.topology, tc.wire),))
    segments = pplan.segments(tc.steps)

    params0 = model.init(jax.random.key(tc.seed))
    start = 0
    resume_step = latest_step(tc.ckpt_dir) if tc.ckpt_dir else None
    # a checkpoint at step s was written while executing under the phase that
    # governs step s-1 — the restore template must match THAT phase's aux keys
    init_phase = pplan.phase_at(max(0, (resume_step or 0) - 1))
    init_plan = make_gossip_plan(init_phase.topology, tc.n_nodes)

    def init_fn(p):
        return init_dist_state(tc.algo, p, init_plan, opt, drop=drop,
                               wire=wire_of(init_phase))

    # built under jit so that no two state leaves share a buffer (the step
    # donates the state, and the replica trees start equal to the params)
    state = jax.jit(init_fn, out_shardings=shardings(
        jax.eval_shape(init_fn, params0)))(params0)
    del params0
    if resume_step is not None:
        state, manifest = restore(tc.ckpt_dir, state, resume_step)
        state = jax.device_put(state, shardings(state))
        start = manifest["step"]
        print(f"resumed from step {start}")

    dc = DataConfig(vocab=cfg.vocab, seq_len=tc.seq_len, global_batch=tc.global_batch,
                    n_shards=tc.n_nodes, seed=tc.seed)

    def batch_at(t):
        batch = stacked_node_batches(dc, t, cfg)
        return jax.device_put(batch, shardings(batch))

    hist = {"step": [], "loss": [], "consensus": [],
            "phases": pplan.records(), "compile_s": [], "compiled_steps": []}
    t0 = time.time()
    for seg_start, seg_stop, phase in segments:
        if seg_stop <= start:
            continue
        plan = make_gossip_plan(phase.topology, tc.n_nodes)
        if seg_start > 0 and seg_start >= start:
            # phase boundary: resync aux to the new plan/wire (pure function
            # of params, so resume-at-boundary == run-through-boundary)
            state = jax.jit(
                lambda st: rekey_dist_state(st, tc.algo, plan, drop=drop,
                                            wire=wire_of(phase)))(state)
            print(f"phase switch @ step {seg_start}: "
                  f"topology={phase.topology} wire={phase.wire}", flush=True)
        first = max(seg_start, start)
        with jax.profiler.TraceAnnotation("dispatch_input"):
            batch = batch_at(first)
        state_sh = shardings(state)
        step_fn = jax.jit(
            make_dist_train_step(loss_fn, tc.algo, opt, wire_of(phase), plan,
                                 sched, mesh=mesh, drop=drop, gamma=tc.gamma),
            in_shardings=(state_sh, shardings(batch)),
            out_shardings=(state_sh, rep_sh), donate_argnums=0)
        tc0 = time.time()
        hist["compiled_steps"].append(step_fn.lower(state, batch).compile())
        hist["compile_s"].append(time.time() - tc0)
        if len(hist["compiled_steps"]) == 1:
            print(f"attention cores: {attention_stats()}", flush=True)
            print(f"decode-axpy leaves: {decode_stats()}", flush=True)
        ran_steps = 0
        for t in range(first, seg_stop):
            # host spans on the profiler's clock, named as the benchmark's
            # (see README "Profiling a run")
            with jax.profiler.StepTraceAnnotation("train", step_num=t):
                if t > first:
                    with jax.profiler.TraceAnnotation("dispatch_input"):
                        batch = batch_at(t)
                with jax.profiler.TraceAnnotation("dispatch_step"):
                    state, metrics = step_fn(state, batch)
                ran_steps += 1
                if (t + 1) % tc.log_every == 0 or t == tc.steps - 1:
                    # reading the metrics waits for every queued step
                    with jax.profiler.TraceAnnotation("log_sync"):
                        hist["step"].append(t + 1)
                        hist["loss"].append(float(metrics["loss"]))
                        hist["consensus"].append(float(metrics["consensus"]))
                        print(f"step {t+1:5d} loss={metrics['loss']:.4f} "
                              f"consensus={metrics['consensus']:.3e} "
                              f"lr={metrics['lr']:.2e}", flush=True)
                if tc.ckpt_dir and (t + 1) % tc.ckpt_every == 0:
                    with jax.profiler.TraceAnnotation("checkpoint"):
                        save(tc.ckpt_dir, t + 1, state,
                             metadata={"loss": float(metrics["loss"])})
        if ran_steps:
            # retrace guard: the segment's freshly-jitted step must have
            # compiled exactly once — a higher count means every step paid a
            # silent retrace (shape/dtype/weak-type drift at the boundary)
            n_compiles = jit_compile_count(step_fn)
            if n_compiles != 1:
                raise RuntimeError(
                    f"retrace guard: phase segment [{seg_start}, {seg_stop}) "
                    f"compiled {n_compiles}x over {ran_steps} steps (expected "
                    "exactly 1) — step inputs must be shape/dtype-stable "
                    "within a segment")
    hist["wall_s"] = time.time() - t0
    print(f"input pipeline: {pipeline_stats(dc, cfg)}", flush=True)
    hist["final_loss"] = hist["loss"][-1]
    hist["state"] = state
    return hist


def main():
    ap = argparse.ArgumentParser()
    for f in dataclasses.fields(TrainConfig):
        if f.type in ("int", int):
            ap.add_argument(f"--{f.name.replace('_','-')}", type=int, default=f.default)
        elif f.type in ("float", float):
            ap.add_argument(f"--{f.name.replace('_','-')}", type=float, default=f.default)
        elif f.type in ("bool", bool):
            ap.add_argument(f"--{f.name.replace('_','-')}",
                            action=argparse.BooleanOptionalAction, default=f.default)
        else:
            ap.add_argument(f"--{f.name.replace('_','-')}", default=f.default)
    args = ap.parse_args()
    tc = TrainConfig(**{f.name: getattr(args, f.name) for f in dataclasses.fields(TrainConfig)})
    use_compile_cache()
    cfg = get_config(tc.arch) if tc.arch else get_config("granite-3-2b")
    if tc.reduced:
        cfg = cfg.reduced()
    hist = run_training(cfg, tc)
    print(json.dumps({k: v for k, v in hist.items() if isinstance(v, (int, float))},
                     indent=2))


if __name__ == "__main__":
    main()
