"""The system under test, assembled the way its training driver assembles it.

``build_model`` -> ``init_dist_state`` (one jitted call that makes the weights
on the device from the seed) -> ``make_dist_train_step``, jitted with the node
shardings, donating its state, compiled ahead of time; on several chips over a
``launch.mesh.node_mesh``.  Every step's input comes from the program's own
pipeline, ``data.stacked_node_batches``.  This is the only module of the
benchmark that imports the program.
"""
from __future__ import annotations

import dataclasses
import os
import sys
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.configs import get_config  # noqa: E402
from repro.data import DataConfig, stacked_node_batches  # noqa: E402
from repro.distributed.decentralized import init_dist_state, make_dist_train_step  # noqa: E402
from repro.distributed.gossip import make_gossip_plan  # noqa: E402
from repro.distributed.wire import make_wire_format  # noqa: E402
from repro.launch.mesh import node_mesh  # noqa: E402
from repro.models.api import build_model  # noqa: E402
from repro.optim import make_optimizer  # noqa: E402
from repro.optim.schedules import linear_warmup_cosine  # noqa: E402

def _replace_at(obj, path: str, value):
    """``obj`` with the field at the dotted ``path`` set to ``value``; each
    nested frozen dataclass on the way is rebuilt by ``dataclasses.replace``."""
    head, _, rest = path.partition(".")
    if rest:
        value = _replace_at(getattr(obj, head), rest, value)
    return dataclasses.replace(obj, **{head: value})


def field_at(obj, path: str):
    for part in path.split("."):
        obj = getattr(obj, part)
    return obj


def arch_config(cfg: Dict[str, Any]):
    """The program's ArchConfig for a configuration file, checked against it.

    Each ``reduced`` key is set through its ``program_fields`` path (dotted
    for a nested group: ``ssm.n_heads``, ``moe.n_routed``); then each of the
    file's ``program_fields`` (its key -> the program's field) must hold the
    file's size."""
    arch = get_config(cfg["program_arch"])
    fields = cfg["program_fields"]
    for key in cfg["reduced"]:
        if key not in fields:
            raise ValueError(f"{cfg['name']}: reduced key {key!r} has no program_fields "
                             f"entry, so the program's size cannot be cut to it")
        arch = _replace_at(arch, fields[key], cfg[key])
    for key, attr in fields.items():
        got = field_at(arch, attr)
        if got != cfg[key]:
            raise ValueError(f"{cfg['name']}: the program's {attr} is {got}, "
                             f"the configuration file says {key}={cfg[key]}")
    return arch


class Cell:
    """One configuration under one traffic mix, built on ``devices``."""

    def __init__(self, cfg: Dict[str, Any], traffic: Dict[str, Any], devices, arch=None):
        t = traffic
        self.cfg, self.traffic, self.devices = cfg, traffic, list(devices)
        self.arch = arch if arch is not None else arch_config(cfg)
        self.n = t["nodes"]
        model = build_model(self.arch)
        self.model = model
        self.opt = make_optimizer(t["optimizer"], b1=t["b1"], b2=t["b2"], eps=t["eps"],
                                  weight_decay=t["weight_decay"])
        self.sched = linear_warmup_cosine(t["lr"], t["warmup"], t["horizon"])
        self.plan = make_gossip_plan(t["topology"], self.n)
        self.wire = make_wire_format(t["wire"])
        self.mesh = node_mesh(self.devices) if len(self.devices) > 1 else None
        if self.mesh is None:
            self.node_sh = self.rep_sh = SingleDeviceSharding(self.devices[0])
        else:
            self.node_sh = NamedSharding(self.mesh, P("node"))
            self.rep_sh = NamedSharding(self.mesh, P())
        # one fixed synthetic language (the traffic's ``data``); --seed picks
        # where in it a run reads, so every seed compiles the same input program
        self.data = DataConfig(vocab=self.arch.vocab, seq_len=t["seq_len"],
                               global_batch=self.n * t["seqs_per_node"],
                               n_shards=self.n, seed=t["data"]["seed"],
                               markov_concentration=t["data"]["concentration"])
        remat = t["remat"]
        self.loss_fn = lambda p, b: model.loss(p, b, remat=remat)

        def init_fn(key):
            return init_dist_state(t["algo"], model.init(key), self.plan, self.opt,
                                   wire=self.wire)

        self.state_shape = jax.eval_shape(init_fn, jax.random.key(0))
        self.state_sh = self.shardings(self.state_shape)
        self._init = jax.jit(init_fn, out_shardings=self.state_sh)
        self.batch_shape = jax.eval_shape(
            lambda: stacked_node_batches(self.data, 0, self.arch))
        self.batch_sh = self.shardings(self.batch_shape)
        self.step_jit = jax.jit(
            make_dist_train_step(self.loss_fn, t["algo"], self.opt, self.wire,
                                 self.plan, self.sched, mesh=self.mesh),
            in_shardings=(self.state_sh, self.batch_sh),
            out_shardings=(self.state_sh, self.rep_sh), donate_argnums=0)

    def shardings(self, tree):
        return jax.tree.map(
            lambda l: self.node_sh if l.ndim and l.shape[0] == self.n else self.rep_sh,
            tree)

    def compile(self):
        """AOT-compile the step for the state and batch shapes."""
        return self.step_jit.lower(self.state_shape, self.batch_shape).compile()

    def init_state(self, seed: int):
        return self._init(jax.random.key(seed))

    def batch(self, seed: int, t: int):
        """The program's input for step ``t`` of a run with ``seed``."""
        b = stacked_node_batches(self.data, seed % self.traffic["data"]["step_span"] + t,
                                 self.arch)
        return jax.device_put(b, self.batch_sh)

    def first_moment(self, state):
        """The optimizer's first moment: after one step, (1 - b1) times the
        gradient that it was given."""
        return state.opt.m

    def initial_params(self, key):
        """The model's initial (single-node) params for a seed's key."""
        return self.model.init(key)


def node_norms(tree):
    """Per-leaf, per-node L2 norms of a node-stacked tree, on the device."""
    return jax.tree.map(
        lambda l: jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32)),
                                   axis=tuple(range(1, l.ndim)))), tree)
