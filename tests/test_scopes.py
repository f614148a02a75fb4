"""The train step's named scopes: every phase an algorithm runs is named in
the compiled step's ``op_name`` metadata, and the scopes change nothing else."""
import contextlib
import dataclasses
import re

import jax
import pytest

from repro.configs import get_config
from repro.data import DataConfig, stacked_node_batches
from repro.distributed.decentralized import PHASES, init_dist_state, make_dist_train_step
from repro.distributed.gossip import make_gossip_plan
from repro.models.api import build_model
from repro.optim import make_optimizer
from repro.optim.schedules import linear_warmup_cosine

N_NODES = 2


def compiled_text(algo, wire, *, remat=True):
    """Optimized HLO of the step at a tiny mamba2 size, two nodes on a ring."""
    arch = dataclasses.replace(get_config("mamba2-370m").reduced(), n_layers=1)
    model = build_model(arch)
    opt = make_optimizer("adamw")
    plan = make_gossip_plan("ring", N_NODES)
    state = jax.eval_shape(
        lambda k: init_dist_state(algo, model.init(k), plan, opt, wire=wire),
        jax.random.key(0))
    dc = DataConfig(vocab=arch.vocab, seq_len=16, global_batch=N_NODES,
                    n_shards=N_NODES, seed=0)
    batch = jax.eval_shape(lambda: stacked_node_batches(dc, 0, arch))
    step = make_dist_train_step(lambda p, b: model.loss(p, b, remat=remat), algo, opt,
                                wire, plan, linear_warmup_cosine(1e-3, 2, 10))
    return jax.jit(step).lower(state, batch).compile().as_text()


def op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def strip_metadata(text):
    """The program without its debug metadata: each instruction's
    ``metadata={...}`` and the file, function and stack-frame tables."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    tables = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
    return "\n".join(l for l in text.splitlines()
                     if l not in tables and not re.match(r"\d+ ", l))


GOSSIP = ("encode", "permute", "decode", "mix")


@pytest.mark.parametrize("algo,wire,scopes", [
    ("cpsgd", None, ("fwd_bwd", "optimizer", "mix", "step_metrics")),
    ("dpsgd", None, ("fwd_bwd", "optimizer", "permute", "mix", "step_metrics")),
    ("dcd", "quant:4", ("fwd_bwd", "optimizer", *GOSSIP, "step_metrics")),
    ("ecd", "quant:4", ("fwd_bwd", "optimizer", *GOSSIP, "step_metrics")),
    ("choco", "quant:4", ("fwd_bwd", "optimizer", *GOSSIP, "step_metrics")),
    ("deepsqueeze", "quant:4", ("fwd_bwd", "optimizer", *GOSSIP, "step_metrics")),
])
def test_compiled_step_names_every_phase_it_runs(algo, wire, scopes):
    names = op_names(compiled_text(algo, wire))
    parts = {p for n in names for p in n.split("/")}
    assert set(scopes) <= set(PHASES)
    assert set(scopes) <= parts, sorted(set(scopes) - parts)
    assert not (set(PHASES) - set(scopes)) & parts
    model = [n for n in names if "fwd_bwd" in n.split("/")]
    remat = [n for n in model if "rematted_computation" in n]
    backward = [n for n in model if "transpose(" in n and n not in remat]
    forward = [n for n in model if "transpose(" not in n and n not in remat]
    assert remat and backward and forward


def test_scopes_change_only_metadata(monkeypatch):
    scoped = compiled_text("dcd", "quant:4")
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = compiled_text("dcd", "quant:4")
    assert "fwd_bwd" in " ".join(op_names(scoped))
    assert "fwd_bwd" not in " ".join(op_names(plain))
    assert strip_metadata(scoped) == strip_metadata(plain)
