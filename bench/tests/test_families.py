"""How a configuration's family plugs into the plain reference, and how a
configuration file maps onto the program's ArchConfig."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec
from bench.program import arch_config
from bench.reference import model
from bench.reference.model import Numerics
from bench.reference.train import ReferenceRun
from bench.tests import tiny

SEED = 2 ** 33 + 21         # over 32 bits, as a benchmark run's seeds may be


def _rows(cfg, traffic, seed):
    """One step's rows: (nodes, rows, S) tokens and labels from the seed."""
    shape = (traffic["nodes"], traffic["seqs_per_node"], traffic["seq_len"])
    tok = np.asarray(jax.random.randint(jax.random.key(seed), shape, 0, cfg["vocab"]),
                     np.int32)
    return {"tokens": tok, "labels": np.roll(tok, -1, axis=-1)}


def _shared_init(fam, cfg, key):
    """The shared model's parameters, put together from the public helpers."""
    ks = jax.random.split(key, 8)
    return dict(model.head_params(cfg, ks),
                blocks=model.stack_init(cfg, ks[6], cfg["n_layers"], fam.block_init))


def _shared_loss(fam, cfg, params, tokens, labels, nx):
    """The shared model's loss, put together from the public helpers."""
    h = model.scan_blocks(cfg, model.embed(params, tokens, nx), params["blocks"],
                          fam.block, nx)
    return model.head_loss(params, h, labels, nx)


@pytest.fixture
def stacked_cell():
    """A stacked cell at the tests' small sizes, one row a node, and one
    step's rows."""
    _, cfg, _, traffic = tiny.small(tiny.accepted(chips=1)[0])
    traffic = dict(traffic, seqs_per_node=1)
    return cfg, traffic, [_rows(cfg, traffic, SEED)]


def _first_row(rows):
    return (jnp.asarray(rows[0][k][0, 0]) for k in ("tokens", "labels"))


def test_a_family_that_owns_its_model_is_used_by_the_reference_and_its_trainer(
        monkeypatch, stacked_cell):
    cfg, traffic, rows = stacked_cell
    fam, nx, key = model.family(cfg), Numerics(), jax.random.key(SEED)
    params = model.init_params(cfg, key)
    tok, lab = _first_row(rows)
    loss = float(model.sequence_loss(cfg, params, tok, lab, nx))
    base = ReferenceRun(cfg, traffic, jax.devices()[:1]).run(SEED, rows)

    called = []

    def own_init(c, k):
        called.append("init_params")
        return _shared_init(fam, c, k)

    def own_loss(c, p, t, l, n):
        called.append("sequence_loss")
        return 2 * _shared_loss(fam, c, p, t, l, n)

    owns = types.SimpleNamespace(block_init=fam.block_init, block=fam.block,
                                 init_params=own_init, sequence_loss=own_loss)
    monkeypatch.setattr(model, "family", lambda c: owns)
    for a, b in zip(jax.tree.leaves(model.init_params(cfg, key)), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(model.sequence_loss(cfg, params, tok, lab, nx)) == 2 * loss
    assert called == ["init_params", "sequence_loss"]
    # the trainer takes init_params and sequence_loss from model.py; its
    # first loss is read before any update, and doubling is exact
    own = ReferenceRun(cfg, traffic, jax.devices()[:1]).run(SEED, rows)
    assert {"init_params", "sequence_loss"} <= set(called[2:])
    assert own["losses"][0] == 2 * base["losses"][0]


def test_a_family_with_a_block_alone_takes_the_shared_model(monkeypatch, stacked_cell):
    cfg, traffic, rows = stacked_cell
    fam, nx, key = model.family(cfg), Numerics(), jax.random.key(SEED)
    params = model.init_params(cfg, key)
    tok, lab = _first_row(rows)
    loss = float(model.sequence_loss(cfg, params, tok, lab, nx))
    base = ReferenceRun(cfg, traffic, jax.devices()[:1]).run(SEED, rows)

    block_only = types.SimpleNamespace(block_init=fam.block_init, block=fam.block)
    monkeypatch.setattr(model, "family", lambda c: block_only)
    for a, b in zip(jax.tree.leaves(model.init_params(cfg, key)), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(model.sequence_loss(cfg, params, tok, lab, nx)) == loss
    assert float(_shared_loss(fam, cfg, params, tok, lab, nx)) == loss
    assert ReferenceRun(cfg, traffic, jax.devices()[:1]).run(SEED, rows)["losses"] == \
        base["losses"]


@pytest.mark.parametrize("workload", tiny.accepted(chips=1) + tiny.accepted(chips=4))
def test_accepted_configurations_map_as_their_flat_cuts(workload):
    """Each accepted configuration's ArchConfig is the program's with every
    ``reduced`` key set by name, as before dotted paths were taken."""
    from repro.configs import get_config

    cfg = spec.cell(spec.load_benchmark(), workload)["config"]
    want = dataclasses.replace(get_config(cfg["program_arch"]),
                               **{k: cfg[k] for k in cfg["reduced"]})
    assert arch_config(cfg) == want


def _moe_cfg(**over):
    cfg = {"name": "deepseek-moe-16b.e8", "program_arch": "deepseek-moe-16b",
           "n_layers": 28, "d_model": 2048, "n_routed": 8, "top_k": 6,
           "program_fields": {"n_layers": "n_layers", "d_model": "d_model",
                              "n_routed": "moe.n_routed", "top_k": "moe.top_k"},
           "reduced": {"n_routed": {"published": 64, "here": 8}}}
    cfg.update(over)
    return cfg


def test_a_dotted_reduced_key_cuts_a_nested_group():
    from repro.configs import get_config

    published = get_config("deepseek-moe-16b")
    arch = arch_config(_moe_cfg())
    assert arch.moe.n_routed == 8 and published.moe.n_routed == 64
    assert arch.moe == dataclasses.replace(published.moe, n_routed=8)
    assert dataclasses.replace(arch, moe=published.moe) == published


def test_a_reduced_key_with_no_program_field_is_refused():
    cfg = _moe_cfg(n_shared=1, reduced={"n_shared": {"published": 2, "here": 1}})
    with pytest.raises(ValueError, match="n_shared"):
        arch_config(cfg)


def test_a_cut_the_program_does_not_hold_is_refused():
    with pytest.raises(ValueError, match="moe.top_k"):
        arch_config(_moe_cfg(top_k=4))


def test_hybrid_reference_init_matches_the_program_with_a_tail():
    """Two whole periods and a tail of one Mamba-2 block: the reference's
    tree and weights are the program's, leaf for leaf, and its float32 loss
    lies near the program's bfloat16 one."""
    from repro.models.api import build_model

    _, cfg, arch, _ = tiny.small(tiny.HYBRID_CELL)
    arch = dataclasses.replace(arch, n_layers=5)
    cfg = dict(cfg, n_layers=5)
    assert model.family(cfg).layout(cfg) == (2, 1, 1)
    key = jax.random.key(SEED)
    prog = build_model(arch)
    ref = model.init_params(cfg, key)
    got = prog.init(key)
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tok = jax.random.randint(key, (1, 64), 0, cfg["vocab"])
    lab = jnp.roll(tok, -1, axis=-1)
    prog_loss = float(prog.loss(got, {"tokens": tok, "labels": lab})[0])
    ref_loss = float(model.sequence_loss(cfg, ref, tok[0], lab[0], Numerics()))
    # bfloat16 activations against float32 at HIGHEST
    np.testing.assert_allclose(prog_loss, ref_loss, rtol=1e-2)


def test_hybrid_flops_compose_the_two_families_counts():
    hybrid = tiny.full(tiny.HYBRID_CELL)
    cfg, traffic = hybrid["config"], hybrid["traffic"]
    no_period = dict(cfg, hybrid_period=cfg["n_layers"] + 1)
    ssm_only = dict(cfg, family="ssm", n_heads=cfg["ssm_heads"])
    assert spec.flops_per_token(no_period, traffic) == spec.flops_per_token(ssm_only, traffic)
    # one period's shared block costs what a dense layer of its widths costs
    one = dict(cfg, n_layers=cfg["hybrid_period"])
    dense_layer = dict(cfg, family="dense", n_layers=1, vocab=0)
    ssm_part = dict(ssm_only, n_layers=cfg["hybrid_period"] - 1)
    assert spec.flops_per_token(one, traffic) == pytest.approx(
        spec.flops_per_token(dense_layer, traffic) + spec.flops_per_token(ssm_part, traffic))
