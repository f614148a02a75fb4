"""Compile-ahead tests: each codec kernel compiled for a described TPU v5e chip.

No chip is attached.  The TPU compiler installed beside jax compiles for a
described ``v5e:2x2`` topology, so Mosaic's refusals — unsupported casts and
reductions, scoped-VMEM overflows from too large a row tile — fail here, at
the shapes the main path hands the kernels: the largest leaf of mamba2-370m
(the 50,432 x 1,024 embedding, stacked over two gossip nodes), and one
granite-3-2b layer at its published widths for the attention kernel, whose
core is also compiled at the widest head groups of the repo's models.

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and every test
worker imports every test file.  The persistent compilation cache is off
around these compiles, since a compile for a described chip cannot be read
back without one.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.jaxpr_checks import decode_sites
from repro.configs import get_config
from repro.distributed import wire as wires
from repro.distributed.decentralized import init_dist_state, make_dist_train_step
from repro.distributed.gossip import make_gossip_plan
from repro.kernels import attention as flash
from repro.kernels import lowrank, quant
from repro.kernels import ops as kops
from repro.models import attention as attn
from repro.models.api import build_model
from repro.optim import make_optimizer
from repro.optim.schedules import constant

_CFG = get_config("mamba2-370m")
_NODES = 2
_VOCAB = _CFG.vocab_padded                 # 50,432 rows of the embedding
_ROWS = _NODES * _VOCAB                    # 1024-wide quant/sign blocks
_SPARSE_ROWS = _ROWS * _CFG.d_model // 128  # 128-wide sparse blocks


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(os.environ, "TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler, or the library is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1, \
        "the kernel did not lower through Mosaic"


_F32, _U32 = jnp.float32, jnp.uint32
_SEED = ((1,), _U32)


@pytest.mark.parametrize("bits", [4, 3])
def test_quantize_pack_compiles(one_chip, bits):
    _compile(functools.partial(quant.quantize_pack_2d, bits=bits),
             one_chip, ((_ROWS, 1024), _F32), _SEED)


def test_quantize_8bit_compiles(one_chip):
    _compile(functools.partial(quant.quantize_2d, bits=8),
             one_chip, ((_ROWS, 1024), _F32), _SEED)


@pytest.mark.parametrize("mode", quant.SPARSE_MODES)
def test_sparse_select_pack_compiles(one_chip, mode):
    _compile(functools.partial(quant.sparse_select_pack_2d, p=0.25, mode=mode),
             one_chip, ((_SPARSE_ROWS, 128), _F32), _SEED)


def test_sign_pack_compiles(one_chip):
    _compile(quant.sign_pack_2d, one_chip, ((_ROWS, 1024), _F32))


@pytest.mark.parametrize("bits", [4, 3])
@pytest.mark.parametrize("rows,last", [(_ROWS, 1024), (_NODES * _CFG.d_model, _VOCAB)],
                         ids=["embed", "lm_head"])
def test_unpack_dequant_axpy_compiles(one_chip, bits, rows, last):
    """The embedding's rows are one block each; the head's rows are 50
    blocks, the last a partial edge block (50,432 = 49 x 1024 + 256)."""
    nblk = -(-last // 1024)
    _compile(functools.partial(quant.unpack_dequant_axpy, bits=bits,
                               weight=0.5, acc_weight=0.5),
             one_chip, ((rows, nblk, 1024 * bits // 32), _U32), ((rows, nblk), _F32),
             ((rows, last), _F32))


def test_sparse_scatter_axpy_compiles(one_chip):
    k, _, _, words = quant.sparse_geometry(128, 0.25)
    _compile(functools.partial(quant.sparse_scatter_axpy_2d, weight=0.5,
                               acc_weight=0.5),
             one_chip, ((_SPARSE_ROWS, k), _F32), ((_SPARSE_ROWS, words), _U32),
             ((_SPARSE_ROWS, 128), _F32))


def test_unpack_sign_axpy_compiles(one_chip):
    _compile(functools.partial(quant.unpack_sign_axpy_2d, weight=0.5,
                               acc_weight=0.5),
             one_chip, ((_ROWS, 32), _U32), ((_ROWS, 1), _F32),
             ((_ROWS, 1024), _F32))


@pytest.mark.parametrize("m,n", [(_VOCAB, _CFG.d_model), (_CFG.d_model, _VOCAB)],
                         ids=["embed", "lm_head"])
def test_lowrank_axpy_compiles(one_chip, m, n):
    _compile(functools.partial(lowrank.lowrank_axpy_2d, weight=0.5,
                               acc_weight=0.5),
             one_chip, ((m, 2), _F32), ((n, 2), _F32), ((m, n), _F32))


def test_attention_kernel_compiles_in_a_granite_layer(one_chip, monkeypatch):
    """The gradient of one granite-3-2b layer (d 2048, 32 query over 8 KV
    heads of 64, S 2048) with remat, vmapped over two nodes: its attention
    core lowers to Mosaic kernels named under the ``attention`` scope in the
    forward and the backward, and no S x S score tensor is left."""
    monkeypatch.setattr(kops, "interpret_kernels", lambda: False)
    nodes, seq = 2, 2048
    cfg = dataclasses.replace(get_config("granite-3-2b"), n_layers=1, vocab=256)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd) == (2048, 32, 8, 64)
    model = build_model(cfg)
    stacked = lambda l: jax.ShapeDtypeStruct((nodes,) + l.shape, l.dtype,  # noqa: E731
                                             sharding=one_chip)
    params = jax.tree.map(stacked, jax.eval_shape(model.init, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((nodes, 1, seq), jnp.int32, sharding=one_chip)
    grad = jax.vmap(jax.grad(lambda p, t: model.loss(
        p, {"tokens": t, "labels": t}, remat=True)[0]))
    text = jax.jit(grad).lower(params, tokens).compile().as_text()

    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and re.match(r"\s*%flash_attention_", line)]
    names = [re.search(r'op_name="([^"]*)"', line).group(1) for line in kernels]
    scoped = [n for n in names if "attention" in n.split("/")]
    assert scoped == names and names, names
    assert [n for n in scoped if "transpose(" not in n], "no forward kernel"
    assert [n for n in scoped if "transpose(" in n], "no backward kernel"
    # an S x S tensor of at least one head group's scores (4 heads)
    for dims in re.findall(rf"(?:f32|bf16)\[([\d,]*{seq},{seq})\]", text):
        assert np.prod([int(d) for d in dims.split(",")]) < 4 * seq * seq, dims


@pytest.mark.parametrize("name,block", [("mistral-large-123b", 256), ("internvl2-76b", 512)])
def test_attention_kernel_compiles_at_wide_head_groups(one_chip, monkeypatch, name, block):
    """The attention core's remat gradient at a model's head widths (12 and 8
    query heads of 128 a group), S 2048, vmapped over two nodes: it takes the
    kernel at the tile ``block_size`` picks from the scoped VMEM, all three
    kernels lower through Mosaic, and no S x S tensor is left."""
    monkeypatch.setattr(kops, "interpret_kernels", lambda: False)
    nodes, seq = 2, 2048
    cfg = get_config(name)
    groups = cfg.n_heads // cfg.n_kv_heads
    assert attn.core_path(seq, groups, cfg.hd, None) == "kernel"
    assert flash.block_size(seq, groups, cfg.hd) == block
    core = jax.checkpoint(lambda q, k, v: flash.causal_gqa(q, k, v, interpret=False),
                          policy=jax.checkpoint_policies.nothing_saveable)
    grad = jax.grad(lambda q, k, v: jnp.sum(jax.vmap(core)(q, k, v).astype(jnp.float32)),
                    argnums=(0, 1, 2))
    q = jax.ShapeDtypeStruct((nodes, 1, seq, cfg.n_heads, cfg.hd), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((nodes, 1, seq, cfg.n_kv_heads, cfg.hd), jnp.bfloat16,
                              sharding=one_chip)
    text = jax.jit(grad).lower(q, kv, kv).compile().as_text()

    kernels = {m for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               for m in re.findall(r"flash_attention_(fwd|dkv|dq)", line.split("=")[0])}
    assert kernels == {"fwd", "dkv", "dq"}, kernels
    assert not re.search(rf"\[[\d,]*{seq},{seq}\]", text)


_RELAYOUT = re.compile(r"^\s*(?:ROOT\s+)?%(\S+)\s*=\s*f32\[([\d,]*)\]\S*\s+"
                       r"(reshape|pad|copy|transpose|fusion)\(.*op_name=\"([^\"]*)\"")


def test_granite_step_decodes_each_leaf_in_its_own_layout(one_chip, monkeypatch):
    """A DCD ``quant:4`` step over one granite-3-2b layer at its published
    widths and a 6,400-row vocabulary (the head's last block is partial),
    two nodes on a ring, compiled for a described v5e: every leaf decodes
    through the fused kernel over its own row view, so no f32 array of a
    kernel leaf's size, or of its size padded to whole blocks, is reshaped,
    padded or copied under the ``decode`` scope, and ``decode_stats``
    counts each traced decode as ``rows``."""
    monkeypatch.setattr(kops, "interpret_kernels", lambda: False)
    nodes, seq = 2, 512
    cfg = dataclasses.replace(get_config("granite-3-2b"), n_layers=1, vocab=6400)
    model = build_model(cfg)
    plan = make_gossip_plan("ring", nodes)
    wire = wires.make_wire_format("quant:4")
    opt = make_optimizer("adamw")
    step = make_dist_train_step(lambda p, b: model.loss(p, b, remat=True), "dcd", opt,
                                wire, plan, constant(1e-4))
    state = jax.eval_shape(lambda k: init_dist_state("dcd", model.init(k), plan, opt,
                                                     wire=wire), jax.random.key(0))
    state = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                        sharding=one_chip), state)
    tokens = jax.ShapeDtypeStruct((nodes, 1, seq), jnp.int32, sharding=one_chip)
    before = wires.decode_stats()
    text = jax.jit(step).lower(state, {"tokens": tokens, "labels": tokens}).compile().as_text()
    after = wires.decode_stats()

    leaves = jax.tree.leaves(state.params)
    blocks = [wire._block_for(l.shape[-1]) for l in leaves]
    sizes = {l.size for l in leaves} | {l.size // l.shape[-1] * -(-l.shape[-1] // b) * b
                                        for l, b in zip(leaves, blocks)}
    traced = decode_sites("dcd", plan) * len(leaves)
    assert {k: after[k] - before[k] for k in after} == {"rows": traced, "jnp": 0}
    assert len(re.findall(r"%unpack_dequant_axpy[.\d]* = ", text)) == traced
    copies = [m.group(0)[:160] for m in map(_RELAYOUT.match, text.splitlines())
              if m and "decode" in m.group(4).split("/")
              and (m.group(3) != "fusion" or re.match(r"(copy|transpose)", m.group(1)))
              and np.prod([int(d) for d in m.group(2).split(",") if d]) in sizes]
    assert not copies, copies
