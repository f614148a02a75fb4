"""Compile-ahead tests: each codec kernel compiled for a described TPU v5e chip.

No chip is attached.  The TPU compiler installed beside jax compiles for a
described ``v5e:2x2`` topology, so Mosaic's refusals — unsupported casts and
reductions, scoped-VMEM overflows from too large a row tile — fail here, at
the shapes the main path hands the kernels: the largest leaf of mamba2-370m
(the 50,432 x 1,024 embedding, stacked over two gossip nodes).

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and every test
worker imports every test file.  The persistent compilation cache is off
around these compiles, since a compile for a described chip cannot be read
back without one.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import lowrank, quant

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
def test_unpack_dequant_axpy_compiles(one_chip, bits):
    _compile(functools.partial(quant.unpack_dequant_axpy_2d, bits=bits,
                               weight=0.5, acc_weight=0.5),
             one_chip, ((_ROWS, 1024 * bits // 32), _U32), ((_ROWS, 1), _F32),
             ((_ROWS, 1024), _F32))


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
