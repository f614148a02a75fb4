"""The Pallas flash kernel for causal GQA self-attention (``kernels/attention.py``),
run interpreted on the CPU against the dense ``_sdpa`` it replaces on a TPU;
the rule that picks an attention core's path; and the tally of traced cores.

Tolerance: the kernel and ``_sdpa`` both take bfloat16 q, k and v and round
their outputs and gradients to bfloat16 (a relative step of 2**-8), and they
sum in different orders, so they differ by rounding: 4-5e-3 of the norm of
``_sdpa``'s output, dq, dk and dv at these sizes.  The limit is 8e-3 of that
norm; a gradient off by 1 % reads 1.1e-2, and a wrong mask or head mapping
tenths.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.data import DataConfig, stacked_node_batches
from repro.distributed.decentralized import init_dist_state, make_dist_train_step
from repro.distributed.gossip import make_gossip_plan
from repro.distributed.wire import make_wire_format
from repro.kernels import attention as ka
from repro.kernels import ops as kops
from repro.models import attention as attn
from repro.models.api import build_model
from repro.optim import make_optimizer
from repro.optim.schedules import linear_warmup_cosine

TOL = 8e-3
HEADS, KV_HEADS, HEAD_DIM, BATCH = 8, 2, 64, 2


def _qkv(seed, seq, lead=(), heads=HEADS, kv_heads=KV_HEADS, head_dim=HEAD_DIM):
    ks = jax.random.split(jax.random.key(seed), 4)
    shape = lead + (BATCH, seq)
    q = jax.random.normal(ks[0], shape + (heads, head_dim), jnp.bfloat16)
    k = jax.random.normal(ks[1], shape + (kv_heads, head_dim), jnp.bfloat16)
    v = jax.random.normal(ks[2], shape + (kv_heads, head_dim), jnp.bfloat16)
    w = jax.random.normal(ks[3], q.shape, jnp.float32)
    return q, k, v, w


def _value_and_grads(core, q, k, v, w):
    """The core's output and (dq, dk, dv) of sum(out * w), through
    ``jax.checkpoint(nothing_saveable)`` as the layer scan runs it."""
    ckpt = jax.checkpoint(core, policy=jax.checkpoint_policies.nothing_saveable)
    out = jax.jit(core)(q, k, v)
    grads = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(ckpt(q, k, v).astype(jnp.float32) * w),
        argnums=(0, 1, 2)))(q, k, v)
    return (out,) + grads


def _assert_close(got, want):
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        err = float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))
        assert err < TOL, f"{name}: {err:.3g} of the norm"


def _sdpa(q, k, v):
    return attn._sdpa(q, k, v, attn.causal_mask(q.shape[1]))


def _kernel(q, k, v):
    return ka.causal_gqa(q, k, v, interpret=True)


@pytest.mark.parametrize("block,seq,heads,kv_heads,head_dim", [
    pytest.param(None, 256, HEADS, KV_HEADS, HEAD_DIM, id="block_of_S-256"),
    pytest.param(None, 512, HEADS, KV_HEADS, HEAD_DIM, id="block_of_S-512"),
    pytest.param(128, 256, HEADS, KV_HEADS, HEAD_DIM, id="block128-256"),
    pytest.param(128, 512, HEADS, KV_HEADS, HEAD_DIM, id="block128-512"),
    # D 128: 1/sqrt(D) is no power of two, so the kernels scale the f32 scores
    pytest.param(128, 256, HEADS, KV_HEADS, 128, id="block128-256-D128"),
    # twelve query heads a group, as starcoder2-15b and mistral-large-123b
    pytest.param(None, 256, 12, 1, 128, id="block_of_S-256-G12-D128"),
])
def test_kernel_matches_sdpa(monkeypatch, block, seq, heads, kv_heads, head_dim):
    if block:
        monkeypatch.setattr(ka, "BLOCKS", (block,))
    q, k, v, w = _qkv(seq, seq, heads=heads, kv_heads=kv_heads, head_dim=head_dim)
    _assert_close(_value_and_grads(_kernel, q, k, v, w),
                  _value_and_grads(_sdpa, q, k, v, w))


def test_kernel_vmapped_over_nodes_matches_sdpa(monkeypatch):
    """As ``make_dist_train_step`` runs it: vmapped over a leading node axis."""
    monkeypatch.setattr(ka, "BLOCKS", (128,))
    q, k, v, w = _qkv(1, 256, lead=(2,))
    _assert_close(_value_and_grads(jax.vmap(_kernel), q, k, v, w),
                  _value_and_grads(jax.vmap(_sdpa), q, k, v, w))


@pytest.mark.parametrize("head_dim,q_scale,s_scale", [
    (64, 0.125, 1.0), (128, 1.0, 128 ** -0.5), (256, 0.0625, 1.0)])
def test_scale_goes_to_q_only_where_bfloat16_keeps_it_exact(head_dim, q_scale, s_scale):
    assert ka._split_scale(head_dim) == pytest.approx((q_scale, s_scale), rel=1e-15)


@pytest.mark.parametrize("seq,groups,head_dim,block", [
    (2048, 4, 64, 512),       # granite-3-2b
    (2048, 8, 128, 512),      # internvl2-76b
    (2048, 12, 128, 256),     # starcoder2-15b, mistral-large-123b: 512 tiles overflow
    (2048, 1, 128, 512),      # codeqwen1.5-7b, deepseek-moe-16b
    (2048, 16, 64, 256),
    (384, 4, 64, 128),
    (2048, 17, 64, None),     # more heads a group than was checked against Mosaic
    (2048, 4, 112, None),     # zamba2-7b's head size
    (2000, 4, 64, None),      # off the 128-lane tiles
])
def test_block_size_fits_the_scoped_vmem(seq, groups, head_dim, block):
    assert ka.block_size(seq, groups, head_dim) == block
    assert ka.supports(seq, groups, head_dim) == (block is not None)
    if block:
        assert ka.vmem_bytes(groups, head_dim, block) <= ka.VMEM_BUDGET
        if block < ka.BLOCKS[0] and seq % (2 * block) == 0:
            assert ka.vmem_bytes(groups, head_dim, 2 * block) > ka.VMEM_BUDGET


@pytest.mark.parametrize("mosaic,window,seq,head_dim,path", [
    (False, None, 2048, 64, "dense"),         # the CPU keeps _sdpa
    (False, 64, 2048, 64, "dense"),
    (False, None, 4096, 64, "chunked"),
    (True, None, 2048, 64, "kernel"),
    (True, None, 256, 128, "kernel"),
    (True, 64, 2048, 64, "dense"),            # sliding window
    (True, None, 2000, 64, "dense"),          # length off the 128-lane tiles
    (True, None, 2048, 96, "dense"),          # head size the kernel does not take
    (True, None, 4096, 64, "chunked"),
    (True, 64, 8192, 64, "chunked"),
])
def test_core_path(monkeypatch, mosaic, window, seq, head_dim, path):
    monkeypatch.setattr(kops, "interpret_kernels", lambda: not mosaic)
    assert attn.core_path(seq, 4, head_dim, window) == path


@pytest.mark.parametrize("seq,groups,path", [
    (2048, 12, "kernel"), (2048, 32, "dense"), (4096, 32, "chunked")])
def test_core_path_by_head_group(monkeypatch, seq, groups, path):
    monkeypatch.setattr(kops, "interpret_kernels", lambda: False)
    assert attn.core_path(seq, groups, 128, None) == path


def test_core_path_elsewhere_than_cpu_and_tpu_is_dense(monkeypatch):
    """Where no Pallas kernel runs, the core stays dense and nothing raises."""
    def no_kernels():
        raise RuntimeError("no Pallas kernels on this backend")
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    monkeypatch.setattr(kops, "interpret_kernels", no_kernels)
    assert attn.core_path(2048, 4, 64, None) == "dense"


def _traced_cores(fn, *args):
    before = attn.attention_stats()
    jax.eval_shape(fn, *args)
    after = attn.attention_stats()
    return {path: after[path] - before[path] for path in after}


def test_cpu_traces_the_dense_core():
    cfg = get_config("granite-3-2b").reduced()
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    cores = _traced_cores(lambda p, t: model.loss(p, {"tokens": t, "labels": t})[0],
                          params, tokens)
    assert cores["dense"] >= 1 and cores["kernel"] == cores["chunked"] == 0, cores


def _cell_step_cores(arch, seq_len):
    """Attention cores traced by one DCD quant:4 ring step of two nodes, one
    sequence each, AdamW, remat: the benchmark cells' step."""
    model = build_model(arch)
    opt = make_optimizer("adamw", b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.01)
    plan, wire = make_gossip_plan("ring", 2), make_wire_format("quant:4")
    state = jax.eval_shape(
        lambda key: init_dist_state("dcd", model.init(key), plan, opt, wire=wire),
        jax.random.key(0))
    data = DataConfig(vocab=arch.vocab, seq_len=seq_len, global_batch=2, n_shards=2)
    batch = jax.eval_shape(lambda: stacked_node_batches(data, 0, arch))
    step = make_dist_train_step(lambda p, b: model.loss(p, b, remat=True), "dcd", opt,
                                wire, plan, linear_warmup_cosine(3e-4, 100, 100_000))
    return _traced_cores(step, state, batch)


@pytest.mark.parametrize("name,layers,vocab,kernel", [
    ("granite-3-2b", 4, 6400, True), ("mamba2-370m", 24, None, False)])
def test_cell_step_tally_for_a_tpu(monkeypatch, name, layers, vocab, kernel):
    """Traced as for a TPU, the granite cell's step runs every attention core
    in the kernel; the mamba2 cell's step traces none."""
    monkeypatch.setattr(kops, "interpret_kernels", lambda: False)
    arch = dataclasses.replace(get_config(name), n_layers=layers)
    if vocab:
        arch = dataclasses.replace(arch, vocab=vocab)
    cores = _cell_step_cores(arch, 2048)
    if kernel:
        assert cores["kernel"] >= 1 and cores["dense"] == cores["chunked"] == 0, cores
    else:
        assert cores == {"kernel": 0, "dense": 0, "chunked": 0}
