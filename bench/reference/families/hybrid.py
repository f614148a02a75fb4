"""A Mamba-2 stack with one shared attention block, the ``hybrid`` family of
the plain reference (the layer pattern of Zamba2, arXiv:2411.15242).

The ``n_layers`` layers run in periods of ``hybrid_period``: ``hybrid_period
- 1`` Mamba-2 blocks of their own, then one attention + SwiGLU block whose
weights every period shares; layers past the last whole period are Mamba-2
blocks (the tail).  The Mamba-2 block is the ``ssm`` family's, with its heads
given by ``ssm_heads``; the shared block is the ``dense`` family's
(pre-norm GQA attention with RoPE, then a pre-norm SwiGLU MLP), its MLP kept
under the key ``mlp``.  The family owns its parameter tree and its loss and
composes them from the shared helpers of ``model.py``.
"""
import jax

from bench.reference import model
from bench.reference.families import dense, ssm


def layout(cfg):
    """(whole periods, Mamba-2 blocks a period, Mamba-2 blocks in the tail)."""
    period = cfg["hybrid_period"]
    n_periods = cfg["n_layers"] // period
    return n_periods, period - 1, cfg["n_layers"] - n_periods * period


def mamba_cfg(cfg):
    """The configuration as the ``ssm`` family reads it."""
    return dict(cfg, n_heads=cfg["ssm_heads"])


def init_params(cfg, key):
    ks = jax.random.split(key, 8)
    n_periods, per_period, tail = layout(cfg)
    mcfg = mamba_cfg(cfg)
    p = model.head_params(cfg, ks)
    p["pm"] = jax.vmap(lambda k: model.stack_init(mcfg, k, per_period, ssm.block_init))(
        jax.random.split(ks[3], max(n_periods, 1)))
    if tail:
        p["tail"] = model.stack_init(mcfg, ks[4], tail, ssm.block_init)
    shared = dense.block_init(cfg, ks[5])
    p["shared_attn"] = {"ln1": shared["ln1"], "attn": shared["attn"], "ln2": shared["ln2"],
                        "mlp": shared["ffn"]}
    return p


def _shared_block(cfg, h, sa, nx):
    return nx.op(dense.block(cfg, h, {**sa, "ffn": sa["mlp"]}, nx))


def sequence_loss(cfg, params, tokens, labels, nx):
    mcfg = mamba_cfg(cfg)
    shared = jax.checkpoint(lambda hh, sa: _shared_block(cfg, hh, sa, nx))

    def period(h, blocks):
        h = model.scan_blocks(mcfg, h, blocks, ssm.block, nx)
        return shared(h, params["shared_attn"]), None

    h, _ = jax.lax.scan(period, model.embed(params, tokens, nx), params["pm"])
    if "tail" in params:
        h = model.scan_blocks(mcfg, h, params["tail"], ssm.block, nx)
    return model.head_loss(params, h, labels, nx)
