"""Plain float32 reference of the model families the benchmark trains.

Written from the published descriptions and from the configuration files under
``bench/configs``, in straightforward ``jax.numpy``: no kernels, no chunked
scan, no mixed precision.  It imports nothing of the program under test.

This module holds what every family shares: the embedding, a stack of
blocks, a final RMSNorm, an untied LM head and the mean token cross-entropy.
A family's block is ``bench/reference/families/<family>.py`` (``block_init``
and ``block``), found by the configuration's ``family``: ``ssm`` (Mamba-2),
``dense`` (a Llama-style decoder).  Departures from the published models
that the program makes, and that the reference therefore makes too, are
listed in each configuration file under ``departures``.

``Numerics`` holds the one knob: how matmul operands are rounded.  The
reference leaves them in float32 and multiplies at ``HIGHEST`` precision; the
control (``Numerics(fp8=True)``) is the usual float8 training recipe, the
step below the bfloat16 compute the configuration states: every matmul
operand, and every activation where the program holds one in bfloat16 (the
embedded tokens, each block's output, the SSD's inputs and decays), rounded
to e4m3 in the forward pass and its cotangent to e5m2 in the backward pass,
each with a per-tensor scale to the format's largest value.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _round_scaled(x, dtype):
    """``x`` rounded to ``dtype`` under a per-tensor scale that maps its
    largest magnitude to the format's largest finite value."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def fp8_operand(x):
    return _round_scaled(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return fp8_operand(x), None


def _fp8_bwd(_, g):
    return (_round_scaled(g, jnp.float8_e5m2),)


fp8_operand.defvjp(_fp8_fwd, _fp8_bwd)


@dataclasses.dataclass(frozen=True)
class Numerics:
    fp8: bool = False

    def op(self, x: jax.Array) -> jax.Array:
        """A matmul operand or a held activation, as this numerics rounds it."""
        x = x.astype(jnp.float32)
        return fp8_operand(x) if self.fp8 else x

    def mm(self, spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
        return jnp.einsum(spec, self.op(a), self.op(b), precision=HIGHEST)


# ----------------------------------------------------------------- init
# The weights follow the program's recipe for the same seed (the same key
# splits and distributions), so that both start from the same point; the
# benchmark's tests check that the two agree leaf for leaf.

def normal(key, shape, scale):
    return scale * jax.random.normal(key, shape, jnp.float32)


def padded_vocab(cfg: Dict[str, Any]) -> int:
    return -(-cfg["vocab"] // 256) * 256


def family(cfg: Dict[str, Any]):
    """The module of the configuration's model family."""
    return importlib.import_module(f"bench.reference.families.{cfg['family']}")


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    d, vp = cfg["d_model"], padded_vocab(cfg)
    layer_keys = jax.random.split(ks[6], cfg["n_layers"])
    block = family(cfg).block_init
    return {
        "embed": normal(ks[0], (vp, d), 0.02),
        "final_ln": jnp.ones((d,), jnp.float32),
        "lm_head": normal(ks[1], (d, vp), 0.02),
        "blocks": jax.vmap(lambda k: block(cfg, k))(layer_keys),
    }


# ----------------------------------------------------------------- layers

def rmsnorm(x, g, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def sequence_loss(cfg: Dict[str, Any], params, tokens, labels, nx: Numerics):
    """Mean next-token cross-entropy of one sequence (tokens, labels: (S,)).

    The logits span the padded vocabulary, as the program's head does."""
    block = family(cfg).block
    h = nx.op(params["embed"][tokens])

    def layer(hh, lp):
        return nx.op(block(cfg, hh, lp, nx)), None

    h, _ = jax.lax.scan(jax.checkpoint(layer), h, params["blocks"])
    h = rmsnorm(h, params["final_ln"])
    logits = nx.mm("sd,dv->sv", h, params["lm_head"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)
