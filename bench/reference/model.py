"""Plain float32 reference of the model families the benchmark trains.

Written from the published descriptions and from the configuration files under
``bench/configs``, in straightforward ``jax.numpy``: no kernels, no chunked
scan, no mixed precision.  It imports nothing of the program under test.

A configuration's ``family`` names its module,
``bench/reference/families/<family>.py``, which plugs in one of two ways:

* with a block alone (``block_init`` and ``block``): ``init_params`` and
  ``sequence_loss`` below make the shared model around it, the embedding, a
  uniform stack of ``n_layers`` blocks, a final RMSNorm, an untied LM head
  and the mean token cross-entropy (``ssm``, Mamba-2; ``dense``, a
  Llama-style decoder);
* with a model of its own: a family that defines ``init_params`` and
  ``sequence_loss`` (same signatures) owns its parameter tree and its loss,
  and composes them from the public helpers here (``head_params``,
  ``stack_init``, ``embed``, ``scan_blocks``, ``head_loss``) and from other
  families' blocks: a layer pattern, a loop over the stack, leading layers of
  another kind, a shared block (``hybrid``).

Departures from the published models that the program makes, and that the
reference therefore makes too, are listed in each configuration file under
``departures``.

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


def head_params(cfg: Dict[str, Any], ks) -> Dict[str, Any]:
    """The embedding, the final norm and the untied head, from the first two
    of the model's eight keys ``ks``."""
    d, vp = cfg["d_model"], padded_vocab(cfg)
    return {
        "embed": normal(ks[0], (vp, d), 0.02),
        "final_ln": jnp.ones((d,), jnp.float32),
        "lm_head": normal(ks[1], (d, vp), 0.02),
    }


def stack_init(cfg: Dict[str, Any], key: jax.Array, n: int, block_init) -> Any:
    """``n`` blocks of ``block_init``, stacked on a leading axis."""
    return jax.vmap(lambda k: block_init(cfg, k))(jax.random.split(key, n))


def init_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    fam = family(cfg)
    if hasattr(fam, "init_params"):
        return fam.init_params(cfg, key)
    ks = jax.random.split(key, 8)
    return dict(head_params(cfg, ks),
                blocks=stack_init(cfg, ks[6], cfg["n_layers"], fam.block_init))


# ----------------------------------------------------------------- layers

def rmsnorm(x, g, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def embed(params, tokens, nx: Numerics):
    """The embedded tokens (S, d), held as the program holds them."""
    return nx.op(params["embed"][tokens])


def scan_blocks(cfg: Dict[str, Any], h, blocks, block, nx: Numerics):
    """``h`` through a stack of ``block``s, each recomputed in the backward
    pass and its output held as the program holds it."""
    def layer(hh, lp):
        return nx.op(block(cfg, hh, lp, nx)), None

    h, _ = jax.lax.scan(jax.checkpoint(layer), h, blocks)
    return h


def head_loss(params, h, labels, nx: Numerics):
    """Final RMSNorm, the untied head over the padded vocabulary, and the
    mean cross-entropy of ``labels``."""
    h = rmsnorm(h, params["final_ln"])
    logits = nx.mm("sd,dv->sv", h, params["lm_head"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def sequence_loss(cfg: Dict[str, Any], params, tokens, labels, nx: Numerics):
    """Mean next-token cross-entropy of one sequence (tokens, labels: (S,)).

    The logits span the padded vocabulary, as the program's head does."""
    fam = family(cfg)
    if hasattr(fam, "sequence_loss"):
        return fam.sequence_loss(cfg, params, tokens, labels, nx)
    h = scan_blocks(cfg, embed(params, tokens, nx), params["blocks"], fam.block, nx)
    return head_loss(params, h, labels, nx)
