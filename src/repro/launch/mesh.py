"""Production mesh construction.

``make_production_mesh`` builds the assignment-mandated mesh.  Training derives a
logical ``(node, fsdp, model)`` view of the same devices: the decentralized gossip
ring runs over ``node`` (across pods in the multi-pod case — compression where the
links are slowest), ``fsdp`` shards each node's replica+optimizer, ``model`` is
tensor/expert parallel.  A function, not a constant: importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def derive_train_mesh(mesh: Mesh, n_nodes: int, tp: int = None) -> Mesh:
    """Reshape the production mesh devices to (node, fsdp, model[=tp]).

    ``tp`` defaults to the physical model-axis width; smaller tp folds the spare
    model-axis factor into fsdp (a 2B model should not be 16-way tensor-parallel).
    Multi-pod: the pod axis becomes the *outermost* part of the node axis, so the
    gossip ring crosses the slow DCN links and the compressed payloads ride them.
    """
    devices = mesh.devices  # (data, model) or (pod, data, model)
    total = devices.size
    tp = tp if tp is not None else devices.shape[-1]
    assert total % (n_nodes * tp) == 0, f"node={n_nodes} x tp={tp} must divide {total}"
    fsdp = total // (n_nodes * tp)
    flat = devices.reshape(-1)                 # pod-major order preserved
    return Mesh(flat.reshape(n_nodes, fsdp, tp), ("node", "fsdp", "model"))


def derive_serve_mesh(mesh: Mesh, mp: int) -> Mesh:
    """Reshape to (dp, mp) for serving (no gossip axis)."""
    devices = mesh.devices.reshape(-1)
    total = devices.size
    assert total % mp == 0
    return Mesh(devices.reshape(total // mp, mp), ("dp", "mp"))


def node_mesh(devices) -> Mesh:
    """1-D ``node`` mesh, one gossip node (or an equal share of them) per
    device.  Its axis is Auto-sharded: ``jax.make_mesh`` now defaults to
    Explicit axes, under which the node-axis ``vmap`` of the train step
    refuses a batch sharded differently from the params."""
    return Mesh(np.asarray(devices), ("node",))
