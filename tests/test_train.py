"""The training driver, ``launch/train.py::run_training``, end to end.

On one device every node is a slice of one stacked program; with several it
shards the node axis over a 1-D ``node`` mesh.  The multi-device case runs in
a subprocess so that XLA_FLAGS can give it four CPU devices while this test
process keeps seeing one.
"""
import dataclasses
import json

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.train import TrainConfig, run_training
from test_distributed import run_subprocess


def _tiny():
    cfg = dataclasses.replace(get_config("mamba2-370m").reduced(), n_layers=1)
    tc = TrainConfig(algo="dcd", wire="quant:4", topology="ring", n_nodes=2,
                     seq_len=16, global_batch=2, steps=2, log_every=1)
    return cfg, tc


def test_run_training_stacked_and_remat_agree():
    """Two nodes stacked on one device: finite losses, one AOT-compiled step,
    a final state on the node axis; recomputing each layer in the backward
    pass gives the same trajectory."""
    cfg, tc = _tiny()
    hist = run_training(cfg, tc)
    assert len(hist["loss"]) == 2 and np.all(np.isfinite(hist["loss"]))
    assert len(hist["compiled_steps"]) == len(hist["compile_s"]) == 1
    assert all(l.shape[0] == 2 for l in jax.tree.leaves(hist["state"].params))
    remat = run_training(cfg, dataclasses.replace(tc, remat=True))
    np.testing.assert_allclose(remat["loss"], hist["loss"], rtol=1e-5)


def test_run_training_shards_nodes_over_a_node_mesh():
    """Four nodes on four devices: the step's permutes carry only wire
    containers, and the losses follow the stacked program's."""
    out = run_subprocess("""
        import dataclasses, json
        import jax
        from repro.analysis.jaxpr_checks import (
            check_permute_payload_whitelist, permute_operands)
        from repro.configs import get_config
        from repro.distributed.wire import make_wire_format
        from repro.launch.train import TrainConfig, run_training
        cfg = dataclasses.replace(get_config("mamba2-370m").reduced(), n_layers=1)
        tc = TrainConfig(algo="dcd", wire="quant:4", topology="ring", n_nodes=4,
                         seq_len=16, global_batch=4, steps=2, log_every=1)
        sharded = run_training(cfg, tc)
        stacked = run_training(cfg, tc, devices=jax.devices()[:1])
        hlo = sharded["compiled_steps"][0].as_text()
        print(json.dumps({
            "mesh": [str(s.sharding.spec) for s in
                     jax.tree.leaves(sharded["state"].params)][:1],
            "permutes": len(permute_operands(hlo)),
            "violations": check_permute_payload_whitelist(
                hlo, make_wire_format(tc.wire), stacked["state"].params,
                n_devices=4),
            "sharded": sharded["loss"], "stacked": stacked["loss"]}))
    """, devices=4)
    r = json.loads(out.strip().splitlines()[-1])
    assert r["mesh"] == ["PartitionSpec('node',)"]
    assert r["permutes"] > 0 and r["violations"] == []
    np.testing.assert_allclose(r["sharded"], r["stacked"], rtol=1e-5)
