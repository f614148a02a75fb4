"""Small same-family cells for the benchmark's own tests on the CPU."""
from __future__ import annotations

import dataclasses
import json
import os

from bench import spec

SSM_CELL = "mamba2-L24.dcd-q4.stack2"
DENSE_CELL = "granite-L4.dcd-q4.stack2"
FOUR_CHIP_CELL = "mamba2.dcd-q4.ring4x4chip"
# the cells' files, by cell: (configuration file, traffic file, chips); the
# granite and four-chip cells are rehearsed here only, from files of the
# tests' own, until a chip run proves them and they join BENCHMARK.json
CELLS = {
    SSM_CELL: ("bench/configs/mamba2-370m.L24.json",
               "bench/workloads/dcd-q4.stack2.b2x2048.json", 1),
    DENSE_CELL: ("bench/tests/cells/granite-3-2b.L4.json",
                 "bench/tests/cells/dcd-q4.stack2.b1x2048.json", 1),
    FOUR_CHIP_CELL: ("bench/tests/cells/mamba2-370m.json",
                     "bench/tests/cells/dcd-q4.ring4.b1x2048.json", 4),
}


def _json(path):
    with open(path) as f:
        return json.load(f)


def full(workload: str):
    """The cell as ``spec.cell`` gives it, read from its files."""
    cfg_file, traffic, chips = CELLS[workload]
    bench = spec.load_benchmark()
    def applies(m):
        return workload in m.get("workloads", [workload])

    return {"chips": chips, "config": _json(os.path.join(spec.ROOT, cfg_file)),
            "traffic": _json(os.path.join(spec.ROOT, traffic)),
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def small(workload: str, *, seq_len: int = 64, rows: int = 2):
    """(bench, cfg, arch, traffic) for ``workload`` at the program's reduced
    widths: the benchmark dict, the configuration dict the reference reads,
    the program's matching ArchConfig, and the cell's traffic shortened."""
    from repro.configs import get_config

    bench = spec.load_benchmark()
    c = full(workload)
    cfg = dict(c["config"])
    arch = get_config(cfg["program_arch"]).reduced()
    arch = dataclasses.replace(arch, n_layers=2)
    cfg.update(n_layers=arch.n_layers, d_model=arch.d_model, vocab=arch.vocab)
    if cfg["family"] == "ssm":
        cfg.update(d_inner=arch.ssm.d_inner, d_state=arch.ssm.d_state,
                   n_heads=arch.ssm.n_heads, n_groups=arch.ssm.n_groups,
                   chunk=arch.ssm.chunk)
    else:
        cfg.update(n_heads=arch.n_heads, n_kv_heads=arch.n_kv_heads,
                   head_dim=arch.hd, d_ff=arch.d_ff)
    traffic = dict(c["traffic"], seq_len=seq_len, seqs_per_node=rows,
                   warm_steps=1, trace_steps=3)
    return bench, cfg, arch, traffic


SMALL_LIMITS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "small_limits.json")


def patched_cell(workload: str, traffic):
    """``spec.cell`` with the traffic and the limits of a small run."""
    real = spec.cell
    limits = _json(SMALL_LIMITS)[workload]["limits"]

    def cell(bench, name):
        if name != workload:
            return real(bench, name)
        return dict(full(name), traffic=traffic, limits=limits)

    return cell
