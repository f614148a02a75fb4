"""Small same-family cells for the benchmark's own tests on the CPU.

The accepted cells are BENCHMARK.json's, read through ``spec``; ``REHEARSED``
holds the cells rehearsed here only, from files of the tests' own, until a
chip run proves them and they join BENCHMARK.json.  Each cell's limits at the
tests' small sizes are ``small_limits/<cell>.json``.
"""
from __future__ import annotations

import dataclasses
import json
import os

from bench import spec

HERE = os.path.dirname(os.path.abspath(__file__))
FOUR_CHIP_CELL = "mamba2.dcd-q4.ring4x4chip"
HYBRID_CELL = "hybrid.dcd-q4.stack2"
# rehearsal-only cells, by cell: (configuration file, traffic file, chips)
REHEARSED = {
    FOUR_CHIP_CELL: ("bench/tests/cells/mamba2-370m.json",
                     "bench/tests/cells/dcd-q4.ring4.b1x2048.json", 4),
    HYBRID_CELL: ("bench/tests/cells/zamba2-7b.hybrid.json",
                  "bench/workloads/dcd-q4.stack2.b1x2048.json", 1),
}


def _json(path):
    with open(path) as f:
        return json.load(f)


def accepted(chips: int):
    """BENCHMARK.json's cells on ``chips`` chips, in its order."""
    return [w["name"] for w in spec.load_benchmark()["workloads"] if w["chips"] == chips]


def cells(chips: int):
    """Every cell rehearsed on ``chips`` chips: BENCHMARK.json's, then the
    rehearsal-only ones."""
    got = accepted(chips)
    return got + [w for w, (_, _, c) in REHEARSED.items() if c == chips and w not in got]


def full(workload: str):
    """The cell as ``spec.cell`` gives it, read from its files (without its
    limits)."""
    bench = spec.load_benchmark()
    if workload in {w["name"] for w in bench["workloads"]}:
        files = spec.cell_files(bench, workload)
        cfg_file, traffic_file, chips = files["config"], files["traffic"], files["chips"]
    else:
        cfg_file, traffic_file, chips = REHEARSED[workload]
        cfg_file, traffic_file = (os.path.join(spec.ROOT, f) for f in (cfg_file, traffic_file))

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {"chips": chips, "config": _json(cfg_file), "traffic": _json(traffic_file),
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def small(workload: str, *, seq_len: int = 64, rows: int = 2):
    """(bench, cfg, arch, traffic) for ``workload`` at the program's reduced
    widths and 2 layers: the benchmark dict, the configuration dict the
    reference reads (every ``program_fields`` key copied from the reduced
    ArchConfig), that ArchConfig, and the cell's traffic shortened."""
    from repro.configs import get_config

    from bench.program import field_at

    bench = spec.load_benchmark()
    c = full(workload)
    cfg = dict(c["config"])
    arch = dataclasses.replace(get_config(cfg["program_arch"]).reduced(), n_layers=2)
    cfg.update({key: field_at(arch, attr) for key, attr in cfg["program_fields"].items()})
    traffic = dict(c["traffic"], seq_len=seq_len, seqs_per_node=rows,
                   warm_steps=1, trace_steps=3)
    return bench, cfg, arch, traffic


def small_limits(workload: str):
    """The cell's limits at the tests' small sizes."""
    return _json(os.path.join(HERE, "small_limits", f"{workload}.json"))["limits"]


def patched_cell(workload: str, traffic):
    """``spec.cell`` with the traffic and the limits of a small run."""
    real = spec.cell
    limits = small_limits(workload)

    def cell(bench, name):
        if name != workload:
            return real(bench, name)
        return dict(full(name), traffic=traffic, limits=limits)

    return cell
