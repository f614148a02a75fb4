"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
The configuration's file is the one ``configs`` gives for it; the traffic
mix is ``bench/workloads/<traffic>.json``; the limits of the comparison that
decides ``correct`` are ``bench/limits/<cell>.json``; a metric is read by
``bench/metrics/<metric>.py``; a model family's FLOPs by
``bench/flops/<family>.py``; a chip's peaks are ``bench/peaks.json`` under
its ``device_kind``.  Nothing is listed in code: adding a cell, a mix, a
configuration or a metric is adding files and entries.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")


def _json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> Dict[str, Any]:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def metric_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "metrics", f"{name}.py")


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def cell_files(bench: Dict[str, Any], workload: str) -> Dict[str, Any]:
    """A cell's chips and the paths of its configuration, traffic and limits
    files."""
    entry = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    conf = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    return {
        "chips": entry["chips"],
        "config": os.path.join(ROOT, conf["file"]),
        "traffic": os.path.join(BENCH_DIR, "workloads", f"{entry['traffic']}.json"),
        "limits": os.path.join(BENCH_DIR, "limits", f"{workload}.json"),
    }


def cell(bench: Dict[str, Any], workload: str) -> Dict[str, Any]:
    """Everything one cell needs, read from its files."""
    files = cell_files(bench, workload)
    return {
        "chips": files["chips"],
        "config": _json(files["config"]),
        "traffic": _json(files["traffic"]),
        "limits": _json(files["limits"])["limits"],
        "end_to_end": [m for m in bench["end_to_end"] if _applies(m, workload)],
        "per_layer": [m for m in bench["per_layer"] if _applies(m, workload)],
    }


def flops_per_token(cfg: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    path = os.path.join(BENCH_DIR, "flops", f"{cfg['family']}.py")
    s = importlib.util.spec_from_file_location(f"bench_flops_{cfg['family']}", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return float(mod.flops_per_token(cfg, traffic))


def peaks(device_kind: str) -> Dict[str, float]:
    table = _json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]
