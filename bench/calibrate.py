"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--out readings.jsonl]

In one process on the cell's chips, with the cell's compiled step built once:

* ``program``: for each seed, the program's first steps (as a benchmark run
  drives them) against the plain reference — the lower readings;
* ``control``: the reference with every matmul operand rounded to float8
  e4m3 (the precision below the configuration's bfloat16 compute), put in
  the program's place — it has to read over the limit;
* ``half_batch`` / ``no_exchange``: the reference with a fault planted (each
  node's gradient from half of its rows; the gossip mix left out, W = I) in
  the program's place — the faults the check must catch;
* ``feed_other_node`` / ``feed_unshifted`` / ``feed_one_token``: the feed's
  rows with a fault planted (each node given its neighbour's rows; labels
  equal to the tokens; one token of each node altered), read by the
  reference's check of the rows alone (``feed_mismatch``).

Each reading is one JSON line: kind, seed, and the numbers of
``bench/check.py``.  The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _alter_one(tokens, vocab):
    """``tokens`` with the middle token of each node's first row changed."""
    out = tokens.copy()
    mid = tokens.shape[-1] // 2
    out[:, 0, mid] = (out[:, 0, mid] + 1) % vocab
    return out


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from bench import check, harness, spec
    from bench.program import Cell
    from bench.reference.model import Numerics
    from bench.reference.train import ReferenceRun

    c = spec.cell(spec.load_benchmark(), args.workload)
    devices = harness.tpu_devices(c["chips"])
    harness.use_cache_dir()
    cfg, traffic = c["config"], c["traffic"]
    cell = Cell(cfg, traffic, devices)
    step = cell.compile()
    faults = ["half_batch", "no_exchange"]
    feed_faults = {
        "feed_other_node": lambda r: {k: np.roll(v, 1, axis=0) for k, v in r.items()},
        "feed_unshifted": lambda r: dict(r, labels=r["tokens"].copy()),
        "feed_one_token": lambda r: dict(r, tokens=_alter_one(r["tokens"], cfg["vocab"])),
    }
    runs = {"reference": ReferenceRun(cfg, traffic, devices)}
    out = open(args.out, "a") if args.out else None

    def emit(kind, seed, values, seconds):
        line = json.dumps({"workload": args.workload, "kind": kind, "seed": seed,
                           "seconds": round(seconds, 2), **values})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    seeds = sorted(set(args.seeds) | set(args.control_seeds) | set(args.fault_seeds),
                   key=lambda s: (s not in args.seeds, s))
    for seed in seeds:
        t0 = time.perf_counter()
        state, prog, rows = harness.first_steps(cell, step, seed, traffic["checked_steps"])
        del state
        ref = runs["reference"].run(seed, rows)
        if seed in args.seeds:
            emit("program", seed, check.readings(prog, ref), time.perf_counter() - t0)
        kinds = (["control"] if seed in args.control_seeds else []) + \
            (faults + list(feed_faults) if seed in args.fault_seeds else [])
        for kind in kinds:
            if kind in feed_faults:
                feed, t1 = runs["reference"].feed, time.perf_counter()
                counts = [feed(seed, t, r["tokens"], r["labels"])[:2]
                          for t, r in enumerate(map(feed_faults[kind], rows))]
                emit(kind, seed, {"feed_mismatch": sum(c[0] for c in counts)
                                  / sum(c[1] for c in counts)}, time.perf_counter() - t1)
                continue
            if kind not in runs:
                runs[kind] = ReferenceRun(cfg, traffic, devices, numerics=Numerics(fp8=True)) \
                    if kind == "control" else ReferenceRun(cfg, traffic, devices, fault=kind)
            t1 = time.perf_counter()
            emit(kind, seed, check.readings(runs[kind].run(seed, rows), ref),
                 time.perf_counter() - t1)
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
