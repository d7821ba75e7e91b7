#!/usr/bin/env python3
"""Reads the two ends a limit is set between, on the chip, at a cell's own
size: for each seed one run of the cell (its own load, a short window),
the program's number as the run compares it, and the control's — the
reference put in the program's place at the next precision below the one
the configuration states (the ``CONTROL`` of the configuration's model
module: int4 weights for int8; float8 for bfloat16).

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 25

Prints one JSON line per seed.  The benchmark's own runs never run the
control; ``benchmark/tests`` keeps it at toy size.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark import run  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402


def read_seed(manifest, workload: str, seed: int, seconds: float) -> dict:
    cell = manifest.cell(workload)
    ctx = run.Context(manifest, cell, seed, seconds, False,
                      time.perf_counter())
    ctx.compiles.start()
    driver = run.make_driver(ctx)
    driver.load()
    out = driver.run()
    row = {"workload": workload, "seed": seed,
           "program": {n: v for n, v, _lim in out["checks"]},
           "control": driver.control_reading(), "notes": ctx.notes,
           "end_to_end": out["end_to_end"]}
    driver.close()
    del driver, out
    gc.collect()
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    manifest = Manifest(_ROOT)
    run.configure_jax(_ROOT)
    run.find_chips(int(manifest.cell(args.workload)["chips"]))
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(read_seed(manifest, args.workload, seed,
                                   args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
