"""Readings for the limits of ``correct``: the control and the planted
faults of a cell, on the chip at the cell's own size.

    python benchmarks/prove.py --workload <name> --seeds 1,2,3 --what control,half_batch

Not part of a benchmark run.  For each seed and each ``what`` it asks
the cell's job (``jobs/<job>.py::prove``) for the numbers ``correct``
compares, with the reference put in the program's place, and prints one
JSON line each.  ``PERF.md`` records what it read and the limits set
from that.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="control")
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmarks import run as R

    cell, config, _, device, peaks = R.prepare(args.workload)
    job = importlib.import_module(f"benchmarks.jobs.{cell['job']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = R.make_ctx(cell, config, peaks, seed=seed, seconds=args.seconds)
        for what in args.what.split(","):
            checks = job.prove(ctx, what)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "what": what,
                "device": device,
                "checks": {n: {"value": v, "limit": lim} for n, v, lim in checks},
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
