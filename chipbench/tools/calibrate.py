#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell, in
one process on the chip:

    python3 chipbench/tools/calibrate.py --workload sketch.apply \\
        --seeds 1,2,3 --seconds 3 [--control]

For every seed it builds the cell, runs a short window at the cell's own
load and size, and prints the number of each check (the program's
reading); with ``--control`` it also prints the numbers with the
reference at the next precision down put in the program's place.  One
JSON line per seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(HERE.parent / "src"))
    import harness
    spec = harness.cell_spec(harness.manifest(), args.workload)
    devs = harness.chips(spec.entry["chips"])
    harness.enable_compile_cache()
    driver = harness.load_module("drivers", spec.workload["driver"])
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = driver.Cell(spec.config, spec.workload, seed)
        res = cell.window(args.seconds)
        cell.release()
        line = {"cell": args.workload, "seed": seed,
                "program": cell.checks(), "attempted": res.attempted,
                "failed": res.failed, "end_to_end": res.end_to_end,
                "device": harness.device_line(devs)}
        if args.control:
            line["control"] = cell.control()
        print(json.dumps(line), flush=True)
        del cell
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
