#!/usr/bin/env python3
"""Record a small traced window of one cell on the chip, for the trace
reader's tests:

    python3 chipbench/tools/record_trace.py --workload sketch.apply \\
        --seed 7 --out chipbench/tests/data/apply_program.xplane.pb
    python3 chipbench/tools/record_trace.py --workload grass.featurize \\
        --seed 7 --examples 256 --out chipbench/tests/data/grass_program.xplane.pb

The cell is set up and warmed as in a run, then its window runs one call
under the profiler (``--seconds`` ends it after the first call), and the
trace file is copied to ``--out``.  ``--examples`` cuts the GraSS training
set, so the trace stays small.  The run's result line, with the per-layer
metrics read from that trace, is printed as in ``run.py``.
"""
from __future__ import annotations

import argparse
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, default=1e-3)
    ap.add_argument("--examples", type=int, default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(HERE.parent / "src"))
    import harness
    spec = harness.cell_spec(harness.manifest(), args.workload)
    if args.examples is not None:
        spec.config["data"]["examples"] = args.examples
    harness.run_cell(args.workload, args.seed, args.seconds, True, spec=spec)
    trace = harness.load_module("", "trace").newest_trace(
        harness.OUT_DIR / "trace")
    shutil.copyfile(trace, args.out)
    print(f"wrote {args.out} ({Path(args.out).stat().st_size} bytes)",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
