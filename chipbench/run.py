#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python3 chipbench/run.py --workload sketch.apply --seed 7 --seconds 20 \\
        --trace 0

Run from the root of a checkout, on a machine with the chips the cell asks
for (``BENCHMARK.json``).  With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` the window is traced and the result
carries its per-layer metrics, the device's busy time and a breakdown.
The last line of standard output is the result, one JSON object; the last
lines of standard error give each compared number beside its limit.
Without a TPU, or short of chips, it exits with code 2 and prints no
result.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _import_program() -> None:
    """Import the system under test from this checkout's ``src/`` and
    nowhere else."""
    src = ROOT / "src"
    sys.path.insert(0, str(src))
    import repro
    origin = Path(repro.__file__).resolve()
    if src.resolve() not in origin.parents:
        raise ImportError(f"repro imported from {origin}, not from {src}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(HERE))
    import harness
    try:
        _import_program()
        harness.run_cell(args.workload, args.seed, args.seconds,
                         bool(args.trace))
    except harness.NoChip as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 2
    except (ImportError, FileNotFoundError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
