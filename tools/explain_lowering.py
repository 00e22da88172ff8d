#!/usr/bin/env python
"""Print the sketch lowering decision trace for one launch shape.

    PYTHONPATH=src python tools/explain_lowering.py --d 65536 --k 1024 --n 512
    PYTHONPATH=src python tools/explain_lowering.py --d 65536 --k 1024 \
        --n 512 --dtype bfloat16 --impl pallas --block-rows 256
    PYTHONPATH=src python tools/explain_lowering.py --d 4096 --k 1024 \
        --n 64 --shard row --devices 8

Shows exactly what ``repro.kernels.ops`` would launch for these knobs —
the resolved impl (with any downgrade and its reason), the tile width and
where it came from, the VMEM footprint, the padding plan — plus the
modeled TPU-v5e roofline of that same record (``engine.cost_of``).  CI
runs this as a smoke step so the engine's public surface cannot rot.

The trace ends with the process-wide counters (``repro.health.report``:
guard and recovery events, lowering-memo hits and misses).

``--check-health`` additionally runs a tiny GUARDED solve on this shape
(``sketch_precondition_lstsq(guard=True)``) and prints its HealthReport
and the process-wide counters — exits non-zero if the guarded solve
fails outright.  CI runs this too, so the guard layer's public surface is
smoke-tested alongside the lowering trace.
"""
from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    from repro.core import precision

    ap = argparse.ArgumentParser(
        description="FlashSketch lowering decision trace")
    ap.add_argument("--d", type=int, required=True, help="input dim (rows)")
    ap.add_argument("--k", type=int, required=True, help="sketch dim")
    ap.add_argument("--n", type=int, required=True, help="operand columns")
    ap.add_argument("--kappa", type=int, default=4)
    ap.add_argument("--s", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--block-rows", type=int, default=None,
                    help="pin B_r (make_plan block_rows=)")
    ap.add_argument("--dtype", choices=list(precision.names()), default=None,
                    help="streaming-precision policy override (any "
                         "registered core.precision policy or alias)")
    ap.add_argument("--op", choices=["fwd", "transpose", "blockrow"],
                    default="fwd")
    ap.add_argument("--impl",
                    choices=["auto", "pallas", "pallas_v1", "xla"],
                    default="pallas",
                    help="requested impl (default 'pallas': show the TPU "
                         "decision regardless of host backend)")
    ap.add_argument("--tn", type=int, default=None,
                    help="explicit tile width (default: tuner/heuristic)")
    ap.add_argument("--gather", action="store_true",
                    help="gather-fused row_index= launch")
    ap.add_argument("--batch", type=int, default=1,
                    help="batched-apply fold factor")
    ap.add_argument("--shard", choices=["none", "row", "col", "batch"],
                    default="none")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--tune-cache", default=None,
                    help="JSON tuner cache to load first (tuned winners "
                         "then show up as the resolved tile)")
    ap.add_argument("--check-health", action="store_true",
                    help="also run a tiny guarded solve on this shape and "
                         "print its HealthReport + the process counters "
                         "(nonzero exit if the guarded solve fails)")
    args = ap.parse_args(argv)

    from repro import engine
    from repro.core.blockperm import make_plan
    from repro.kernels import tune

    if args.tune_cache:
        n_loaded = tune.load_cache(args.tune_cache)
        print(f"loaded {n_loaded} tuned winners from {args.tune_cache}\n")

    plan = make_plan(args.d, args.k, kappa=args.kappa, s=args.s,
                     seed=args.seed, block_rows=args.block_rows)
    spec = engine.LaunchSpec(
        op=args.op, n=args.n, impl=args.impl, tn=args.tn, dtype=args.dtype,
        gather=args.gather, batch=args.batch, shard=args.shard,
        devices=args.devices)
    print(engine.explain(plan, spec))

    lw = engine.lower(plan, spec)
    try:
        kc = engine.cost_of(lw)
    except ValueError as e:           # e.g. row-sharded blockrow
        print(f"\nmodeled cost: n/a ({e})")
        return 0
    print(f"\nmodeled TPU-v5e roofline of this record "
          f"(repro.engine.cost_of):")
    print(f"  mxu={1e6 * kc.compute_s:8.2f} us   "
          f"vpu={1e6 * kc.vpu_s:8.2f} us   "
          f"hbm={1e6 * kc.memory_s:8.2f} us   "
          f"ici={1e6 * kc.ici_s:8.2f} us")
    print(f"  modeled {kc.modeled_us:.2f} us, bottleneck: {kc.bottleneck}")

    if args.check_health:
        return _check_health(args)
    return 0


def _check_health(args) -> int:
    """Tiny guarded solve with this launch's κ/s/seed knobs; prints the
    HealthReport and the process-wide counters.  The problem shape is
    capped (the point is exercising the guard surface, not the launch
    size)."""
    import numpy as np

    from repro.health import report
    from repro.solvers.sketch_precondition import sketch_precondition_lstsq

    d = min(args.d, 8192)
    n = min(args.n, 32)
    rng = np.random.default_rng(args.seed)
    A = rng.standard_normal((d, n)).astype(np.float32)
    b = (A @ np.ones(n, np.float32)).astype(np.float32)
    res = sketch_precondition_lstsq(
        A, b, kappa=args.kappa, s=args.s, seed=args.seed,
        impl="auto", guard=True, probe=True)
    print(f"\nguarded solve on a capped ({d}, {n}) problem:")
    print(res.health.describe())
    print(f"converged={res.converged} relres={res.relres:.3g} "
          f"iterations={res.iterations}")
    print("counters: " + report.summarize_counters(max_items=100))
    if res.health.status == "failed" or not res.converged:
        print("health check FAILED", file=sys.stderr)
        return 1
    print("health check ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
