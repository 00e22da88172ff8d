"""Multi-device FlashSketch benchmark: shard-mapped sketching over every
device of the host (4 chips on a v5e host), plus the distributed
sketch-and-precondition solver.

    PYTHONPATH=src python -m benchmarks.dist_bench               # paper grid
    PYTHONPATH=src python -m benchmarks.dist_bench --tiny        # CI smoke

Off the chip, give the CPU backend several devices before launching:
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Writes ``BENCH_dist.json``.  Each row covers one (d, n, k, κ, dtype) cell:

  * ``exact_*``   — ``array_equal`` gates: row-sharded (the psum'd-partials
    path), column-sharded and batch-sharded applies against the
    single-device ``ops`` entry points.  These must hold BITWISE — the
    per-ℓ psum protocol guarantees it (see ``repro.distributed``).
  * ``measured_*`` — wall-clock on THIS host.  Emulated host devices
    share the same cores, so sharded wall-clock says nothing about real
    scaling; it is a smoke signal only.
  * ``modeled_*`` — TPU-v5e numbers priced from the LOWERING RECORDS of
    the two organizations (``engine.cost_of``): the row-sharded partial
    (1/P HBM slab + ring-psum at ``hw.ICI_BW``) against the single-chip
    launch the dispatch engine would actually make.  For plans whose
    fused v2 scratch cannot fit VMEM, that single-chip baseline is the
    v1 revisiting kernel — what ``ops.sketch_apply`` really runs — not a
    hypothetical v2 launch that could never fit (the PR-4 class of
    model-vs-kernel contradiction).

The run FAILS (non-zero exit) if any exactness gate is lost, if the
modeled multi-chip scaling geomean drops below 1.5× at P devices, or if
the distributed solver fails to converge — CI runs ``--tiny`` as a
regression gate.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import geomean, time_fn
from repro import engine
from repro.compile_cache import enable_compile_cache
from repro.distributed import (dist_sketch_precondition_lstsq,
                               plan_for_mesh,
                               sketch_apply_batched_sharded,
                               sketch_apply_colsharded,
                               sketch_apply_sharded)
from repro.kernels import ops
from repro.launch import mesh as mesh_lib

DTYPES = (None, "bfloat16")          # None = fp32 (the plan default)


def bench_grid(cells, *, mesh, axis, iters=3) -> List[Dict]:
    devices = mesh.shape[axis]
    rows: List[Dict] = []
    rng = np.random.default_rng(0)
    for d, n, k, kappa in cells:
        for dtype in DTYPES:
            plan = plan_for_mesh(d, k, devices, kappa=kappa, s=2, seed=0,
                                 dtype=dtype or "float32")
            A = jnp.asarray(rng.normal(size=(d, n)).astype(np.float32))
            G = jnp.asarray(
                rng.normal(size=(devices, d, max(1, n // devices)))
                .astype(np.float32))

            ref = ops.sketch_apply(plan, A)
            sharded = sketch_apply_sharded(plan, A, mesh, axis)
            exact_row = bool(np.array_equal(np.asarray(sharded),
                                            np.asarray(ref)))
            exact_col = bool(np.array_equal(
                np.asarray(sketch_apply_colsharded(plan, A, mesh, axis)),
                np.asarray(ref)))
            exact_batch = bool(np.array_equal(
                np.asarray(sketch_apply_batched_sharded(plan, G, mesh, axis)),
                np.asarray(ops.sketch_apply_batched(plan, G))))

            single_fn = jax.jit(lambda X: ops.sketch_apply(plan, X))
            shard_fn = jax.jit(
                lambda X: sketch_apply_sharded(plan, X, mesh, axis))
            measured_single_us = 1e6 * time_fn(single_fn, A, iters=iters)
            measured_sharded_us = 1e6 * time_fn(shard_fn, A, iters=iters)

            # modeled from the lowering records of the two organizations
            # being compared: the single-chip launch as dispatch would
            # actually make it (v2, or the v1 downgrade when the fused
            # scratch cannot fit VMEM) and the row-sharded partial (the
            # same engine path sharded_apply lowers through)
            lw1 = engine.lower(plan, engine.LaunchSpec(
                op="fwd", n=n, impl="pallas", tn=128))
            lwP = engine.lower(plan, engine.LaunchSpec(
                op="fwd", n=n, impl="pallas", tn=128, shard="row",
                devices=devices))
            c1 = engine.cost_of(lw1)
            cP = engine.cost_of(lwP)
            row = dict(
                d=d, n=n, k=plan.k_pad, kappa=kappa,
                dtype=dtype or "float32",
                M=plan.M, Br=plan.Br, Bc=plan.Bc, devices=devices,
                exact_row_sharded=exact_row,
                exact_col_sharded=exact_col,
                exact_batch_sharded=exact_batch,
                measured_single_us=measured_single_us,
                measured_sharded_us=measured_sharded_us,
                modeled_single_chip_us=c1.modeled_us,
                modeled_per_chip_us=cP.modeled_us,
                modeled_ici_us=1e6 * cP.ici_s,
                modeled_bottleneck=cP.bottleneck,
                modeled_speedup=c1.modeled_us / cP.modeled_us,
                lowering_sharded=lwP.describe(),
            )
            rows.append(row)
            ok = exact_row and exact_col and exact_batch
            print(f"d={d:>8} n={n:>4} k={plan.k_pad:>5} kappa={kappa} "
                  f"dtype={row['dtype']:<8} exact={'OK' if ok else 'FAIL'} "
                  f"modeled x{row['modeled_speedup']:.2f} "
                  f"({row['modeled_bottleneck']})")
    return rows


def bench_solver(d, n, *, mesh, axis, tol=1e-5) -> Dict:
    rng = np.random.default_rng(1)
    A = jnp.asarray(rng.normal(size=(d, n)).astype(np.float32))
    x_true = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    b = A @ x_true
    res = dist_sketch_precondition_lstsq(A, b, mesh, axis, tol=tol)
    print(f"dist solver d={d} n={n}: iters={res.iterations} "
          f"relres={res.relres:.2e} converged={res.converged}")
    return dict(d=d, n=n, iterations=res.iterations,
                relres=float(res.relres), converged=bool(res.converged),
                tol=tol)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke grid (seconds, still gates exactness)")
    ap.add_argument("--out", default="BENCH_dist.json")
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args(argv)

    enable_compile_cache()
    devices = jax.device_count()
    if devices < 2:
        print(f"FAIL: sharded apply needs several devices, got {devices} "
              f"(off the chip, set XLA_FLAGS="
              f"--xla_force_host_platform_device_count=8)", file=sys.stderr)
        return 1
    mesh, axis = mesh_lib.make_mesh((devices,), ("shard",)), "shard"

    if args.tiny:
        # d/k ≈ 512: deep enough in the paper's d >> k regime that the
        # modeled 1/P HBM saving clears the psum cost (the gate's subject)
        cells = [(65_536, 16, 128, 1), (65_536, 16, 128, 2)]
        solver_dims = (4096, 24)
    else:
        cells = [(65_536, 64, 512, 1), (65_536, 64, 512, 2),
                 (262_144, 128, 1024, 2)]
        solver_dims = (65_536, 64)

    rows = bench_grid(cells, mesh=mesh, axis=axis, iters=args.iters)
    solver = bench_solver(*solver_dims, mesh=mesh, axis=axis)

    all_exact = all(r["exact_row_sharded"] and r["exact_col_sharded"]
                    and r["exact_batch_sharded"] for r in rows)
    geo_modeled = geomean([r["modeled_speedup"] for r in rows])
    payload = {
        "meta": {
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "devices": devices,
            "tiny": args.tiny,
            "note": ("row/col/batch-sharded FlashSketch vs single device on "
                     f"{devices} {jax.default_backend()} devices; exact_* are "
                     "array_equal gates (psum'd per-kappa partials); "
                     "measured_* is host wall-clock (emulated devices share "
                     "cores — smoke only); modeled_* is engine.cost_of of "
                     "the two lowering records on TPU v5e: the row-sharded "
                     "partial (1/P HBM slab + ring psum at hw.ICI_BW) vs "
                     "the single-chip launch dispatch would actually make "
                     "(v1 when the fused v2 scratch cannot fit VMEM)"),
        },
        "rows": rows,
        "solver": solver,
        "all_exact": all_exact,
        "geomean_modeled_speedup": geo_modeled,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"\nwrote {args.out}: modeled geomean x{geo_modeled:.2f} at "
          f"{devices} devices, exact={'OK' if all_exact else 'FAIL'}, "
          f"solver={'OK' if solver['converged'] else 'FAIL'}")

    if not all_exact:
        print("FAIL: sharded apply lost bit-exactness vs single device",
              file=sys.stderr)
        return 1
    if not (geo_modeled >= 1.5):
        print(f"FAIL: modeled multi-chip scaling {geo_modeled:.2f}x < 1.5x "
              f"at {devices} devices", file=sys.stderr)
        return 1
    if not solver["converged"]:
        print("FAIL: distributed sketch-and-precondition did not converge",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
