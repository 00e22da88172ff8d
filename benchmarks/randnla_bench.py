"""RandNLA solver benchmark: sketch-and-precondition / sketch-and-solve.

    PYTHONPATH=src python -m benchmarks.randnla_bench            # smoke grid
    PYTHONPATH=src python -m benchmarks.randnla_bench --full     # larger grid

Exercises FlashSketch end-to-end the way the paper's evaluation does —
overdetermined least squares and low-rank approximation driven by the
sketch — and writes ``BENCH_randnla.json``.  For every (d, n) problem size
× κ ∈ {1, 2, 4} × streaming-precision policy — fp32/bf16 at the
ill-conditioned regime, the four fp8 policies (e4m3/e5m2 ×
nearest/stochastic) plus a matched bf16 reference at the
quantizer-reachable conditioning (see ``FP8_COND``):

  * unpreconditioned LSQR iterations to tol (the baseline every RandNLA
    paper compares against — blows up with cond(A));
  * sketch-and-precondition LSQR: iterations, final relative residual,
    measured wall time (sketch + factor + iterations);
  * one-shot sketch-and-solve relative residual;
  * modeled TPU-v5e time for the sketch step (roofline.sketch_model) plus
    flop-derived factor/iteration terms — the number to read off-TPU.

The solver iterations run in float64 (x64 enabled below) while the sketch
and factorization run in the plan's streaming precision — the standard
sketch-and-precondition split (low-precision preconditioner, full-precision
refinement; cf. Chen et al. arXiv:2506.03070).  The κ/dtype sweep makes the
paper's quality-vs-speed knob visible as iteration counts.
"""
from __future__ import annotations

import argparse
import json
import math
from typing import Dict, List

import jax

jax.config.update("jax_enable_x64", True)   # solver iterations in f64

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import time_fn  # noqa: E402
from repro.core.blockperm import make_plan  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.roofline import hw, sketch_model  # noqa: E402
from repro.solvers import (  # noqa: E402
    lsqr,
    multisketch_lstsq,
    sketch_and_solve_lstsq,
    sketched_svd,
    sketch_precondition_lstsq,
)
from repro.compile_cache import enable_compile_cache

KAPPAS = (1, 2, 4)
# the precision-policy sweep: fp32/bf16 at the ill-conditioned regime,
# plus the four fp8 streaming policies (e4m3/e5m2 × nearest/stochastic)
# from ``core.precision``
DTYPES = ("float32", "bfloat16")
FP8_DTYPES = ("fp8_e4m3", "fp8_e4m3_sr", "fp8_e5m2", "fp8_e5m2_sr")
TOL = 1e-6
# The fp8 preconditioner's quality floor is the quantization noise
# (e4m3 rounds at ~6% relative, e5m2 at ~12%), so its reach is bounded:
# noise × cond(A) must stay O(10) for the preconditioned iteration to
# converge like a preconditioned iteration.  The fp8 rows therefore run
# at cond = min(--cond, FP8_COND) — the regime the 1-byte stream is FOR
# — alongside a matched bf16 reference row at the same cond; at the
# fp32/bf16 regime's cond=1e4 an fp8 preconditioner saturates near
# relres ~ 1e-3 (measured), which is the documented cliff, not a bug.
FP8_COND = 1e2
# CI gate: every fp8 row must converge, with LSQR iteration inflation vs
# the same-(d, n, κ, cond) bf16 row bounded by this factor (+ absolute
# slack for tiny iteration counts).  fp8 quantizes the PRECONDITIONER
# only — iterations absorb the quality loss; the refinement runs f64.
# Measured worst case on the smoke grid is 3.58x (e4m3+SR at 8192x128);
# 4x + slack is the regression band, not a target.
FP8_ITER_INFLATION = 4.0
FP8_ITER_SLACK = 10


def make_ls_problem(d: int, n: int, cond: float, seed: int = 0):
    """Tall (d, n) least-squares problem with controlled cond(A) and a
    CONSISTENT rhs (b = A x*), so the optimal residual is 0 and relative
    residual is a clean convergence meter."""
    rng = np.random.default_rng(seed)
    U, _ = np.linalg.qr(rng.normal(size=(d, n)))
    V, _ = np.linalg.qr(rng.normal(size=(n, n)))
    svals = np.logspace(0.0, -math.log10(cond), n)
    A = (U * svals) @ V.T
    x_true = rng.normal(size=n)
    return A, A @ x_true, x_true


def modeled_sketch_lowering(plan, n: int):
    """The record of the sketch launch the solver issues, pinned to the v2
    kernel (the modeled hardware is a TPU even when the host traces the
    xla oracle) — modeled columns are priced from THIS record."""
    from repro import engine
    return engine.lower(plan, engine.LaunchSpec(op="fwd", n=n,
                                                impl="pallas"))


def modeled_solver_us(plan, n: int, iters: int, d: int) -> float:
    """Modeled TPU time: sketch kernel (roofline of the lowering record) +
    QR of the (k, n) sketch + per-iteration 2 matvecs (4 d n flops) +
    triangular solves."""
    sketch_us = sketch_model.cost_of(modeled_sketch_lowering(plan, n)).modeled_us
    qr_flops = 2.0 * plan.k * n * n
    iter_flops = iters * (4.0 * d * n + 2.0 * n * n)
    dense_us = 1e6 * (qr_flops + iter_flops) / hw.PEAK_FLOPS_FP32
    # matvecs are memory-bound on a (d, n) operand: charge the streams too
    iter_mem_us = 1e6 * iters * (2.0 * 4 * d * n) / hw.HBM_BW
    return sketch_us + dense_us + iter_mem_us


def bench_lstsq(problems, *, cond: float, seed: int, unprecond_cap: int,
                iters: int) -> List[Dict]:
    rows: List[Dict] = []
    # two condition regimes: the ill-conditioned fp32/bf16 sweep, and the
    # fp8 sweep (with a matched bf16 reference for the inflation gate) at
    # the quantizer-reachable conditioning — see FP8_COND above
    regimes = [(cond, DTYPES)]
    regimes.append((min(cond, FP8_COND), ("bfloat16",) + FP8_DTYPES))
    for (d, n) in problems:
        for prob_cond, dtypes in regimes:
            A_np, b_np, _ = make_ls_problem(d, n, prob_cond, seed)
            A, b = jnp.asarray(A_np), jnp.asarray(b_np)
            base = lsqr(A, b, tol=TOL, max_iters=unprecond_cap)
            print(f"[{d}x{n}] cond={prob_cond:.0e} unpreconditioned: "
                  f"it={base.iterations} relres={base.relres:.2e} "
                  f"converged={base.converged}")
            for kappa in KAPPAS:
                for dtype in dtypes:
                    k = max(4 * n, n + 8)
                    plan = make_plan(d, k, kappa=kappa, s=2, seed=seed,
                                     dtype=dtype)

                    def solve():
                        return sketch_precondition_lstsq(
                            A, b, plan=plan, tol=TOL, max_iters=200)

                    res = solve()
                    t_us = 1e6 * time_fn(lambda: solve().x, iters=iters)
                    x_ss = sketch_and_solve_lstsq(plan, A, b)
                    ss_relres = float(jnp.linalg.norm(A @ x_ss - b)
                                      / jnp.linalg.norm(b))
                    row = dict(
                        task="lstsq", d=d, n=n, k=plan.k, kappa=kappa, s=2,
                        dtype=dtype, cond=prob_cond,
                        iters_precond=res.iterations,
                        relres_precond=res.relres,
                        converged_precond=res.converged,
                        iters_unprecond=base.iterations,
                        relres_unprecond=base.relres,
                        converged_unprecond=base.converged,
                        relres_sketch_solve=ss_relres,
                        measured_precond_us=t_us,
                        modeled_precond_us=modeled_solver_us(
                            plan, n, res.iterations, d),
                        modeled_sketch_us=sketch_model.cost_of(
                            modeled_sketch_lowering(plan, n)).modeled_us,
                        lowering_sketch=modeled_sketch_lowering(
                            plan, n).describe(),
                    )
                    rows.append(row)
                    print(f"[{d}x{n}] cond={prob_cond:.0e} kappa={kappa} "
                          f"{dtype:>11}: it={res.iterations:>3} "
                          f"relres={res.relres:.2e} "
                          f"sketch&solve={ss_relres:.2e} "
                          f"measured={t_us/1e3:.1f}ms")
    return rows


def bench_multisketch(problems, *, cond: float, seed: int) -> List[Dict]:
    rows = []
    for (d, n) in problems:
        A_np, b_np, _ = make_ls_problem(d, n, cond, seed)
        A, b = jnp.asarray(A_np), jnp.asarray(b_np)
        res = multisketch_lstsq(A, b, seed=seed, tol=TOL)
        rows.append(dict(
            task="multisketch", d=d, n=n, t=2,
            iterations=res.iterations, restarts=res.restarts,
            relres=res.relres, converged=res.converged,
            seeds=[list(s) for s in res.seeds],
        ))
        print(f"[{d}x{n}] multisketch: it={res.iterations} "
              f"restarts={res.restarts} relres={res.relres:.2e}")
    return rows


def bench_guarded(problems, *, cond: float, seed: int,
                  iters: int) -> List[Dict]:
    """Guarded-solve rows: what the health layer costs on HEALTHY inputs.

    Runs ``sketch_precondition_lstsq`` with and without ``guard=True`` on
    the same problem and reports the overhead percentage plus the
    HealthReport counters — on a well-posed problem the ladder must accept
    draw #1 (attempts == 1) and the guards cost two Frobenius norms and a
    diagonal scan.
    """
    rows: List[Dict] = []
    for (d, n) in problems:
        A_np, b_np, _ = make_ls_problem(d, n, cond, seed)
        A, b = jnp.asarray(A_np), jnp.asarray(b_np)

        def solve(guard):
            return sketch_precondition_lstsq(A, b, seed=seed, tol=TOL,
                                             max_iters=200, guard=guard)

        t_plain = 1e6 * time_fn(lambda: solve(False).x, iters=iters)
        t_guard = 1e6 * time_fn(lambda: solve(True).x, iters=iters)
        res = solve(True)
        rows.append(dict(
            task="guarded_lstsq", d=d, n=n,
            health_status=res.health.status,
            attempts=res.health.attempts,
            converged=res.converged, relres=res.relres,
            guard_overhead_pct=100.0 * (t_guard - t_plain)
            / max(t_plain, 1e-12),
            health_counters=res.health.counters(),
        ))
        print(f"[{d}x{n}] guarded: status={res.health.status} "
              f"attempts={res.health.attempts} "
              f"overhead={(t_guard - t_plain) / 1e3:+.1f}ms")
    return rows


def bench_lowrank(problems, *, rank: int, seed: int) -> List[Dict]:
    """Sketched low-rank SVD vs. numpy's truncated SVD (quality + time)."""
    rows = []
    for (d, n) in problems:
        rng = np.random.default_rng(seed)
        # rapidly decaying spectrum: rank-r signal + small tail
        L = (rng.normal(size=(d, rank)) @ rng.normal(size=(rank, n))
             / math.sqrt(rank)
             + 0.01 * rng.normal(size=(d, n)))
        Lj = jnp.asarray(L.astype(np.float32))
        plan = make_plan(d, max(4 * rank, 64), kappa=4, s=2, seed=seed)
        U, svals, Vt = sketched_svd(plan, Lj, rank=rank)
        err = float(np.linalg.norm(
            np.asarray(U) @ np.diag(np.asarray(svals)) @ np.asarray(Vt) - L)
            / np.linalg.norm(L))
        U0, s0, Vt0 = np.linalg.svd(L, full_matrices=False)
        opt = float(np.linalg.norm(
            (U0[:, :rank] * s0[:rank]) @ Vt0[:rank] - L) / np.linalg.norm(L))
        rows.append(dict(task="lowrank_svd", d=d, n=n, rank=rank,
                         rel_err=err, optimal_rel_err=opt,
                         suboptimality=err / max(opt, 1e-30)))
        print(f"[{d}x{n}] sketched svd rank={rank}: err={err:.4f} "
              f"(optimal {opt:.4f})")
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="larger (d, n) grid")
    ap.add_argument("--out", default="BENCH_randnla.json")
    ap.add_argument("--cond", type=float, default=1e4,
                    help="condition number of the test matrices")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=1,
                    help="timing repetitions per row")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.full:
        problems = [(8192, 64), (16384, 128), (32768, 256)]
        unprecond_cap = 2000
    else:
        problems = [(4096, 64), (8192, 128)]
        unprecond_cap = 1000

    rows = bench_lstsq(problems, cond=args.cond, seed=args.seed,
                       unprecond_cap=unprecond_cap, iters=args.iters)
    ms_rows = bench_multisketch(problems, cond=args.cond, seed=args.seed)
    g_rows = bench_guarded(problems, cond=args.cond, seed=args.seed,
                           iters=args.iters)
    lr_rows = bench_lowrank(problems, rank=16, seed=args.seed)

    fp32 = [r for r in rows if r["dtype"] == "float32"]
    ok = all(r["relres_precond"] <= TOL
             and r["iters_precond"] < r["iters_unprecond"] for r in fp32)
    # fp8 gate: every fp8 row converged, iteration inflation vs the
    # matching bf16 row bounded (the "robustness surfaces as iteration
    # count" acceptance check for the precision refactor)
    bf16_iters = {(r["d"], r["n"], r["kappa"], r["cond"]):
                  r["iters_precond"]
                  for r in rows if r["dtype"] == "bfloat16"}

    def _ref(r):
        return bf16_iters[(r["d"], r["n"], r["kappa"], r["cond"])]

    fp8 = [r for r in rows if r["dtype"].startswith("fp8")]
    inflations = [r["iters_precond"] / max(_ref(r), 1) for r in fp8]
    fp8_ok = bool(fp8) and all(
        r["converged_precond"]
        and r["iters_precond"] <= (FP8_ITER_INFLATION * _ref(r)
                                   + FP8_ITER_SLACK)
        for r in fp8)
    payload = {
        "meta": {
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "interpret": jax.default_backend() != "tpu",
            "tol": TOL,
            "cond": args.cond,
            "problems": [list(p) for p in problems],
            "kappas": list(KAPPAS),
            "dtypes": list(DTYPES),
            "note": ("solver iterations in f64, sketch+factor in the "
                     "plan's streaming dtype; measured_* is CPU wall-clock "
                     "off-TPU, modeled_* is the TPU-v5e roofline"),
            "fp32_rows_all_converged_faster_than_unpreconditioned": ok,
            "fp8_rows_all_converged_with_bounded_inflation": fp8_ok,
            "fp8_dtypes": list(FP8_DTYPES),
            "fp8_cond": min(args.cond, FP8_COND),
            "fp8_iter_inflation_bound": FP8_ITER_INFLATION,
            "fp8_iter_slack": FP8_ITER_SLACK,
            "fp8_max_iter_inflation_vs_bf16": max(inflations, default=None),
        },
        "rows": rows,
        "multisketch": ms_rows,
        "guarded": g_rows,
        "lowrank": lr_rows,
    }
    from repro.health import report as health_report
    payload["meta"]["health_counters"] = health_report.counters()
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"\nwrote {args.out}: {len(rows)} lstsq rows, "
          f"fp32 precond-beats-unprecond on all rows: {ok}, "
          f"fp8 converged within {FP8_ITER_INFLATION}x bf16 iterations: "
          f"{fp8_ok} (max inflation "
          f"{max(inflations, default=float('nan')):.2f}x)")
    if not (ok and fp8_ok):
        # CI gate: the JSON above is already on disk as the debugging
        # artifact for exactly the failing rows
        raise SystemExit(1)


if __name__ == "__main__":
    main()
