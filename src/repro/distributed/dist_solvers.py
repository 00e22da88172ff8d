"""Distributed sketch-and-precondition least squares on a device mesh.

The multi-device form of ``solvers.sketch_precondition`` (Chen et al.'s
sparse-sign sketch-and-precondition, at Higgins & Boman's too-big-for-one-
device scale):

  1. sketch:   row-sharded ``A`` → ``SA`` via ``sketch_apply_sharded``
     (per-device partial kernels + one psum; ``SA`` lands REPLICATED, and
     bit-exact to the single-device sketch);
  2. factor:   ``R`` from the small replicated ``(k, n)`` sketch — every
     device factors the identical matrix, no collective;
  3. iterate:  LSQR through ``solvers.lsqr_operator`` with INJECTED
     ``shard_map``'d matvec/rmatvec — the forward product stays row-sharded
     (no gather of the (d,) iterate), the adjoint ``psum``s the (n,)
     reduction; per iteration the only collective is one (n,)-sized psum
     plus LSQR's scalar norms.

No step ever materializes all of ``A`` on one device, and the sketch means
the iteration count is O(1) in cond(A) — the whole point of running the
sketch, not the factorization, at scale.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.blockperm import BlockPermPlan
from repro.core.precision import fp32_matmul
from repro.distributed.sharded_apply import (_jit_shard_map, plan_for_mesh,
                                             shard_count,
                                             sketch_apply_sharded)
from repro.kernels import ops
from repro.solvers.sketch_precondition import (SolveResult,
                                               default_sketch_rows,
                                               lsqr_operator)


def sharded_matvec_ops(A: jnp.ndarray, mesh, axis: str):
    """(matvec, rmatvec) closures for a row-sharded tall operator.

    ``matvec(v)``: each device multiplies its row slab by the replicated
    ``(n,)`` vector — output ``(d,)`` stays sharded over ``axis`` (LSQR's
    u-vectors never need gathering; norms reduce them directly).
    ``rmatvec(u)``: per-device ``A_locᵀ u_loc`` followed by a psum — the
    one real collective per iteration, ``(n,)``-sized.

    ``A.shape[0]`` must be divisible by the axis size (see
    ``dist_sketch_precondition_lstsq`` for the zero-row padding that
    guarantees it).
    """
    num = shard_count(mesh, axis)
    if A.shape[0] % num != 0:
        raise ValueError(
            f"row-sharded matvec needs P | d: P={num}, d={A.shape[0]}")

    mv = _jit_shard_map(
        lambda Al, v: fp32_matmul(Al, v), mesh=mesh,
        in_specs=(P(axis, None), P(None)), out_specs=P(axis),
        check_vma=False)
    rmv = _jit_shard_map(
        lambda Al, ul: jax.lax.psum(fp32_matmul(Al.T, ul), axis), mesh=mesh,
        in_specs=(P(axis, None), P(axis)), out_specs=P(None),
        check_vma=False)
    return (lambda v: mv(A, v)), (lambda u: rmv(A, u))


def _pad_rows_to(A: jnp.ndarray, b: jnp.ndarray, multiple: int):
    """Append zero rows so the shard axis divides d — appended rows
    contribute 0 to every residual, so argmin ||Ax-b|| is unchanged."""
    d = A.shape[0]
    d_pad = ((d + multiple - 1) // multiple) * multiple
    if d_pad == d:
        return A, b
    A = jnp.pad(A, ((0, d_pad - d), (0, 0)))
    b = jnp.pad(b, (0, d_pad - d))
    return A, b


def dist_sketch_precondition_lstsq(
    A: jnp.ndarray,
    b: jnp.ndarray,
    mesh,
    axis: str,
    plan: Optional[BlockPermPlan] = None,
    *,
    k: Optional[int] = None,
    kappa: int = 4,
    s: int = 2,
    seed: int = 0,
    dtype: str = "float32",
    sampling_factor: float = 4.0,
    factorization: str = "qr",
    tol: float = 1e-6,
    max_iters: int = 100,
    impl: str = "auto",
    guard: bool = False,
    policy: Optional[object] = None,
) -> SolveResult:
    """Solve ``min_x ||A x - b||`` by DISTRIBUTED sketch-and-precondition.

    Args:
      A: (d, n) tall matrix, d >> n; may arrive as a committed row-sharded
        jax.Array (shard_map re-lays it out over ``axis`` either way).
      b: (d,) right-hand side.
      mesh / axis: the device mesh and the axis carrying the row shards;
        ``mesh.shape[axis]`` must divide the plan's block grid M (the
        default plan always satisfies this for power-of-two axis sizes).
      plan: optional pre-built sketch plan (wins over k/kappa/s/seed/dtype).
      k, kappa, s, seed, dtype, sampling_factor, factorization, tol,
        max_iters, impl: as in ``solvers.sketch_precondition_lstsq``.
      guard: run the distributed health guards before iterating — the
        psum'd ``SA`` must be BIT-IDENTICAL on every device (any replica
        deviation means a corrupted collective contribution: zeroed or
        permuted partial, dropped participant), plus the finite and
        triangular-condition guards on ``R``.  A ``failed`` verdict
        re-draws the sketch once (``RedrawPolicy``-derived seed) before
        giving up; the ``HealthReport`` lands on ``.health``.
      policy: optional ``repro.health.policy.RedrawPolicy`` (guard path
        only) controlling the re-draw budget.

    Returns:
      ``SolveResult``; the solution matches the single-device solver to
      iteration-level rounding (the preconditioner never biases the fixed
      point, and the sharded sketch is bit-exact, so R is identical).
    """
    d, n = A.shape
    if plan is None:
        plan = plan_for_mesh(
            d, k or default_sketch_rows(n, sampling_factor),
            shard_count(mesh, axis), kappa=kappa, s=s, seed=seed, dtype=dtype)
    num = shard_count(mesh, axis)

    def sketch_and_factor(p):
        # 1. sketch (psum'd partials -> replicated SA, bit-exact)
        SA = sketch_apply_sharded(p, A.astype(jnp.float32), mesh, axis, impl)
        # 2. factor (tiny n×n problem, replicated)
        return SA, ops.triangular_factor(SA.astype(jnp.float32),
                                         factorization)

    rpt = None
    if not guard:
        _, R = sketch_and_factor(plan)
    else:
        from repro.health import guards
        from repro.health import report as health_report
        from repro.health.policy import RedrawPolicy

        pol = policy if policy is not None else RedrawPolicy()
        rpt = health_report.HealthReport(op="dist_sketch_precondition_lstsq")

        def check(p, SA, R):
            findings = [
                guards.replica_consistency_guard(guards.replica_arrays(SA),
                                                 "SA"),
                guards.finite_guard(SA, "SA"),
                guards.finite_guard(R, "R"),
                guards.r_condition_guard(R, "R"),
            ]
            findings = [f for f in findings if f is not None]
            for f in findings:
                rpt.add(f)
            return health_report.worst_status(
                *[f.status for f in findings]) if findings else \
                health_report.HEALTHY

        R = None
        for attempt in pol.attempts(seed=plan.seed, kappa=plan.kappa,
                                    sampling_factor=sampling_factor):
            p = plan if attempt.index == 0 else plan_for_mesh(
                d, plan.k_req, num, kappa=plan.kappa, s=plan.s,
                seed=attempt.seed, dtype=dtype)
            pol.record(attempt)
            if attempt.index > 0:
                rpt.act(attempt.describe())
            rpt.attempts += 1
            SA, R = sketch_and_factor(p)
            if pol.accepts(check(p, SA, R)):
                break
            # structural bumps don't help a corrupted collective; the
            # ladder here is redraw-only — stop once redraws are spent
            if attempt.index >= pol.max_redraws:
                rpt.act("escalation_budget_exhausted")
                health_report.record("policy.budget_exhausted")
                break
    R = R.astype(b.dtype)
    # 3. iterate with sharded products
    Ap, bp = _pad_rows_to(A, b, num)
    matvec, rmatvec = sharded_matvec_ops(Ap, mesh, axis)
    res = lsqr_operator(matvec, rmatvec, bp, nvars=n, R=R,
                        tol=tol, max_iters=max_iters)
    res.health = rpt
    return res
