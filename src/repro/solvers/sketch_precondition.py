"""Sketch-and-precondition least squares (the paper's headline RandNLA task).

For tall ``A (d, n)`` with ``d >> n``, solve ``min_x ||A x - b||_2`` to
machine precision:

  1. sketch:  ``SA = S A`` with a BlockPerm-SJLT plan, ``k = O(n)`` rows
     (one FlashSketch kernel launch — the only pass over ``A`` besides the
     iteration matvecs);
  2. factor:  ``R`` upper-triangular with ``SAᵀSA = RᵀR`` (QR of the small
     ``(k, n)`` sketch, or Cholesky of its Gram);
  3. iterate: LSQR (or CG on the normal equations) on the preconditioned
     operator ``A R⁻¹``, whose condition number is ``(1+ε)/(1-ε)`` when S
     is an ε-subspace-embedding for range(A).

Chen et al. (arXiv:2506.03070) show this sparse-sign variant is the
GPU-friendly way to run regression: the sketch is one memory-bound kernel,
the factorization is a tiny ``n × n`` problem, and the iteration count is
O(1) in cond(A).  The sketch quality knobs (κ, s, streaming dtype) move the
embedding distortion ε, which shows up directly — and only — in the
iteration count; the converged solution matches the direct solver because
the preconditioner never biases the fixed point.

Precision notes: the sketch + factorization run in the plan's streaming
precision (fp32 or bf16-streamed); the LSQR/CG iteration runs in the dtype
of ``A``/``b``, its products at full precision (``fp32_matmul`` — the TPU
default would round fp32 inputs to bf16) (pass float64 arrays under ``jax.config jax_enable_x64`` for
residuals below fp32 rounding).  A bf16 sketch only perturbs R — i.e. costs
a few extra iterations — never the attainable accuracy.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl

from repro.configs import flashsketch_paper
from repro.core.blockperm import (BlockPermPlan, FAMILY_DEFAULT_S,
                                  make_plan)
from repro.core.precision import fp32_matmul
from repro.kernels import lowering, ops


@dataclasses.dataclass
class SolveResult:
    """Outcome of an iterative least-squares solve.

    Attributes:
      x:          (n,) solution (original, un-preconditioned variables).
      iterations: number of LSQR/CG iterations actually run.
      relres:     final ``||A x - b|| / ||b||`` (recurrence estimate for
                  LSQR, recomputed exactly by the drivers that report it).
      converged:  whether ``relres <= tol`` was reached before the cap.
      lowering:   the ``kernels.lowering.Lowering`` record of the sketch
                  launch that built the preconditioner (``None`` for
                  drivers that never sketched, e.g. plain ``lsqr``) — how
                  the sketch actually ran: impl, tile, dtype, downgrades.
      health:     the ``repro.health.report.HealthReport`` of a guarded
                  solve — every guard verdict on the sketch/factor plus
                  the escalation-ladder actions taken (``None`` when the
                  solve ran unguarded).
    """

    x: jnp.ndarray
    iterations: int
    relres: float
    converged: bool
    lowering: Optional[object] = None
    health: Optional[object] = None


def _identity(v):
    return v


def _right_precond_ops(
    A: Optional[jnp.ndarray],
    R: Optional[jnp.ndarray],
    matvec: Optional[Callable] = None,
    rmatvec: Optional[Callable] = None,
):
    """(matvec, rmatvec, unprecondition) for the operator ``A R⁻¹``.

    The base operator is either the dense array ``A`` or INJECTED
    ``matvec``/``rmatvec`` closures (e.g. ``shard_map``'d products over a
    row-sharded A — see ``repro.distributed.dist_solvers``); the
    right-preconditioner composition is identical either way.
    """
    mv = matvec if matvec is not None else (lambda v: fp32_matmul(A, v))
    rmv = rmatvec if rmatvec is not None else (
        lambda u: fp32_matmul(A.T, u))
    if R is None:
        return mv, rmv, _identity
    Rt = R.T

    def pmatvec(v):                     # A R⁻¹ v
        return mv(jsl.solve_triangular(R, v, lower=False))

    def prmatvec(u):                    # R⁻ᵀ Aᵀ u
        return jsl.solve_triangular(Rt, rmv(u), lower=True)

    def unprecondition(y):              # x = R⁻¹ y
        return jsl.solve_triangular(R, y, lower=False)

    return pmatvec, prmatvec, unprecondition


def _lsqr_recurrence(matvec, rmatvec, unprec, base_matvec, b, x0, nvars,
                     *, tol: float, max_iters: int):
    """Golub–Kahan LSQR on ``min ||A R⁻¹ y - b||`` with x = R⁻¹ y.

    Operator-agnostic core (traced under jit by both the dense and the
    injected-ops drivers): ``matvec``/``rmatvec`` are the PRECONDITIONED
    products, ``base_matvec`` the raw ``A·`` used for the warm-start
    residual.  Carries the standard (u, v, w, phibar, rhobar) recurrence;
    stops when the recurrence residual estimate ``phibar / ||b||`` drops
    below ``tol`` or ``max_iters`` is hit.  Returns
    (x, iterations, relres_estimate).
    """
    dtype = b.dtype
    eps = jnp.finfo(dtype).tiny

    r0 = b - base_matvec(x0) if x0 is not None else b
    bnorm = jnp.maximum(jnp.linalg.norm(b), eps)
    beta = jnp.linalg.norm(r0)
    u = r0 / jnp.maximum(beta, eps)
    v = rmatvec(u)
    alpha = jnp.linalg.norm(v)
    v = v / jnp.maximum(alpha, eps)

    def cond(state):
        it, _, _, _, _, _, phibar, _ = state
        return jnp.logical_and(it < max_iters, phibar / bnorm > tol)

    def body(state):
        it, y, u, v, w, alpha, phibar, rhobar = state
        u_next = matvec(v) - alpha * u
        beta = jnp.linalg.norm(u_next)
        u_next = u_next / jnp.maximum(beta, eps)
        v_next = rmatvec(u_next) - beta * v
        alpha_next = jnp.linalg.norm(v_next)
        v_next = v_next / jnp.maximum(alpha_next, eps)
        rho = jnp.sqrt(rhobar ** 2 + beta ** 2)
        c = rhobar / rho
        s = beta / rho
        theta = s * alpha_next
        rhobar_next = -c * alpha_next
        phi = c * phibar
        phibar_next = s * phibar
        y = y + (phi / rho) * w
        w = v_next - (theta / rho) * w
        return (it + 1, y, u_next, v_next, w, alpha_next,
                phibar_next, rhobar_next)

    y0 = jnp.zeros(nvars, dtype)
    state = (jnp.int32(0), y0, u, v, v, alpha, beta, alpha)
    it, y, *_, phibar, _ = jax.lax.while_loop(cond, body, state)
    x = unprec(y)
    if x0 is not None:
        x = x + x0
    return x, it, phibar / bnorm


@functools.partial(jax.jit, static_argnames=("tol", "max_iters", "has_R"))
def _lsqr_jit(A, b, R, x0, *, tol: float, max_iters: int, has_R: bool):
    """Dense-array LSQR chunk (shape-keyed jit cache over A/b/R)."""
    matvec, rmatvec, unprec = _right_precond_ops(A, R if has_R else None)
    return _lsqr_recurrence(matvec, rmatvec, unprec,
                            lambda v: fp32_matmul(A, v),
                            b, x0, A.shape[1], tol=tol, max_iters=max_iters)


def lsqr(
    A: jnp.ndarray,
    b: jnp.ndarray,
    R: Optional[jnp.ndarray] = None,
    x0: Optional[jnp.ndarray] = None,
    tol: float = 1e-6,
    max_iters: Optional[int] = None,
    restart_every: int = 50,
) -> SolveResult:
    """LSQR for ``min ||A x - b||``, optionally right-preconditioned by R.

    Runs the Golub–Kahan recurrence in chunks of ``restart_every``
    iterations, recomputing the EXACT residual ``b - A x`` between chunks
    and warm-restarting from it.  In fp32 the recurrence residual estimate
    drifts from the true residual after a few dozen iterations (lost
    orthogonality), which stalls a non-restarted solver around 1e-5; the
    exact-residual restart is the textbook fix and costs one extra matvec
    per chunk.

    Args:
      A: (d, n) operator, d >= n.
      b: (d,) right-hand side.
      R: optional (n, n) upper-triangular preconditioner (from
        ``ops.sketch_qr``); iterations then run on ``A R⁻¹``.
      x0: optional warm start (the restart hook used by ``multisketch``).
      tol: stop when ``||A x - b|| / ||b|| <= tol`` (checked exactly at
        chunk boundaries, by recurrence estimate inside a chunk).
      max_iters: iteration cap (default ``4 n`` unpreconditioned, 200
        preconditioned — a subspace-embedding preconditioner converges in
        tens of iterations or something is wrong).
      restart_every: chunk length between exact-residual recomputations.

    Returns:
      ``SolveResult`` with the *recomputed* (not recurrence) final relres.
    """
    if max_iters is None:
        max_iters = 200 if R is not None else 4 * A.shape[1]
    R_arg = R if R is not None else jnp.zeros(())

    def run_chunk(x, chunk):
        return _lsqr_jit(A, b, R_arg, x, tol=float(tol),
                         max_iters=chunk, has_R=R is not None)

    return _restarted_drive(run_chunk, lambda x: fp32_matmul(A, x) - b, b, x0,
                            nvars=A.shape[1], tol=tol,
                            max_iters=int(max_iters),
                            restart_every=restart_every)


def lsqr_operator(
    matvec: Callable,
    rmatvec: Callable,
    b: jnp.ndarray,
    *,
    nvars: int,
    R: Optional[jnp.ndarray] = None,
    x0: Optional[jnp.ndarray] = None,
    tol: float = 1e-6,
    max_iters: Optional[int] = None,
    restart_every: int = 50,
) -> SolveResult:
    """LSQR on an ABSTRACT operator given by injected matvec ops.

    Identical semantics to ``lsqr`` (chunked Golub–Kahan with
    exact-residual restarts), but ``A`` never has to exist as one dense
    array: ``matvec(v) -> (d,)`` and ``rmatvec(u) -> (n,)`` may be
    arbitrary closures — ``repro.distributed.dist_solvers`` injects
    ``shard_map``'d products over a row-sharded A, so the iteration runs
    with only matrix SLABS resident per device.

    Args:
      matvec / rmatvec: the base (un-preconditioned) operator products.
      b: (d,) right-hand side.
      nvars: n, the number of unknowns (``rmatvec`` output length).
      R / x0 / tol / max_iters / restart_every: as in ``lsqr``.

    Returns:
      ``SolveResult`` with the recomputed (not recurrence) final relres.
    """
    if max_iters is None:
        max_iters = 200 if R is not None else 4 * nvars
    has_R = R is not None
    R_arg = R if has_R else jnp.zeros(())

    # One jit per lsqr_operator call (the closures are fresh objects);
    # fine for the distributed use where a solve is few, large chunks.
    @functools.partial(jax.jit, static_argnames=("chunk",))
    def _chunk_jit(Rv, x, *, chunk):
        mv, rmv, unprec = _right_precond_ops(
            None, Rv if has_R else None, matvec=matvec, rmatvec=rmatvec)
        return _lsqr_recurrence(mv, rmv, unprec, matvec, b, x, nvars,
                                tol=float(tol), max_iters=chunk)

    def run_chunk(x, chunk):
        return _chunk_jit(R_arg, x, chunk=chunk)

    return _restarted_drive(run_chunk, lambda x: matvec(x) - b, b, x0,
                            nvars=nvars, tol=tol, max_iters=int(max_iters),
                            restart_every=restart_every)


def _restarted_drive(run_chunk, resid, b, x0, *, nvars, tol, max_iters,
                     restart_every) -> SolveResult:
    """Shared chunk driver: run ``restart_every``-iteration recurrence
    chunks, recompute the EXACT residual between chunks, warm-restart, and
    stop on convergence or stall (precision floor)."""
    bnorm = float(jnp.linalg.norm(b))
    x = x0
    total = 0
    relres = float("inf")
    while total < max_iters:
        chunk = min(int(restart_every), max_iters - total)
        x_new, it, _ = run_chunk(x, chunk)
        total += int(it)
        new_relres = float(jnp.linalg.norm(resid(x_new))) / max(bnorm, 1e-30)
        stalled = new_relres >= relres
        if new_relres < relres:
            x, relres = x_new, new_relres
        if relres <= tol:
            break
        if stalled:
            # the chunk produced no improvement, so x is unchanged and the
            # next chunk would deterministically recompute the identical
            # result — we are at the precision floor; stop now instead of
            # burning the rest of max_iters on byte-identical work
            break
    if x is None:               # max_iters == 0 edge case
        x = jnp.zeros(nvars, b.dtype)
    return SolveResult(x=x, iterations=total, relres=relres,
                       converged=relres <= tol)


@functools.partial(jax.jit, static_argnames=("tol", "max_iters"))
def _pcg_normal_jit(A, b, R, *, tol: float, max_iters: int):
    """CG on the preconditioned normal equations ``(AR⁻¹)ᵀ(AR⁻¹) y = (AR⁻¹)ᵀb``."""
    matvec, rmatvec, unprec = _right_precond_ops(A, R)
    dtype = b.dtype
    rhs = rmatvec(b)
    rhs_norm = jnp.maximum(jnp.linalg.norm(rhs), jnp.finfo(dtype).tiny)

    def normal_op(y):
        return rmatvec(matvec(y))

    y0 = jnp.zeros(A.shape[1], dtype)
    r0 = rhs
    state = (jnp.int32(0), y0, r0, r0, jnp.vdot(r0, r0))

    def cond(state):
        it, _, r, _, rr = state
        return jnp.logical_and(it < max_iters,
                               jnp.sqrt(rr) / rhs_norm > tol)

    def body(state):
        it, y, r, p, rr = state
        Ap = normal_op(p)
        alpha = rr / jnp.vdot(p, Ap)
        y = y + alpha * p
        r = r - alpha * Ap
        rr_next = jnp.vdot(r, r)
        p = r + (rr_next / rr) * p
        return (it + 1, y, r, p, rr_next)

    it, y, _, _, rr = jax.lax.while_loop(cond, body, state)
    return unprec(y), it, jnp.sqrt(rr) / rhs_norm


def pcg_normal(
    A: jnp.ndarray,
    b: jnp.ndarray,
    R: jnp.ndarray,
    tol: float = 1e-6,
    max_iters: int = 100,
) -> SolveResult:
    """Preconditioned CG on the normal equations (cheaper per-iter than
    LSQR — one fewer vector — but squares the effective condition number;
    safe here because ``A R⁻¹`` is near-orthonormal).

    Args as ``lsqr``, but ``tol`` is on the NORMAL-EQUATION residual
    ``||(AR⁻¹)ᵀ(Ax-b)||`` relative to ``||(AR⁻¹)ᵀb||`` — the natural CG
    quantity — and ``converged`` reports that criterion.  The returned
    ``relres`` is still the plain residual ``||Ax-b||/||b||`` for
    comparability with ``lsqr`` (it is NOT what ``converged`` tests).
    """
    x, it, normal_relres = _pcg_normal_jit(A, b, R, tol=float(tol),
                                           max_iters=int(max_iters))
    relres = float(jnp.linalg.norm(fp32_matmul(A, x) - b)
                   / jnp.linalg.norm(b))
    return SolveResult(x=x, iterations=int(it), relres=relres,
                       converged=bool(float(normal_relres) <= tol))


def default_sketch_rows(n: int, sampling_factor: float = 4.0) -> int:
    """Sketch size k for an n-column problem (k = ⌈γ n⌉, γ ≈ 4 gives
    ε ≈ 1/2 distortion and ~20 LSQR iterations to 1e-14).  Delegates to
    the shared sizing rule in ``configs.flashsketch_paper``."""
    return flashsketch_paper.solver_sketch_rows(n, sampling_factor)


def _run_iteration(A, b, R, method, tol, max_iters) -> SolveResult:
    if method == "lsqr":
        return lsqr(A, b, R=R, tol=tol, max_iters=max_iters)
    if method == "cg":
        return pcg_normal(A, b, R, tol=tol, max_iters=max_iters)
    raise ValueError(f"method must be 'lsqr' or 'cg', got {method!r}")


def _diverged(res: SolveResult) -> bool:
    """Mid-solve divergence: the exact-residual chunk driver stopped
    without convergence at a residual no better than x = 0 (or NaN) — the
    preconditioner actively hurt, not merely underperformed."""
    import math
    return (not res.converged
            and (not math.isfinite(res.relres) or res.relres >= 1.0))


def sketch_precondition_lstsq(
    A: jnp.ndarray,
    b: jnp.ndarray,
    plan: Optional[BlockPermPlan] = None,
    *,
    k: Optional[int] = None,
    kappa: int = 4,
    s: Optional[int] = None,
    seed: int = 0,
    dtype: str = "float32",
    precision: Optional[object] = None,
    family: str = "blockperm",
    sampling_factor: float = 4.0,
    factorization: str = "qr",
    method: str = "lsqr",
    tol: float = 1e-6,
    max_iters: int = 100,
    impl: str = "auto",
    guard: bool = False,
    policy: Optional[object] = None,
    probe: bool = False,
) -> SolveResult:
    """Solve ``min_x ||A x - b||`` by sketch-and-precondition.

    Args:
      A: (d, n) tall matrix (d >> n).
      b: (d,) right-hand side.
      plan: optional pre-built sketch plan (wins over k/kappa/s/seed/dtype).
      k: sketch rows; default ``sampling_factor * n``.
      kappa, s, seed, dtype: BlockPerm-SJLT knobs (see ``make_plan``);
        κ/s/dtype trade sketch speed against preconditioner quality, i.e.
        against LSQR iteration count.
      precision: optional precision policy — a registered name/alias
        (``"fp8_e4m3_sr"``, ``"bf16"``, ...) or a ``core.precision.Precision``
        record.  Overrides ``dtype`` when given; the policy rides the plan,
        so lower-precision streaming surfaces directly as a higher
        ``.iterations`` count, and the guarded path reads its per-policy
        isometry/OSE tolerance bands (fp8 draws are judged against the
        widened fp8 bands, not the fp32 ones).
      family: sketch construction ("blockperm" | "countsketch" | "graph")
        — the preconditioning pipeline is family-parametric; the family
        rides the plan through every guard rung and re-sketch restart.
        ``s=None`` resolves to the family's CANONICAL nonzero count
        (``FAMILY_DEFAULT_S``: blockperm 2, countsketch 1, graph 4) and
        the plan seed is drawn from the family's disjoint seed stream —
        the same construction ``variants.make_sketch(family, ...)``
        builds, so e.g. countsketch and graph solves under one master
        seed are genuinely different sketches.
      factorization: "qr" | "chol" (see ``ops.sketch_qr``).
      method: "lsqr" | "cg".
      tol / max_iters: iteration stopping rule.
      impl: kernel dispatch for the sketch ("auto"|"pallas"|"pallas_v1"|"xla").
      guard: run the post-launch validators (``repro.health.guards``) on
        every sketch/factor and climb the ``RedrawPolicy`` escalation
        ladder on a ``failed`` verdict (re-draw seed → bump κ → bump the
        sampling factor — the paper's δ/κ tradeoff run in reverse); a
        diverging iteration additionally triggers a re-sketch restart.
        The guarded path attaches a ``HealthReport`` to ``.health``.
      policy: optional ``repro.health.policy.RedrawPolicy`` overriding the
        default escalation budget (ignored unless ``guard=True``).
      probe: with ``guard=True``, additionally run the O(d·n²) ground-truth
        OSE probe (σ_min of S·orth(A)) per attempt — the strictest
        acceptance check; off by default (the cheap guards catch the same
        catastrophic draws at a fraction of the cost).

    Returns:
      ``SolveResult``; ``.iterations`` is the paper's quality-vs-speed knob
      made visible (κ=1 sketches are fastest but precondition worst).
    """
    d, n = A.shape
    if precision is not None:
        from repro.core import precision as precision_mod
        dtype = precision_mod.canonical(
            precision.name if isinstance(precision, precision_mod.Precision)
            else precision)
    if s is None:
        # unknown families fall through to make_plan/family_stream, whose
        # ValueError names the valid set
        s = FAMILY_DEFAULT_S.get(family, 2)
    if plan is None and family != "blockperm":
        # match variants.make_sketch: non-blockperm families draw their
        # plan seed from the family's disjoint stream
        from repro.solvers.multisketch import derive_seed, family_stream
        seed = derive_seed(seed, 0, 0, stream=family_stream(family))
    if not guard:
        if plan is None:
            plan = make_plan(d, k or default_sketch_rows(n, sampling_factor),
                             kappa=kappa, s=s, seed=seed, dtype=dtype,
                             family=family)
        _, R = ops.sketch_qr(plan, A.astype(jnp.float32), impl,
                             factorization=factorization)
        res = _run_iteration(A, b, R.astype(b.dtype), method, tol, max_iters)
        # attach the record of how the sketch actually launched (trace-time
        # metadata only — the engine memoizes, so this re-lower is free)
        res.lowering = lowering.lower(
            plan, lowering.LaunchSpec(op="fwd", n=n, impl=impl))
        return res

    # ---- guarded path (eager by construction: guards read values) -------
    from repro.health import guards
    from repro.health import report as health_report
    from repro.health.policy import RedrawPolicy

    pol = policy if policy is not None else RedrawPolicy()
    rpt = health_report.HealthReport(op="sketch_precondition_lstsq")
    A32 = A.astype(jnp.float32)
    base_seed = plan.seed if plan is not None else seed
    base_kappa = plan.kappa if plan is not None else kappa
    base_s = plan.s if plan is not None else s
    base_k = plan.k_req if plan is not None else k
    base_family = plan.family if plan is not None else family

    def draw_and_check(p):
        """Sketch + factor + guard verdict for one attempt's plan."""
        SA, R = ops.sketch_qr(p, A32, impl, factorization=factorization)
        # judge the draw against ITS policy's tolerance bands — an fp8
        # sketch that lands inside the widened fp8 band is a healthy fp8
        # sketch, not a degraded fp32 one
        findings = [guards.finite_guard(SA, "SA"),
                    guards.isometry_guard(A32, SA, "SA",
                                          **p.precision.isometry_band()),
                    guards.finite_guard(R, "R"),
                    guards.r_condition_guard(R, "R")]
        if probe:
            findings.append(guards.ose_probe(p, A32, impl=impl,
                                             **p.precision.ose_band()))
        findings = [f for f in findings if f is not None]
        for f in findings:
            rpt.add(f)
        verdict = health_report.worst_status(
            *[f.status for f in findings]) if findings else \
            health_report.HEALTHY
        return R, verdict

    accepted = None          # (plan, R)
    best = None              # least-bad fallback if the budget exhausts
    best_rank = len(health_report.STATUS_ORDER)
    for attempt in pol.attempts(seed=base_seed, kappa=base_kappa,
                                sampling_factor=sampling_factor):
        if attempt.index == 0 and plan is not None:
            p = plan
        else:
            p = pol.plan_for(attempt, d, n, s=base_s, dtype=dtype, k=base_k,
                             family=base_family)
        pol.record(attempt)
        if attempt.index > 0:
            rpt.act(attempt.describe())
        rpt.attempts += 1
        R, verdict = draw_and_check(p)
        rank = health_report.STATUS_ORDER.index(verdict)
        if rank < best_rank:
            best, best_rank = (p, R), rank
        if pol.accepts(verdict):
            accepted = (p, R)
            break
    if accepted is None:
        # every rung failed: proceed with the least-bad draw rather than
        # silently returning garbage or raising — the report says so.
        accepted = best
        rpt.act("escalation_budget_exhausted")
        health_report.record("policy.budget_exhausted")
    p, R = accepted
    res = _run_iteration(A, b, R.astype(b.dtype), method, tol, max_iters)

    # Mid-solve divergence → re-sketch restart (the multisketch restart
    # rule applied to the guarded single-sketch solver): an accepted factor
    # whose iteration still diverges means the draw was bad in a way the
    # cheap guards missed; throw it away and re-draw from a disjoint seed
    # stream.
    from repro.solvers.multisketch import derive_seed, \
        family_stream   # lazy: no cycle
    restarts = 0
    while _diverged(res) and restarts < pol.max_resketch_restarts:
        restarts += 1
        new_seed = derive_seed(p.seed, pol.budget + restarts, 3,
                               stream=family_stream(p.family))
        p = make_plan(d, p.k_req, kappa=p.kappa, s=p.s, seed=new_seed,
                      dtype=dtype, family=p.family)
        rpt.act(f"resketch_restart(seed={new_seed})")
        health_report.record("policy.resketch_restart")
        R, verdict = draw_and_check(p)
        rpt.attempts += 1
        res = _run_iteration(A, b, R.astype(b.dtype), method, tol, max_iters)

    res.health = rpt
    res.lowering = lowering.lower(
        p, lowering.LaunchSpec(op="fwd", n=n, impl=impl))
    return res
