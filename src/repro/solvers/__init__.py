"""RandNLA solvers built on the FlashSketch kernels (paper §7 "standard
RandNLA benchmarks" — overdetermined least squares and low-rank
approximation driven by the sketch).

Three layers, lowest to highest risk/speed:

  sketch_precondition — sketch → QR/Cholesky factor → preconditioned
                        LSQR/CG to machine-precision least squares
                        (Rokhlin–Tygert / Blendenpik lineage; the
                        GPU-friendly formulation of Chen et al. 2025,
                        arXiv:2506.03070)
  sketch_solve        — direct sketch-and-solve regression and sketched
                        randomized range-finder / low-rank SVD (one-shot,
                        residual within (1+ε) of optimal)
  multisketch         — independent-seed multisketching with
                        residual-based adaptive restarts (Higgins & Boman
                        2025, arXiv:2508.14209)

All solvers consume ``core.blockperm.make_plan`` plans and apply the sketch
through ``kernels.ops`` (Pallas on TPU, XLA oracle on CPU), so the paper's
κ/s/dtype quality-vs-speed knobs surface directly in iteration counts.
"""
from repro.solvers.sketch_precondition import (  # noqa: F401
    SolveResult,
    lsqr,
    lsqr_operator,
    pcg_normal,
    sketch_precondition_lstsq,
)
from repro.solvers.sketch_solve import (  # noqa: F401
    sketch_and_solve_lstsq,
    sketched_rowspace,
    sketched_svd,
    subspace_embedding_eps,
)
from repro.solvers.multisketch import (  # noqa: F401
    MultisketchResult,
    multisketch_apply,
    multisketch_lstsq,
    multisketch_plans,
)


def solve_preset(A, b, preset, *, seed: int = 0, impl: str = "auto"):
    """Run a named solver operating point from
    ``configs.flashsketch_paper.SOLVER_PRESETS`` on ``min ||A x - b||``.

    Args:
      A, b: the (d, n) / (d,) problem.
      preset: a preset name (``"precise" | "default" | "fast" | "direct" |
        "multisketch"``) or a ``SolverPreset`` instance.
      seed: master sketch seed.
      impl: kernel dispatch forwarded to the sketch.

    Returns:
      ``SolveResult`` (iterative presets), ``MultisketchResult``
      (``num_sketches > 1``), or — for ``method="direct"`` — a
      ``SolveResult`` with ``iterations=0`` and ``converged=True``
      (one-shot: the answer is (1+ε)-optimal by construction, there is no
      tolerance to iterate toward).
    """
    import jax.numpy as jnp

    from repro.configs.flashsketch_paper import SOLVER_PRESETS, solver_sketch_rows
    from repro.core.blockperm import make_plan
    from repro.core.precision import fp32_matmul
    from repro.solvers.sketch_precondition import SolveResult

    if isinstance(preset, str):
        preset = SOLVER_PRESETS[preset]
    d, n = A.shape
    k = solver_sketch_rows(n, preset.sampling_factor)
    if preset.method == "direct":
        plan = make_plan(d, k, kappa=preset.kappa, s=preset.s, seed=seed,
                         dtype=preset.dtype)
        x = sketch_and_solve_lstsq(plan, A, b, impl=impl)
        relres = float(jnp.linalg.norm(fp32_matmul(A, x) - b)
                       / jnp.linalg.norm(b))
        return SolveResult(x=x, iterations=0, relres=relres, converged=True)
    if preset.num_sketches > 1:
        return multisketch_lstsq(
            A, b, k_each=k, t=preset.num_sketches, kappa=preset.kappa,
            s=preset.s, seed=seed, dtype=preset.dtype, tol=preset.tol,
            factorization=preset.factorization, impl=impl)
    return sketch_precondition_lstsq(
        A, b, k=k, kappa=preset.kappa, s=preset.s, seed=seed,
        dtype=preset.dtype, factorization=preset.factorization,
        method=preset.method, tol=preset.tol, max_iters=preset.max_iters,
        impl=impl)
