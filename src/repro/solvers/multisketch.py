"""Multisketch least squares with residual-based adaptive restarts.

Higgins & Boman (arXiv:2508.14209) observe that a cheap sparse sketch
(CountSketch there; BlockPerm-SJLT here) occasionally draws a poor
preconditioner — the failure probability is per-draw, so instead of paying
for one conservative large sketch, draw ``t`` small INDEPENDENT-SEED
sketches, stack them, and monitor the solver: if the residual decay rate
says the preconditioner is bad, throw it away and re-draw.  Expected cost
stays near the optimistic single-sketch cost while the tail disappears.

Everything is deterministic under a fixed master seed: per-sketch seeds are
derived by a fixed affine rule from (seed, round, slot), so two runs with
the same inputs produce bit-identical iterates and restart decisions.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp

from repro.core.blockperm import (BlockPermPlan, FAMILY_DEFAULT_S,
                                  make_plan)
from repro.core.precision import fp32_matmul
from repro.kernels import ops
from repro.solvers import sketch_precondition as sp

# Deterministic per-draw seed derivation (odd multipliers, splitmix-style).
_ROUND_STRIDE = 0x9E3779B1
_SLOT_STRIDE = 0x85EBCA77

# Seed space is 31 bits; the top 4 bits are a STREAM id, the low 27 the
# mixed draw.  Each sketch family gets its own stream, so independent
# draws across families provably come from disjoint seed ranges — no
# collision is possible between e.g. a countsketch redraw rung and a
# blockperm one, whatever the (round, slot) mixing lands on.
_STREAM_SHIFT = 27
_STREAM_MASK = 0xF
_MIX_MASK = (1 << _STREAM_SHIFT) - 1

_FAMILY_STREAMS = {"blockperm": 0, "countsketch": 1, "graph": 2}


def family_stream(family: str) -> int:
    """Disjoint 4-bit seed-stream id of a sketch family."""
    try:
        return _FAMILY_STREAMS[family]
    except KeyError:
        raise ValueError(
            f"no seed stream registered for family {family!r}; known: "
            f"{sorted(_FAMILY_STREAMS)}") from None


def derive_seed(master_seed: int, round_idx: int, slot: int,
                *, stream: Optional[int] = None) -> int:
    """Seed of sketch ``slot`` in restart round ``round_idx`` — a fixed
    injective-in-practice mixing of the master seed, so restarts are
    reproducible and all draws are distinct.

    ``stream`` selects one of 16 provably disjoint seed ranges (the top 4
    bits of the 31-bit seed space; use ``family_stream(name)`` for the
    per-family ids).  ``None`` inherits the master seed's own stream bits,
    so raw small master seeds keep deriving in stream 0 exactly as before
    the partition existed.
    """
    mixed = (master_seed
             + _ROUND_STRIDE * (round_idx + 1)
             + _SLOT_STRIDE * (slot + 1)) & _MIX_MASK
    if stream is None:
        stream = (master_seed >> _STREAM_SHIFT) & _STREAM_MASK
    return ((stream & _STREAM_MASK) << _STREAM_SHIFT) | mixed


def multisketch_plans(
    d: int,
    k_each: int,
    t: int,
    *,
    kappa: int = 4,
    s: Optional[int] = None,
    seed: int = 0,
    round_idx: int = 0,
    dtype: str = "float32",
    family: str = "blockperm",
) -> Tuple[BlockPermPlan, ...]:
    """``t`` independent-seed plans of ``k_each`` rows each (total t·k_each).

    ``family`` picks the sketch construction, its canonical per-column
    nonzero count (``s=None`` resolves to ``FAMILY_DEFAULT_S[family]`` —
    countsketch means s=1, graph means s=4) AND its disjoint seed stream,
    so mixing families under one master seed never collides draws."""
    stream = family_stream(family)     # validates family before s lookup
    if s is None:
        s = FAMILY_DEFAULT_S[family]
    return tuple(
        make_plan(d, k_each, kappa=kappa, s=s,
                  seed=derive_seed(seed, round_idx, i, stream=stream),
                  dtype=dtype, family=family)
        for i in range(t)
    )


def multisketch_apply(
    plans: Sequence[BlockPermPlan],
    A: jnp.ndarray,
    impl: str = "auto",
) -> jnp.ndarray:
    """Stacked sketch ``[S₁A; …; S_tA] / √t`` — rows (Σᵢ kᵢ, n).

    The 1/√t rescale keeps the stack an (approximate) isometry, so it plugs
    into ``ops.sketch_qr``-style factorizations unchanged.  Plans are
    static, so this is t kernel launches (one per independent seed), not a
    batched launch — the sketches differ in their Φ tables, not their data.
    """
    t = len(plans)
    parts = [ops.sketch_apply(p, A, impl) for p in plans]
    return jnp.concatenate(parts, axis=0) / jnp.sqrt(float(t))


@dataclasses.dataclass
class MultisketchResult:
    """Outcome of an adaptive multisketch solve.

    Attributes:
      x:           (n,) solution.
      iterations:  total LSQR iterations across all rounds.
      restarts:    number of re-sketch rounds taken (0 = first draw worked).
      relres:      final exact ``||Ax-b||/||b||``.
      converged:   relres <= tol.
      seeds:       derived seeds actually used, per round (for audit /
                   determinism tests).
    """

    x: jnp.ndarray
    iterations: int
    restarts: int
    relres: float
    converged: bool
    seeds: List[Tuple[int, ...]]


def multisketch_lstsq(
    A: jnp.ndarray,
    b: jnp.ndarray,
    *,
    k_each: Optional[int] = None,
    t: int = 2,
    kappa: int = 2,
    s: int = 1,
    seed: int = 0,
    dtype: str = "float32",
    tol: float = 1e-6,
    iters_per_round: int = 25,
    max_restarts: int = 3,
    stall_factor: float = 0.5,
    factorization: str = "qr",
    impl: str = "auto",
) -> MultisketchResult:
    """Adaptive multisketch sketch-and-precondition least squares.

    Per round: stack ``t`` independent ``k_each``-row sketches, factor, run
    up to ``iters_per_round`` preconditioned LSQR iterations warm-started
    from the current iterate.  If the round shrank the residual by less
    than ``stall_factor`` (i.e. the draw preconditions poorly — a good draw
    contracts by orders of magnitude in 25 iterations), re-draw with fresh
    round-derived seeds and repeat, keeping the iterate.

    Defaults use deliberately *cheap* per-draw sketches (κ=2, s=1, small
    k_each) — the restart safety-net is what makes that aggressive choice
    sound, per Higgins & Boman.

    Args:
      A, b: the (d, n) / (d,) least-squares problem.
      k_each: rows per individual sketch (default 2n, so the stack has 2tn).
      t: independent sketches per round.
      kappa, s, dtype: per-sketch BlockPerm-SJLT knobs.
      seed: master seed — the ONLY randomness input; fixed seed ⇒ bitwise
        reproducible trajectory including restart decisions.
      tol: target relative residual.
      iters_per_round / max_restarts / stall_factor: restart policy.
      factorization, impl: forwarded to the factor/sketch steps.

    Returns:
      ``MultisketchResult``.
    """
    d, n = A.shape
    if k_each is None:
        k_each = max(2 * n, n + 8)
    bnorm = float(jnp.linalg.norm(b))
    x = jnp.zeros(n, b.dtype)
    relres = 1.0
    total_iters = 0
    restarts = 0
    seeds_used: List[Tuple[int, ...]] = []

    def draw(round_idx: int) -> jnp.ndarray:
        plans = multisketch_plans(d, k_each, t, kappa=kappa, s=s, seed=seed,
                                  round_idx=round_idx, dtype=dtype)
        seeds_used.append(tuple(p.seed for p in plans))
        SA = multisketch_apply(plans, A.astype(jnp.float32), impl)
        return ops.triangular_factor(SA, factorization).astype(b.dtype)

    R = draw(0)
    # Total-iteration budget: the work one conservative single-sketch solve
    # would have spent; restarts spend it in chunks.
    budget = iters_per_round * (max_restarts + 2)
    while total_iters < budget:
        res = sp.lsqr(A, b, R=R, x0=x, tol=tol, max_iters=iters_per_round)
        total_iters += res.iterations
        new_relres = (float(jnp.linalg.norm(fp32_matmul(A, res.x) - b))
                      / max(bnorm, 1e-30))
        prev_relres = relres
        if new_relres < relres:
            x, relres = res.x, new_relres
        if relres <= tol:
            return MultisketchResult(x, total_iters, restarts, relres,
                                     True, seeds_used)
        # Residual-based restart rule: a good draw contracts the residual
        # by orders of magnitude per round; a round that fails to shrink it
        # below stall_factor × (previous) means the draw preconditions
        # poorly — discard it and re-draw with fresh round-derived seeds,
        # keeping the iterate.  Otherwise keep the factor and keep going.
        if new_relres > stall_factor * prev_relres:
            if restarts >= max_restarts:
                break
            restarts += 1
            R = draw(restarts)

    return MultisketchResult(x, total_iters, restarts, relres,
                             relres <= tol, seeds_used)
