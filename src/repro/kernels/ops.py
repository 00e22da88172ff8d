"""Public jit'd wrappers for the FlashSketch kernels.

``sketch_apply(plan, A, impl=..., tn=..., dtype=...)`` handles padding, impl
dispatch, tile selection, and differentiation:

  * ``impl``: ``"pallas"`` (the fused v2 kernel, default on TPU),
    ``"pallas_v1"`` (the original κ-grid-reduction kernel, kept as a
    reference/benchmark baseline), or ``"xla"`` (pure-jnp oracle, default on
    CPU). ``"auto"`` picks per backend.
  * ``tn``: column-tile width.  ``None`` (default) defers to the autotuner
    cache (``kernels.tune.resolve_tn``) — tuned winner if one is cached for
    this shape class, else a VMEM-budget heuristic.  The lookup happens at
    *trace time*: load tuned winners (``tune.load_cache``) before the first
    jitted call for a shape, or pass ``tn`` explicitly — jit will not
    retrace when the cache changes later.
  * ``dtype``: streaming precision override (``"float32"``/``"bfloat16"``);
    ``None`` uses the plan-level knob.  bf16 streams the input at half the
    HBM traffic while accumulating in fp32 (robust per Jeendgar et al.).

Every entry point here is a THIN shell: all resolution — impl dispatch and
downgrades, tile selection, VMEM budgeting, the gather-fuse-or-materialize
decision, padding — lives in ``kernels.lowering``.  Each call builds one
``lowering.LaunchSpec``, resolves it with ``lowering.lower`` (memoized,
trace-time safe) and runs ``lowering.execute`` on the operands (an eager
call runs the record's memoized compiled executor); the ``custom_vjp``
wiring below is the only logic this module owns.  Inspect
any launch decision with ``lowering.explain(plan, n=..., ...)`` or the
``tools/explain_lowering.py`` CLI.  Each public entry point runs under a
``repro.ops.<entry>`` program span and scope (``repro.tracing``).

The VJP of ``Y = S A`` w.r.t. ``A`` is ``Sᵀ dY`` — the transpose kernel —
so sketching composes with ``jax.grad`` (needed when the sketch sits inside
a training graph, e.g. sketched gradient compression with error feedback).

Gather-fused path (the GraSS sparsify→sketch fusion): every forward entry
point takes ``row_index=`` — a ``(plan.d,)`` int array of source rows — and
computes ``Y = S @ A[row_index, :]`` in ONE kernel launch with no
``A[row_index]`` intermediate (``sketch_apply_indexed`` runs the
underlying custom_vjp primitive; its VJP scatters ``Sᵀ dY`` back into the
masked rows).  ``sketch_apply_batched`` folds a stack of matrices into the column
axis of that same single launch, so a B-example batch of sparsified
gradients is sketched at full tile width instead of B skinny launches.

Ragged ``n`` (``n`` not a multiple of the tile) is handled IN-KERNEL on
every path — the edge column tile rides the Pallas machinery (masked
loads/stores on TPU, internal pad+slice in interpret mode) and the gather
kernels clip their row DMAs — so no entry point ever materializes a
column-padded copy of the operand (regression-tested structurally: the
jaxpr contains no ``pad`` of the operand's column axis).
"""
from __future__ import annotations

import functools
import warnings
from typing import Literal, Optional

import jax
import jax.numpy as jnp

from repro.core.blockperm import BlockPermPlan
from repro.core.precision import fp32_matmul
from repro.health import report as health_report
from repro.kernels import lowering
from repro.tracing import scoped

Impl = Literal["auto", "pallas", "pallas_v1", "xla"]


def _lower(plan: BlockPermPlan, op: str, n: int, impl: Impl,
           tn: Optional[int], dtype: Optional[str], *,
           gather: bool = False, batch: int = 1) -> lowering.Lowering:
    return lowering.lower(plan, lowering.LaunchSpec(
        op=op, n=n, impl=impl, tn=tn, dtype=dtype, gather=gather,
        batch=batch))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 2, 3, 4))
def _sketch_apply_vjp(
    plan: BlockPermPlan,
    A: jnp.ndarray,
    impl: Impl = "auto",
    tn: Optional[int] = None,
    dtype: Optional[str] = None,
):
    """custom_vjp core of ``sketch_apply`` (VJP is ``Sᵀ dY``)."""
    return _sketch_apply_impl(plan, A, impl, tn, dtype)


@scoped("repro.ops.sketch_apply")
def sketch_apply(
    plan: BlockPermPlan,
    A: jnp.ndarray,
    impl: Impl = "auto",
    tn: Optional[int] = None,
    dtype: Optional[str] = None,
    *,
    row_index: Optional[jnp.ndarray] = None,
):
    """Apply the sketch: ``Y = S A`` (or ``S A[row_index, :]``, fused).

    Args:
      plan: frozen ``BlockPermPlan`` (static — participates in jit keys).
      A: ``(d, n)`` float array; rows beyond ``plan.d`` must not exist
        (padding to ``d_pad`` is internal).  Any float dtype; the kernel
        streams it in ``plan.stream_dtype`` (see ``dtype`` below).  With
        ``row_index`` the row count is instead the source dim ``d_src``.
      impl: ``"auto"`` (pallas on TPU, xla elsewhere), ``"pallas"`` (v2
        fused-κ kernel; silently downgrades to v1 if the fused Φ scratch
        cannot fit VMEM — the downgrade and its reason are recorded on the
        ``lowering.Lowering`` record), ``"pallas_v1"`` (κ-grid-reduction
        baseline), or ``"xla"`` (pure-jnp oracle).  Anything else raises
        ``ValueError``.
      tn: column-tile width for the Pallas paths; ``None`` defers to the
        autotuner cache (trace-time lookup).  Ignored by ``"xla"``.
      dtype: streaming-precision override, ``"float32"`` or ``"bfloat16"``;
        ``None`` keeps the plan's knob.  bf16 halves the HBM stream of A
        while the MXU accumulates in fp32; the output is always fp32.
      row_index: optional ``(plan.d,)`` int array of source rows; when
        given, computes ``S @ A[row_index, :]`` with the gather fused into
        the kernel load (no ``A[row_index]`` intermediate) — see
        ``sketch_apply_indexed``.

    Returns:
      ``(k, n)`` fp32 array, ``k = plan.k`` (the padded-up sketch dim).
      Differentiable in A: the VJP is ``sketch_apply_t`` (``Sᵀ dY``) at the
      same impl/tn/dtype (scattered back into the masked rows when
      ``row_index`` is given).
    """
    if row_index is None:
        return _sketch_apply_vjp(plan, A, impl, tn, dtype)
    return sketch_apply_indexed(plan, A, row_index, impl, tn, dtype)


def _sketch_apply_impl(plan, A, impl, tn, dtype):
    lw = _lower(plan, "fwd", A.shape[1], impl, tn, dtype)
    return lowering.execute(lw, A)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 2, 3, 4))
def _sketch_apply_t_vjp(
    plan: BlockPermPlan,
    Y: jnp.ndarray,
    impl: Impl = "auto",
    tn: Optional[int] = None,
    dtype: Optional[str] = None,
):
    """custom_vjp core of ``sketch_apply_t`` (VJP is ``S dX``)."""
    return _sketch_apply_t_impl(plan, Y, impl, tn, dtype)


@scoped("repro.ops.sketch_apply_t")
def sketch_apply_t(
    plan: BlockPermPlan,
    Y: jnp.ndarray,
    impl: Impl = "auto",
    tn: Optional[int] = None,
    dtype: Optional[str] = None,
    *,
    row_index: Optional[jnp.ndarray] = None,
    d_src: Optional[int] = None,
):
    """Apply the transposed sketch: ``X = Sᵀ Y`` (the un-sketch / VJP map).

    Args:
      plan: frozen ``BlockPermPlan``.
      Y: ``(k, n)`` float array (``k = plan.k`` or ``plan.k_pad``; shorter
        inputs are zero-padded to ``k_pad``).  Streamed in the effective
        streaming dtype, accumulated in fp32.
      impl: same valid values and semantics as ``sketch_apply``:
        ``"auto" | "pallas" | "pallas_v1" | "xla"``.
      tn / dtype: as in ``sketch_apply`` (``dtype`` rounds the Y stream to
        bf16 when ``"bfloat16"``; accumulation stays fp32).
      row_index / d_src: the dual of the gather path — when given, the
        compact ``(plan.d, n)`` result is scattered into rows ``row_index``
        of a zero ``(d_src, n)`` array (the un-sketch of a gather-fused
        sketch lands back at the masked coordinates).

    Returns:
      ``(d, n)`` fp32 array (logical d, padding stripped) — or ``(d_src,
      n)`` with the scatter.  Differentiable in Y; the VJP is
      ``sketch_apply``.
    """
    X = _sketch_apply_t_vjp(plan, Y, impl, tn, dtype)
    if row_index is None:
        return X
    if d_src is None:
        raise ValueError("row_index requires d_src (the scatter target dim)")
    out = jnp.zeros((d_src, X.shape[1]), X.dtype)
    return out.at[jnp.asarray(row_index, jnp.int32)].add(X)


def _sketch_apply_t_impl(plan, Y, impl, tn, dtype):
    lw = _lower(plan, "transpose", Y.shape[1], impl, tn, dtype)
    return lowering.execute(lw, Y)


def _apply_fwd(plan, A, impl, tn, dtype):
    return _sketch_apply_impl(plan, A, impl, tn, dtype), None


def _apply_bwd(plan, impl, tn, dtype, _res, dY):
    return (_sketch_apply_t_impl(plan, dY, impl, tn, dtype),)


def _apply_t_fwd(plan, Y, impl, tn, dtype):
    return _sketch_apply_t_impl(plan, Y, impl, tn, dtype), None


def _apply_t_bwd(plan, impl, tn, dtype, _res, dX):
    return (_sketch_apply_impl(plan, dX, impl, tn, dtype),)


_sketch_apply_vjp.defvjp(_apply_fwd, _apply_bwd)
_sketch_apply_t_vjp.defvjp(_apply_t_fwd, _apply_t_bwd)


# ---------------------------------------------------------------------------
# Gather-fused apply: Y = S @ A[row_index, :] in one launch.
# ---------------------------------------------------------------------------

def _sketch_apply_indexed_impl(plan, A, row_index, impl, tn, dtype):
    lw = _lower(plan, "fwd", A.shape[1], impl, tn, dtype, gather=True)
    return lowering.execute(lw, A, row_index=row_index)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 3, 4, 5))
def _sketch_apply_indexed_vjp(plan, A, row_index, impl, tn, dtype):
    """custom_vjp core of ``sketch_apply_indexed`` (VJP scatters ``Sᵀ dY``
    into the masked rows)."""
    return _sketch_apply_indexed_impl(plan, A, row_index, impl, tn, dtype)


@scoped("repro.ops.sketch_apply_indexed")
def sketch_apply_indexed(
    plan: BlockPermPlan,
    A: jnp.ndarray,
    row_index: jnp.ndarray,
    impl: Impl = "auto",
    tn: Optional[int] = None,
    dtype: Optional[str] = None,
):
    """Gather-fused sketch: ``Y = S @ A[row_index, :]`` in ONE launch.

    The sparsify→sketch fusion of the GraSS pipeline: the kernel keeps
    ``A`` in HBM and DMAs only the ``row_index`` rows into its gather
    scratch — no ``A[row_index]`` intermediate is ever written, which
    removes one full read+write of the sparsified matrix per application
    and (batched) turns B per-example gathers into tile-wide streams.

    Args:
      plan: frozen plan for the MASKED dim — ``plan.d`` must equal
        ``len(row_index)``.
      A: ``(d_src, n)`` float array, ``d_src >= 1``; only the indexed rows
        are read (streamed in the effective dtype, see ``dtype``).
      row_index: ``(plan.d,)`` int array of row indices into ``A``.
        Treated as non-differentiable (integer) data.
      impl / tn / dtype: as in ``sketch_apply``.  ``"xla"`` runs the
        materializing oracle ``flashsketch_ref(plan, A[row_index])``;
        ``"pallas_v1"`` (and the VMEM fallback) materialize the gather and
        use the regular kernels — the ``lowering.Lowering`` record keeps
        ``gather_fused=False`` plus the reason.

    Returns:
      ``(k, n)`` fp32 array.  Differentiable in ``A``: the VJP scatters
      ``Sᵀ dY`` into rows ``row_index`` of a zero ``(d_src, n)`` cotangent.
    """
    return _sketch_apply_indexed_vjp(plan, A, row_index, impl, tn, dtype)


def _indexed_fwd(plan, A, row_index, impl, tn, dtype):
    out = _sketch_apply_indexed_impl(plan, A, row_index, impl, tn, dtype)
    return out, (row_index, A.shape[0])


def _indexed_bwd(plan, impl, tn, dtype, res, dY):
    row_index, d_src = res
    # the scatter dual is single-sourced in sketch_apply_t(row_index=)
    dA = sketch_apply_t(plan, dY, impl, tn, dtype,
                        row_index=row_index, d_src=d_src)
    return dA, None


_sketch_apply_indexed_vjp.defvjp(_indexed_fwd, _indexed_bwd)


@scoped("repro.ops.blockrow_apply")
def blockrow_apply(
    plan: BlockPermPlan,
    A: jnp.ndarray,
    impl: Impl = "auto",
    tn: Optional[int] = None,
    dtype: Optional[str] = None,
    *,
    row_index: Optional[jnp.ndarray] = None,
):
    """FLASHBLOCKROW forward: ``Y = S_blockrow A`` (paper App. C).

    The gather-only appendix variant (iid block wiring, per-row pattern):
    reads A approximately once, but its embedding guarantees are weaker —
    eval-only, and intentionally has NO custom VJP (it never sits inside a
    training graph).

    Args:
      plan: frozen ``BlockPermPlan`` (wiring drawn iid per plan seed).
      A: ``(d, n)`` float array (``(d_src, n)`` with ``row_index``).
      impl: ``"auto" | "pallas" | "pallas_v1" | "xla"`` — same dispatch
        rules as ``sketch_apply``.
      tn / dtype: as in ``sketch_apply`` (bf16 streams A at half the HBM
        traffic, fp32 accumulate).
      row_index: optional ``(plan.d,)`` int rows; computes
        ``S_blockrow @ A[row_index, :]`` with the gather fused in-kernel
        (same contract as ``sketch_apply_indexed``).

    Returns:
      ``(k, n)`` fp32 array.
    """
    lw = _lower(plan, "blockrow", A.shape[1], impl, tn, dtype,
                gather=row_index is not None)
    return lowering.execute(lw, A, row_index=row_index)


def _lower_batched(plan, op, n, impl, tn, dtype, n_batch, gather):
    """One batch-aware lowering shared by the two batch entry points, so
    ``sketch_vectors`` and ``sketch_apply_batched`` resolve the identical
    launch (same tuner shape class, same downgrade ladder)."""
    return lowering.lower(plan, lowering.LaunchSpec(
        op=op, n=n, impl=impl, tn=tn, dtype=dtype, gather=gather,
        batch=n_batch))


def sketch_vectors(plan: BlockPermPlan, x: jnp.ndarray, impl: Impl = "auto",
                   tn: Optional[int] = None, dtype: Optional[str] = None,
                   *, row_index: Optional[jnp.ndarray] = None):
    """Sketch a batch of vectors laid out along the LAST axis.

    Args:
      plan: the frozen sketch draw (``core.blockperm.make_plan``).
      x: ``(..., d)`` float array; leading axes are an arbitrary batch
        (``(..., d_src)`` with ``row_index`` — e.g. a stack of raw
        per-example gradients whose sparsification is fused into the
        sketch).
      impl: one of ``"auto" | "pallas" | "pallas_v1" | "xla"`` (see
        ``sketch_apply``).
      tn / dtype: forwarded to ``sketch_apply``.  ``tn=None`` resolves
        against the autotuner's *batched* shape class exactly as
        ``sketch_apply_batched`` does (each vector is a width-1 matrix,
        the batch is folded into the column axis) — both entry points
        share ``_lower_batched``.
      row_index: optional ``(plan.d,)`` int rows — fused
        ``S x[..., row_index]`` (the GraSS sparsify→sketch fusion).

    Returns:
      ``(..., k)`` array, ``y[..., :] = S x[..., :]``.  Internally the batch
      is flattened into the column axis of one ``sketch_apply`` launch.
    """
    flat = x.reshape(-1, x.shape[-1])                 # (n, d)
    if tn is None:
        tn = _lower_batched(plan, "fwd", 1, impl, tn, dtype, flat.shape[0],
                            row_index is not None).tn
    Y = sketch_apply(plan, flat.T, impl, tn, dtype,
                     row_index=row_index)             # (k, n)
    return Y.T.reshape(*x.shape[:-1], plan.k)


@scoped("repro.ops.sketch_apply_batched")
def sketch_apply_batched(
    plan: BlockPermPlan,
    A: jnp.ndarray,
    impl: Impl = "auto",
    tn: Optional[int] = None,
    dtype: Optional[str] = None,
    *,
    row_index: Optional[jnp.ndarray] = None,
):
    """Apply S to a stack of matrices in ONE kernel launch.

    Args:
      plan: the frozen sketch draw.
      A: ``(..., d, n)`` float array — a batch of tall matrices sharing the
        sketch.  The batch axes are folded into the column axis (``S`` acts
        on the row axis only), so a ``(B, d, n)`` stack costs one launch on
        a ``(d, B·n)`` operand instead of ``B`` launches (or a vmap, which
        would re-trace the Pallas kernel per batch layout).  The cached Φ
        scratch is built once per launch and reused across the whole batch.
      impl / tn / dtype: forwarded to ``sketch_apply`` (same valid values).
        ``tn=None`` resolves against the autotuner's *batched* shape class
        (``batch=B`` on the ``LaunchSpec``), not the per-matrix width.
      row_index: optional ``(plan.d,)`` int rows shared by every batch
        element — fused ``S @ A[b][row_index, :]`` per element, still one
        launch (the GraSS per-example-gradient path).

    Returns:
      ``(..., k, n)`` array with ``out[b] = S @ A[b]`` for every batch
      index ``b``.  Differentiable in ``A`` (inherits the custom VJP of
      ``sketch_apply`` / ``sketch_apply_indexed``).
    """
    if A.ndim < 2:
        raise ValueError(f"A must be at least 2-D (d, n), got shape {A.shape}")
    batch = A.shape[:-2]
    d, n = A.shape[-2:]
    n_batch = 1
    for b in batch:
        n_batch *= b
    if tn is None:
        tn = _lower_batched(plan, "fwd", n, impl, tn, dtype, n_batch,
                            row_index is not None).tn
    flat = jnp.moveaxis(A.reshape((-1, d, n)), 0, 1).reshape(d, -1)  # (d, B·n)
    Y = sketch_apply(plan, flat, impl, tn, dtype, row_index=row_index)
    Y = jnp.moveaxis(Y.reshape(plan.k, -1, n), 1, 0)                 # (k, B·n)
    return Y.reshape(*batch, plan.k, n)


def sketch_qr(
    plan: BlockPermPlan,
    A: jnp.ndarray,
    impl: Impl = "auto",
    tn: Optional[int] = None,
    dtype: Optional[str] = None,
    factorization: str = "qr",
):
    """Sketch-and-factor: ``SA = S A`` plus a triangular factor of ``SA``.

    The workhorse of sketch-and-precondition (Rokhlin–Tygert / Blendenpik
    lineage): for tall ``A (d, n)`` with ``d >> n``, the ``(k, n)`` sketch
    ``SA`` is an approximate isometry on ``range(A)``, so the triangular
    ``R`` with ``SAᵀ SA = Rᵀ R`` makes ``A R⁻¹`` nearly orthonormal — LSQR
    on ``A R⁻¹`` then converges in O(1) iterations regardless of cond(A).

    Args:
      plan: the frozen sketch draw; ``plan.k`` should be a few × n.
      A: ``(d, n)`` float array, ``d >> n``.
      impl / tn / dtype: forwarded to ``sketch_apply`` (``dtype="bfloat16"``
        streams the sketch in bf16; the factorization itself is always fp32).
      factorization: ``"qr"`` (Householder QR of SA — backward stable) or
        ``"chol"`` (Cholesky of ``SAᵀSA`` — cheaper, squares the condition
        number of the sketch; fine when ``SA`` is well-conditioned, which a
        subspace-embedding sketch guarantees).

    Returns:
      ``(SA, R)``: the sketch ``(k, n)`` and upper-triangular ``R (n, n)``
      with ``SAᵀ SA = Rᵀ R`` (up to rounding).  ``R`` may be singular only
      if ``A`` is rank-deficient.
    """
    SA = sketch_apply(plan, A, impl, tn, dtype).astype(jnp.float32)
    return SA, triangular_factor(SA, factorization)


def triangular_factor(SA: jnp.ndarray, factorization: str = "qr") -> jnp.ndarray:
    """Upper-triangular R (n, n) with ``SAᵀ SA = Rᵀ R``, positive diagonal.

    Args:
      SA: ``(k, n)`` fp32 matrix (typically a sketch).
      factorization: ``"qr"`` (Householder QR — backward stable) or
        ``"chol"`` (Cholesky of the Gram — cheaper, squares the condition
        number).  Anything else raises ``ValueError``.

    Returns:
      R with a positive diagonal (fixes the QR/Cholesky sign ambiguity so
      the two factorizations agree and ``R⁻¹`` is well-defined).

    The Cholesky path squares cond(SA); on a (near-)rank-deficient Gram it
    silently returns NaN columns rather than raising.  On concrete (eager)
    inputs a non-finite Cholesky factor is detected and automatically
    downgraded to Householder QR, with the reason recorded in the health
    registry (``factor.chol_downgrade``) and warned once per call — under
    a jit tracer values are unreadable, so the jitted path keeps the
    caller's choice (guarded entry points run this eagerly).
    """
    if factorization == "qr":
        R = jnp.linalg.qr(SA, mode="r")
    elif factorization == "chol":
        R = jnp.linalg.cholesky(fp32_matmul(SA.T, SA)).T  # upper-triangular
        if not isinstance(R, jax.core.Tracer) and not bool(
                jnp.all(jnp.isfinite(R))):
            health_report.record(
                "factor.chol_downgrade",
                detail="non-finite Cholesky factor -> Householder QR")
            warnings.warn(
                "Cholesky of the sketch Gram returned non-finite entries "
                "(near-rank-deficient SA); falling back to Householder QR",
                RuntimeWarning, stacklevel=2)
            R = jnp.linalg.qr(SA, mode="r")
    else:
        raise ValueError(
            f"factorization must be 'qr' or 'chol', got {factorization!r}")
    sgn = jnp.sign(jnp.diagonal(R))
    sgn = jnp.where(sgn == 0, 1.0, sgn)
    return R * sgn[:, None]
