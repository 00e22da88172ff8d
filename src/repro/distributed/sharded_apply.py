"""Multi-device FlashSketch: ``shard_map``-mapped apply over a device mesh.

Three sharding layouts, in decreasing collective cost:

  * **Row-sharded** (``sketch_apply_sharded``) — the d ≫ k regime the paper
    targets, at matrices too large for one device (Higgins & Boman's
    multisketching setting): ``A``'s row axis is partitioned so each of the
    P devices owns a CONTIGUOUS range of ``M_loc = M/P`` of the plan's M
    input blocks (``P | M``).  Each device runs the local partial kernel on
    its block slab and the ``(k, n)`` partials are ``psum``'d — ``S`` is
    never gathered and no device ever materializes all of ``A``.
  * **Column-sharded** (``sketch_apply_colsharded``) — ``n`` partitioned;
    embarrassingly parallel (every device applies the full sketch to its
    column slab, NO collective), output column-sharded.
  * **Batch-sharded** (``sketch_apply_batched_sharded``) — a stack of
    matrices partitioned over its batch axis; each device runs the fused
    batched (optionally gather-fused) launch on its local stack.  This is
    the distributed GraSS featurize layout (``attribution.grass``).

Bit-exactness (tested, fp32 AND bf16): the row-sharded path is
``array_equal`` to single-device ``ops.sketch_apply``, not merely close.
The trick is the reduction layout: each device produces PER-ℓ partials
``(κ, k_pad, n)`` where, for every ``(ℓ, output-block)`` pair, exactly ONE
device holds a nonzero value (block ownership is a partition and π_ℓ is a
permutation).  The ``psum`` therefore only ever adds exact zeros to the
one real contribution — an exact fp32 reduction regardless of device
order — and the κ-fold afterwards runs in the reference oracle's
summation order.  Shipping κ·k·n instead of k·n over ICI is the price of
exactness; ``roofline.sketch_model.dist_sketch_cost`` charges it.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import hashing
from repro.core.blockperm import BlockPermPlan, make_plan
from repro.kernels import flashsketch as fsk
from repro.kernels import lowering
from repro.kernels import ops
from repro.kernels import ref as kref

# The VMEM predicate is single-sourced in the lowering engine (shared with
# ops dispatch); re-exported here because it is part of this package's API.
partial_fits_vmem = lowering.partial_fits_vmem


def _jit_shard_map(fn, **kwargs):
    """``jax.shard_map`` compiled as ONE program: called eagerly, a
    shard_map runs its body op by op, compiling a multi-device program
    per primitive."""
    return jax.jit(jax.shard_map(fn, **kwargs))


def shard_count(mesh, axis: str) -> int:
    """Size of one mesh axis (the sketch-shard degree P)."""
    return mesh.shape[axis]


def check_row_partition(plan: BlockPermPlan, num_shards: int) -> int:
    """Validate ``P | M`` and return the per-device block count M_loc."""
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if plan.M % num_shards != 0:
        raise ValueError(
            f"row-sharding needs the shard count to divide the block grid: "
            f"P={num_shards} does not divide M={plan.M} "
            f"(rebuild the plan with block_rows= so that P | M)")
    return plan.M // num_shards


def plan_for_mesh(
    d: int,
    k: int,
    num_shards: int,
    *,
    kappa: int = 4,
    s: int = 2,
    seed: int = 0,
    dtype: str = "float32",
) -> BlockPermPlan:
    """``make_plan`` with a block grid that ``P`` divides.

    The auto planner's grid is kept when ``P | M``: it is the one sized
    for one chip's VMEM, so the per-device partial kernel (one (B_r, B_c)
    Φ tile) fits as well.  Otherwise ``B_r`` is pinned so the grid has
    exactly ``M = max(P, κ)`` blocks (powers of two).  Tiny sketches
    (``k < P·s``) cannot host P shards and fail ``check_row_partition``
    downstream.
    """
    import math as _math

    from repro.core.blockperm import _next_pow2
    plan = make_plan(d, k, kappa=kappa, s=s, seed=seed, dtype=dtype)
    if plan.M % max(1, num_shards) == 0:
        return plan
    m_target = max(_next_pow2(max(1, num_shards)), _next_pow2(max(1, kappa)))
    br = max(_next_pow2(_math.ceil(k / m_target)), _next_pow2(max(1, s)))
    return make_plan(d, k, kappa=kappa, s=s, seed=seed, block_rows=br,
                     dtype=dtype)


def partial_tables(plan: BlockPermPlan, lo, M_loc: int,
                   rows_pattern: bool = False) -> jnp.ndarray:
    """Prefetch/scatter tables for the device-local partial apply.

    ``lo`` (the first owned block index) may be traced — under ``shard_map``
    it is ``axis_index * M_loc``.

    Default (BLOCKPERM): the wiring π_ℓ is a permutation, so each owned
    input block ``h = lo + m`` feeds exactly one output block
    ``g = π_ℓ⁻¹(h)`` per level — returns the COMPACT ``(2, κ, M_loc)``
    ``[global g, global h]`` table driving the owned-pair-only kernel grid
    (per-chip work shards 1/P) and the caller-side scatter.

    ``rows_pattern`` (FLASHBLOCKROW): iid wiring is not a permutation, so
    there is no compact form — returns the MASKED ``(3, κ, M)``
    ``[local gather index, global h, owned flag]`` table for the
    full-grid kernel; non-owned entries keep a VALID clipped gather index
    and their Φ is zeroed by the owned flag.
    """
    lo = jnp.asarray(lo, jnp.int32)
    if rows_pattern:
        h = jnp.asarray(fsk._blockrow_table(plan), jnp.int32)   # (κ, M)
        owned = ((h >= lo) & (h < lo + M_loc)).astype(jnp.int32)
        local = jnp.clip(h - lo, 0, M_loc - 1)
        return jnp.stack([local, h, owned])
    inv = jnp.asarray(fsk._inv_neighbor_table(plan), jnp.int32)  # (κ, M)
    h_of_m = lo + jnp.arange(M_loc, dtype=jnp.int32)             # (M_loc,)
    g_of_m = jnp.take(inv, h_of_m, axis=1)                       # (κ, M_loc)
    h_rows = jnp.broadcast_to(h_of_m[None, :], (plan.kappa, M_loc))
    return jnp.stack([g_of_m, h_rows])


def _phi_pairs(plan: BlockPermPlan, g_of_m: jnp.ndarray,
               h_of_m: jnp.ndarray) -> jnp.ndarray:
    """Φ for explicit (g, h) block pairs: (M_loc, Br, Bc), ±1/0 unscaled.

    The explicit-g generalization of ``kref._phi_all_blocks`` (which fixes
    ``g = arange(M)``): the hashes are elementwise in (g, h, u, i), so
    each slice is bitwise identical to the corresponding row of the
    full-grid build — the property the compact partials' exactness rests
    on.
    """
    g = g_of_m[:, None].astype(jnp.uint32)                # (M_loc, 1)
    h = h_of_m[:, None].astype(jnp.uint32)                # (M_loc, 1)
    u = jnp.arange(plan.Bc, dtype=jnp.uint32)[None, :]    # (1, Bc)
    r_iota = jnp.arange(plan.Br, dtype=jnp.int32)         # (Br,)
    phi = jnp.zeros((g_of_m.shape[0], plan.Br, plan.Bc), jnp.float32)
    chunk = plan.chunk
    for i in range(plan.s):
        hsh = hashing.hash_words(np.uint32(plan.seed), g, h, u, np.uint32(i))
        rows = i * chunk + hashing.hash_mod(hsh, chunk)   # (M_loc, Bc)
        signs = hashing.hash_to_unit_sign(hsh)
        onehot = (r_iota[None, :, None] == rows[:, None, :]).astype(
            jnp.float32)
        phi = phi + onehot * signs[:, None, :]
    return phi


def _partial_oracle(plan: BlockPermPlan, slab: jnp.ndarray,
                    tables: jnp.ndarray,
                    rows_pattern: bool = False) -> jnp.ndarray:
    """Pure-jnp per-ℓ partials, unscaled — the off-TPU twin of
    ``fsk.flashsketch_pallas_partial`` (same compact/masked split).

    Default: COMPACT ``(κ, M_loc·Br, n)`` over owned pairs only — the
    einsum is the batch-split of the single-device oracle's (per-g
    contractions are independent batch elements), so each slice is
    bitwise identical to the corresponding rows of
    ``kref.flashsketch_ref``'s per-ℓ contribution.

    ``rows_pattern``: masked ``(κ, k_pad, n)`` on the full grid (iid
    wiring; non-owned entries computed on junk clipped gathers and masked
    to exact zeros).
    """
    n = slab.shape[1]
    M_loc = slab.shape[0] // plan.Bc
    A_blocks = slab.reshape(M_loc, plan.Bc, n)
    parts = []
    if rows_pattern:
        for ell in range(plan.kappa):
            local, h_of_g, owned = (tables[0, ell], tables[1, ell],
                                    tables[2, ell])
            gathered = A_blocks[local]                    # (M, Bc, n)
            phi = kref._phi_rows_all_blocks(plan, h_of_g)  # (M, Br, Bc)
            contrib = kref.block_einsum("gbc,gcn->gbn", phi, gathered)
            parts.append(jnp.where(owned[:, None, None] == 1, contrib, 0.0))
        return jnp.stack(parts).reshape(plan.kappa, plan.k_pad, n)
    for ell in range(plan.kappa):
        phi = _phi_pairs(plan, tables[0, ell], tables[1, ell])
        contrib = kref.block_einsum("gbc,gcn->gbn", phi,
                                    A_blocks)             # (M_loc, Br, n)
        parts.append(contrib)
    return jnp.stack(parts).reshape(plan.kappa, M_loc * plan.Br, n)


def local_partial_apply(
    plan: BlockPermPlan,
    slab: jnp.ndarray,
    lo,
    *,
    impl: str = "auto",
    tn: Optional[int] = None,
    rows_pattern: bool = False,
) -> jnp.ndarray:
    """Device-local per-ℓ partial sketch of one contiguous block slab.

    Args:
      plan: the frozen GLOBAL plan.
      slab: ``(M_loc·B_c, n)`` rows of the PADDED input owned locally.
      lo: first owned block index (``axis_index * M_loc`` under shard_map;
        may be traced).
      impl: ``"auto" | "pallas" | "xla"`` — ``auto`` picks the fused
        partial Pallas kernel on TPU, the jnp oracle elsewhere (matching
        ``ops`` dispatch so sharded and single-device runs use the same
        backend family).
      tn: Pallas column-tile width (``None`` → the fwd shape-class tile).
      rows_pattern: FLASHBLOCKROW Φ pattern instead of BLOCKPERM.

    Returns:
      ``(κ, k_pad, n)`` fp32 per-ℓ partials, UNSCALED, in the GLOBAL
      output-block layout — exact zeros at every non-owned position (see
      ``sketch_apply_sharded`` for the exact-reduction protocol).  The
      compact kernel/oracle results are scattered into that layout here.
    """
    M_loc = slab.shape[0] // plan.Bc
    n = slab.shape[1]
    tables = partial_tables(plan, lo, M_loc, rows_pattern)
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(
            f"impl must be 'auto', 'pallas' or 'xla', got {impl!r}")
    # The launch decision — impl dispatch, tile resolution, the
    # shrink-then-oracle VMEM fallback — comes from the SAME lowering
    # engine as the single-device ops entry points (shard="row" selects
    # the partial formulation); only the shard_map plumbing lives here.
    lw = lowering.lower(plan, lowering.LaunchSpec(
        op="blockrow" if rows_pattern else "fwd", n=n, impl=impl, tn=tn,
        shard="row", devices=plan.M // M_loc))
    if lw.impl == "xla":
        # match ops' xla path: the oracle sees the stream-rounded input —
        # seeded precision emulation so stochastic-rounding policies stay
        # bit-identical to the kernel's in-flight quantization
        from repro.core import precision as precision_mod
        slab32 = slab.astype(jnp.float32)
        if plan.dtype != "float32":
            slab32 = precision_mod.emulate_stream(
                slab32, plan.precision, seed=plan.seed)
        parts = _partial_oracle(plan, slab32, tables, rows_pattern)
    else:
        # ragged n is handled in-kernel — the slab is never column-padded
        parts = fsk.flashsketch_pallas_partial(
            plan, slab, tables, tn=lw.tn, rows_pattern=rows_pattern,
            interpret=lw.interpret)[:, :, :n]
    if rows_pattern:
        return parts                                      # already global
    # scatter the compact owned-pair rows into the zero global layout —
    # π_ℓ is a permutation, so the per-ℓ indices are collision-free
    compact = parts.reshape(plan.kappa, M_loc, plan.Br, n)
    out = jnp.zeros((plan.kappa, plan.M, plan.Br, n), jnp.float32)
    for ell in range(plan.kappa):
        out = out.at[ell, tables[0, ell]].set(compact[ell])
    return out.reshape(plan.kappa, plan.k_pad, n)


def _fold_scale_truncate(parts: jnp.ndarray, plan: BlockPermPlan,
                         scale: float) -> jnp.ndarray:
    """Σ_ℓ parts[ℓ] in the ORACLE's left-to-right order, then scale and
    truncate to the logical k — the last mile of the exactness argument."""
    Y = parts[0]
    for ell in range(1, plan.kappa):
        Y = Y + parts[ell]
    return (Y * scale)[: plan.k]


def sketch_apply_sharded(
    plan: BlockPermPlan,
    A: jnp.ndarray,
    mesh,
    axis: str,
    impl: str = "auto",
    tn: Optional[int] = None,
    dtype: Optional[str] = None,
    *,
    rows_pattern: bool = False,
):
    """Row-sharded ``Y = S A`` on a device mesh: psum'd partials, S never
    gathered, no device holds all of A.

    Args:
      plan: frozen plan; ``P = mesh.shape[axis]`` must divide ``plan.M``.
      A: ``(d, n)`` float array (a global/committed jax.Array is fine —
        ``shard_map`` re-lays it out row-sharded over ``axis``).
      mesh: a ``jax.sharding.Mesh`` (see ``launch.mesh.make_mesh``).
      axis: mesh axis name carrying the row shards.
      impl / tn / dtype: as in ``ops.sketch_apply`` (``pallas_v1`` has no
        partial formulation — ``impl`` here is ``auto | pallas | xla``).
      rows_pattern: apply the FLASHBLOCKROW sketch instead (the
        ``blockrow_apply`` analogue, including its extra √(d_pad/k_pad)
        scale).

    Returns:
      ``(k, n)`` fp32, REPLICATED across the mesh — ``array_equal`` to the
      single-device ``ops.sketch_apply(plan, A)`` / ``blockrow_apply`` at
      both streaming dtypes (the per-ℓ psum protocol; see module
      docstring).
    """
    if dtype is not None and dtype != plan.dtype:
        plan = plan.with_dtype(dtype)
    num = shard_count(mesh, axis)
    M_loc = check_row_partition(plan, num)
    n = A.shape[1]
    Ap = kref.pad_input(plan, A)                          # (d_pad, n)
    scale = plan.scale
    if rows_pattern:
        import math
        scale = plan.scale * math.sqrt(plan.d_pad / plan.k_pad)
        # pre-warm the lru-cached iid wiring table OUTSIDE the shard_map
        # trace (its concrete-eval guard cannot run under a tracer)
        fsk._blockrow_table(plan)

    def shard_fn(A_loc):
        lo = jax.lax.axis_index(axis) * M_loc
        parts = local_partial_apply(
            plan, A_loc, lo, impl=impl, tn=tn, rows_pattern=rows_pattern)
        parts = jax.lax.psum(parts, axis)   # exact: one contributor/element
        return _fold_scale_truncate(parts, plan, scale)

    return _jit_shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axis, None),), out_specs=P(None, None),
        check_vma=False,
    )(Ap)


def sketch_apply_colsharded(
    plan: BlockPermPlan,
    A: jnp.ndarray,
    mesh,
    axis: str,
    impl: str = "auto",
    tn: Optional[int] = None,
    dtype: Optional[str] = None,
):
    """Column-sharded ``Y = S A``: embarrassingly parallel, NO collective.

    Every device applies the full sketch to its ``n / P`` column slab
    (``P`` must divide ``n``); the output stays column-sharded over
    ``axis``.  Columns are independent in ``S A``, so this is
    ``array_equal`` to the single-device apply.
    """
    num = shard_count(mesh, axis)
    if A.shape[1] % num != 0:
        raise ValueError(
            f"column sharding needs P | n: P={num}, n={A.shape[1]}")

    def shard_fn(A_loc):
        return ops.sketch_apply(plan, A_loc, impl, tn, dtype)

    return _jit_shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(None, axis),), out_specs=P(None, axis),
        check_vma=False,
    )(A)


def sketch_apply_batched_sharded(
    plan: BlockPermPlan,
    A: jnp.ndarray,
    mesh,
    axis: str,
    impl: str = "auto",
    tn: Optional[int] = None,
    dtype: Optional[str] = None,
    *,
    row_index: Optional[jnp.ndarray] = None,
):
    """Batch-sharded ``out[b] = S @ A[b]``: the distributed GraSS layout.

    The leading batch axis of ``A (B, d, n)`` is partitioned over ``axis``
    (``P | B``); each device runs ONE fused batched (optionally
    gather-fused via ``row_index``) launch on its local stack — no
    collective, output batch-sharded.
    """
    num = shard_count(mesh, axis)
    if A.ndim < 3:
        raise ValueError(
            f"batched sharding expects a (B, ..., d, n) stack, got {A.shape}")
    if A.shape[0] % num != 0:
        raise ValueError(
            f"batch sharding needs P | B: P={num}, B={A.shape[0]}")

    if row_index is None:
        def shard_fn(A_loc):
            return ops.sketch_apply_batched(plan, A_loc, impl, tn, dtype)
        in_specs = (P(axis),)
        args = (A,)
    else:
        def shard_fn(A_loc, ri):
            return ops.sketch_apply_batched(plan, A_loc, impl, tn, dtype,
                                            row_index=ri)
        in_specs = (P(axis), P(None))
        args = (A, jnp.asarray(row_index, jnp.int32))

    return _jit_shard_map(
        shard_fn, mesh=mesh,
        in_specs=in_specs, out_specs=P(axis),
        check_vma=False,
    )(*args)
