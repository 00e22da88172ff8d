"""FLASHSKETCH v2 Pallas/TPU kernel suite (paper §5, adapted per DESIGN.md §2).

v2 (default) has two forward formulations, which ``kernels.lowering``
picks between (``Lowering.stationary``): output-stationary (below; every
plan) and input-stationary (permutation-wired plans whose slots fit).

v2 output-stationary — fused-κ single-write formulation:

  * Grid ``(M, n/T_n)`` with the column-tile axis ``j`` **innermost**.
    Program ``(g, j)`` owns output tile ``Y[g·B_r:(g+1)B_r, j·T_n:(j+1)T_n]``
    and receives all κ gathered input blocks ``A[π_ℓ(g)·B_c:…, j·T_n:…]``
    for ℓ = 1..κ via κ block-pipelined views of the same operand.
  * The κ reduction happens **inside** the kernel: the κ tiles
    ``Φ_{g,π_ℓ(g)} ∈ (B_r, B_c)`` are contracted against their pipelined
    input blocks ``(B_c, T_n)`` and summed in registers, producing exactly
    **one** output write per tile — no κ grid revisits, no output
    read-modify-writes, and no (κ·B_c, T_n) VMEM copy of the input.
  * The stacked Φ lives in VMEM scratch and depends only on ``g`` — it is
    rebuilt only at ``j == 0`` and reused across all n/T_n column tiles,
    amortizing the s hash passes (VPU work) by a factor of n/T_n.
  * Mixed precision: the plan's ``Precision`` policy (core.precision)
    decides the streaming cast — bf16 halves, fp8 quarters the HBM
    stream of A; the ``*_sr`` fp8 policies apply seeded stochastic
    rounding at the cast (``_stream``).  In-kernel, fp8 tiles upcast to
    bf16 (exact) for the MXU and Φ is held in the compute dtype (entries
    ±1/0 are exact in every policy), while the MXU accumulates in fp32
    (``preferred_element_type``; fp32 operands contract at
    ``Precision.HIGHEST``).  This shrinks the dominant HBM term in the
    paper's d ≫ k regime.
  * Chip constraints the engine (``kernels.lowering``) guarantees: every
    tile width it picks is a multiple of 128 lanes or the launch's full
    column count, and every launch fits ``VMEM_BUDGET_BYTES``, which is
    also passed to Mosaic as the scoped-VMEM limit.  Φ is built in
    lane-aligned column chunks so its hash temporaries stay small.

v2 input-stationary (``flashsketch_pallas_stationary``) — for a wiring
that is a permutation per level (the ``blockperm`` family):

  * Grid ``(n/T_n, M)`` over column tiles ``j`` (outer) and input blocks
    ``h`` (inner), with ONE pipelined view of A: every ``(B_c, T_n)``
    block is read from HBM once, not κ times.
  * Input block ``h`` feeds exactly one output block per level,
    ``g_ℓ = π_ℓ⁻¹(h)``.  Each program builds the κ tiles ``Φ_{g_ℓ,h}``
    into one ``(κ·B_r, B_c)`` VMEM stack (the same hashes, so the same Φ)
    and contracts it against the block in one dot per chunk: each A chunk
    enters the MXU once, against κ·B_r rows.  Φ is rebuilt once per
    column tile; the engine picks the widest tile that fits, one tile at
    the benchmark cells' n.
  * Level ℓ's ``(B_r, T_n)`` result goes to a write-once fp32 slot
    ``(κ, M, B_r, T_n)`` in VMEM; at the last input block of a column
    tile the slots are folded in ℓ order and scaled — the
    output-stationary kernel's order, so the two are bit-identical
    wherever the MXU's rows are independent of how many rows share a
    dot.  The ``(M, B_r, T_n)`` output block stays resident across ``h``
    and is written back once per column tile.  The working set depends
    on the tile and the plan, not on n; column tiles are independent and
    may be megacore-partitioned, input blocks run in order.

v2-gather (``*_gather``) — the same fused-κ formulation with the input row
gather folded INTO the kernel: the operand stays in HBM (``pl.ANY``)
and an arbitrary per-row index map (e.g. the GraSS sparsify mask) is
scalar-prefetched; each program DMAs its κ·B_c masked rows straight into a
VMEM gather scratch and contracts the cached stacked Φ against it.
``S @ A[mask, :]`` in one launch — no ``A[mask]`` intermediate ever touches
HBM.  The contraction shape and operand values are identical to the
non-gather v2 kernel fed a materialized gather, so the two are bit-exact.

v1 — the original output-revisiting grid reduction, grid ``(n/T_n, M, κ)``
with κ as an arbitrary-order reduction axis and Φ rebuilt for every
``(j, g, ℓ)`` program.  Kept as a reference oracle for equivalence tests
and as the perf baseline for ``benchmarks/kernel_bench.py``.

Both paths build Φ from counter-based hashes bit-identical to ``ref.py`` /
``core.blockperm.dense_block``; the block wiring arrives as a scalar-prefetch
table so BlockSpec index_maps do data-dependent block gathering (the
Pallas-idiomatic realization of the paper's App. D on-the-fly wiring).

Program spans and scopes (``repro.tracing``): ``repro.kernel.tables`` (the
wiring tables), ``repro.kernel.stream`` / ``repro.kernel.row_stack`` (the
operand's cast and relayout) and, around each ``pallas_call`` launch,
``repro.kernel.<kernel>`` — the same stable name the ``pallas_call`` gets
(``flashsketch_fwd``, ``flashsketch_transpose``, ``flashsketch_fwd_gather``,
``flashsketch_partial``, ``flashsketch_blockrow*``, ``*_v1``).
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing
from repro.core import precision as precision_mod
from repro.core.blockperm import (GLOBAL_FAMILY_TAG, VMEM_BUDGET_BYTES,
                                  BlockPermPlan, phi_chunk)
from repro.kernels import ref as kref
from repro.tracing import scope, scoped


# ---------------------------------------------------------------------------
# Static wiring tables:  π_ℓ(g) = A_ℓ·g + B_ℓ (mod M)  for ℓ = 1..κ,
# plus the inverse maps for the transpose kernel.
# ---------------------------------------------------------------------------

def _wiring_tables(plan: BlockPermPlan) -> Tuple[np.ndarray, np.ndarray]:
    A_tab = np.empty(plan.kappa, np.int32)
    B_tab = np.empty(plan.kappa, np.int32)
    a_l, b_l = 1, 0
    for ell in range(plan.kappa):
        # f^{ell+1} = f ∘ f^{ell}:  a_{l+1} = a·a_l, b_{l+1} = a·b_l + b.
        a_l = (plan.a * a_l) % plan.M
        b_l = (plan.a * b_l + plan.b) % plan.M
        A_tab[ell], B_tab[ell] = a_l, b_l
    return A_tab, B_tab


def _inverse_wiring_tables(plan: BlockPermPlan) -> Tuple[np.ndarray, np.ndarray]:
    A_tab, B_tab = _wiring_tables(plan)
    Ai = np.empty_like(A_tab)
    Bi = np.empty_like(B_tab)
    for ell in range(plan.kappa):
        a_inv = pow(int(A_tab[ell]), -1, plan.M) if plan.M > 1 else 0
        Ai[ell] = a_inv % plan.M
        Bi[ell] = (-a_inv * int(B_tab[ell])) % plan.M
    return Ai, Bi


def _fwd_neighbor_table(plan: BlockPermPlan) -> np.ndarray:
    """(κ, M) table: h = π_{ℓ+1}(g)."""
    A_tab, B_tab = _wiring_tables(plan)
    g = np.arange(plan.M, dtype=np.int64)
    return np.stack(
        [(A_tab[l] * g + B_tab[l]) % plan.M for l in range(plan.kappa)]
    ).astype(np.int32)


def _inv_neighbor_table(plan: BlockPermPlan) -> np.ndarray:
    """(κ, M) table: g = π_{ℓ+1}^{-1}(h)."""
    Ai, Bi = _inverse_wiring_tables(plan)
    h = np.arange(plan.M, dtype=np.int64)
    return np.stack(
        [(int(Ai[l]) * h + int(Bi[l])) % plan.M for l in range(plan.kappa)]
    ).astype(np.int32)


@scoped("repro.kernel.tables")
@functools.lru_cache(maxsize=None)
def _blockrow_table(plan: BlockPermPlan) -> np.ndarray:
    """(κ, M) iid wiring, forced to concrete numpy so the wrappers stay
    jittable (the table depends only on the static plan)."""
    with jax.ensure_compile_time_eval():
        return np.asarray(kref.blockrow_wiring(plan))


@functools.lru_cache(maxsize=None)
def _global_table(M: int) -> np.ndarray:
    """(M, M) all-blocks wiring for the GLOBAL families (κ == M):
    ``tab[ℓ, ·] = ℓ`` — every input block feeds every output block.  The
    SAME table serves the forward (program g pipelines input block ℓ) and
    the transpose (program hb pipelines output block g = ℓ): both
    directions of the complete bipartite wiring enumerate all M blocks."""
    return np.tile(np.arange(M, dtype=np.int32)[:, None], (1, M))


@scoped("repro.kernel.tables")
def _fwd_phi_and_table(plan: BlockPermPlan):
    """(phi_fn, prefetch table) for the forward/gather launch."""
    if plan.is_global:
        return _phi_global_tile, _global_table(plan.M)
    return _phi_tile, _fwd_neighbor_table(plan)


@scoped("repro.kernel.tables")
def _transpose_phi_and_table(plan: BlockPermPlan):
    """(phi_fn, prefetch table) for the transpose launch."""
    if plan.is_global:
        return _phi_global_tile, _global_table(plan.M)
    return _phi_tile, _inv_neighbor_table(plan)


# ---------------------------------------------------------------------------
# In-kernel Φ construction (must match ref._phi_all_blocks bit-for-bit).
# ---------------------------------------------------------------------------

def _phi_tile(plan: BlockPermPlan, g, h, c0=0, width=None) -> jnp.ndarray:
    """Columns ``[c0, c0 + width)`` of Φ_{g,h} ∈ (Br, Bc), entries ±1/0,
    built from hashes (the whole tile by default).  g, h traced scalars."""
    width = plan.Bc if width is None else width
    u = c0 + jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (plan.Br, width), 0)
    chunk = plan.chunk
    phi = jnp.zeros((plan.Br, width), jnp.float32)
    for i in range(plan.s):
        hsh = hashing.hash_words(
            np.uint32(plan.seed),
            g.astype(jnp.uint32),
            h.astype(jnp.uint32),
            u.astype(jnp.uint32),
            np.uint32(i),
        )                                              # (1, width)
        rows = i * chunk + hashing.hash_mod(hsh, chunk)
        signs = hashing.hash_to_unit_sign(hsh)
        phi = phi + jnp.where(r_iota == rows, signs, 0.0)
    return phi


def _phi_global_tile(plan: BlockPermPlan, g, h, c0=0, width=None) -> jnp.ndarray:
    """Columns ``[c0, c0 + width)`` of block (g, h) of a GLOBAL family's S
    (countsketch/graph), entries ±1/0.  Nonzero i of global column
    ``h·Bc + u`` lands at GLOBAL row ``i·(k_pad/s) + hash mod (k_pad/s)``;
    rows outside block g never match the local row iota, so the masking
    is free.  Matches ``core.blockperm.dense_global_block`` bit-for-bit."""
    width = plan.Bc if width is None else width
    u = c0 + jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (plan.Br, width), 0)
    chunk = plan.chunk                      # k_pad // s (global partition)
    gcol = h * plan.Bc + u                  # global column indices
    phi = jnp.zeros((plan.Br, width), jnp.float32)
    for i in range(plan.s):
        hsh = hashing.hash_words(
            np.uint32(plan.seed),
            np.uint32(GLOBAL_FAMILY_TAG),
            gcol.astype(jnp.uint32),
            np.uint32(i),
        )                                              # (1, width)
        rows = i * chunk + hashing.hash_mod(hsh, chunk)
        local = rows - g * plan.Br
        signs = hashing.hash_to_unit_sign(hsh)
        phi = phi + jnp.where(r_iota == local, signs, 0.0)
    return phi


def _phi_rows_tile(plan: BlockPermPlan, g, h, c0=0, width=None) -> jnp.ndarray:
    """FLASHBLOCKROW pattern (s ±1 entries per *row*), columns
    ``[c0, c0 + width)``.  Matches ref._phi_rows_all_blocks."""
    width = plan.Bc if width is None else width
    r = jax.lax.broadcasted_iota(jnp.int32, (plan.Br, 1), 0)
    c_iota = c0 + jax.lax.broadcasted_iota(jnp.int32, (plan.Br, width), 1)
    phi = jnp.zeros((plan.Br, width), jnp.float32)
    for t in range(plan.s):
        hsh = hashing.hash_words(
            np.uint32(plan.seed),
            np.uint32(0x5EED),
            g.astype(jnp.uint32),
            h.astype(jnp.uint32),
            r.astype(jnp.uint32),
            np.uint32(t),
        )                                              # (Br, 1)
        cols = hashing.hash_mod(hsh, plan.Bc)
        signs = hashing.hash_to_unit_sign(hsh)
        phi = phi + jnp.where(c_iota == cols, signs, 0.0)
    return phi


def _build_phi(phi_ref, plan: BlockPermPlan, phi_fn, g, h) -> None:
    """Write Φ_{g,h} into the (Br, Bc) ref ``phi_ref`` in lane-aligned
    column chunks of ``phi_chunk(Bc)``: the hash/compare temporaries stay
    (Br, chunk)-sized instead of (Br, Bc), which is what keeps the build
    inside the VMEM budget at the paper's block widths."""
    ch = phi_chunk(plan.Bc)

    def body(c, carry):
        c0 = pl.multiple_of(c * ch, ch)
        phi_ref[:, pl.ds(c0, ch)] = phi_fn(plan, g, h, c0, ch).astype(
            phi_ref.dtype)
        return carry

    jax.lax.fori_loop(0, plan.Bc // ch, body, 0)


def stacked_phi(plan: BlockPermPlan, g, neighbors, *, rows_pattern: bool = False):
    """The fused tile [Φ_{g,h₁} | … | Φ_{g,h_κ}] ∈ (Br, κ·Bc).

    The κ tiles the v2 kernel writes into its (κ, Br, Bc) VMEM scratch at
    ``j == 0``, side by side (exposed for bit-exactness tests against
    ``dense_block`` / ``dense_global_block`` — the family picks the tile
    builder).
    """
    if rows_pattern:
        tile_fn = _phi_rows_tile
    else:
        tile_fn = _phi_global_tile if plan.is_global else _phi_tile
    g = jnp.asarray(g, jnp.int32)
    return jnp.concatenate(
        [tile_fn(plan, g, jnp.asarray(h, jnp.int32)) for h in neighbors], axis=1
    )


# ---------------------------------------------------------------------------
# v2 kernel bodies: fused-κ, single output write, Φ cached across j.
# ---------------------------------------------------------------------------

def _dot(phi, x, *, transpose: bool = False):
    """One MXU contraction ``Φ·x`` (``Φᵀ·x`` with ``transpose``) in Φ's
    compute dtype, accumulated in fp32.  fp32 operands contract at full
    fp32 precision (``Precision.HIGHEST``); bf16 products are exact in the
    fp32 accumulator, so bf16/fp8 policies keep the default."""
    x = x.astype(phi.dtype)
    prec = (jax.lax.Precision.HIGHEST if phi.dtype == jnp.float32
            else None)
    dims = (((0,), (0,)), ((), ())) if transpose else (((1,), (0,)), ((), ()))
    return jax.lax.dot_general(phi, x, dims, precision=prec,
                               preferred_element_type=jnp.float32)


def _phi_dot(phi_ref, x_chunk, Bc: int, out_shape):
    """``Φ · x`` for one (B_r, B_c) Φ ref, contracted in lane-aligned
    chunks of ``phi_chunk(B_c)`` along B_c and summed in chunk order from
    zero.  ``x_chunk(c0, ch)`` loads rows ``[c0, c0 + ch)`` of the operand.
    Chunking bounds the contraction's VMEM temporaries at (ch, tn) however
    wide the block is; every kernel that contracts a Φ tile (fused,
    gather, partial, v1) goes through here, so their per-ℓ sums are
    bit-identical."""
    ch = phi_chunk(Bc)

    def body(c, acc):
        c0 = pl.multiple_of(c * ch, ch)
        return acc + _dot(phi_ref[:, pl.ds(c0, ch)], x_chunk(c0, ch))

    return jax.lax.fori_loop(0, Bc // ch, body,
                             jnp.zeros(out_shape, jnp.float32))


def _fused_sum(phi_ref, x_chunk, plan: BlockPermPlan, out_shape):
    """``Σ_ℓ Φ_ℓ · x_ℓ`` over the κ stacked Φ tiles, summed in ℓ order —
    the reference oracle's order, and the fold order of the row-sharded
    partials.  ``x_chunk(ℓ, c0, ch)`` loads rows of the ℓ-th operand.  One
    (κ·B_c)-deep dot over a concatenated operand would need a (κ·B_c, tn)
    VMEM copy of the input; κ chunked dots need none."""
    acc = None
    for ell in range(plan.kappa):
        y = _phi_dot(phi_ref.at[ell],
                     lambda c0, ch, _ell=ell: x_chunk(_ell, c0, ch),
                     plan.Bc, out_shape)
        acc = y if acc is None else acc + y
    return acc


def _fused_fwd_kernel(tab_ref, *refs, plan: BlockPermPlan, scale, phi_fn):
    a_refs = refs[: plan.kappa]
    o_ref = refs[plan.kappa]
    phi_ref = refs[plan.kappa + 1]          # (κ, Br, Bc) VMEM scratch
    g = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _build():
        for ell in range(plan.kappa):
            _build_phi(phi_ref.at[ell], plan, phi_fn, g, tab_ref[ell, g])

    # streamed dtype → MXU compute dtype inside ``_dot`` (no-op for
    # fp32/bf16; fp8 upcasts to bf16 — exact — inside VMEM)
    o_ref[...] = _fused_sum(
        phi_ref, lambda ell, c0, ch: a_refs[ell][pl.ds(c0, ch), :],
        plan, o_ref.shape) * scale


def _stationary_fwd_kernel(tab_ref, a_ref, o_ref, phi_ref, slot_ref, *,
                           plan: BlockPermPlan, scale, phi_fn):
    """Input-stationary forward body: program ``(j, h)`` contracts input
    block ``A[h·B_c:…, j·tn:…]``, fetched once, against the κ tiles
    ``Φ_{g_ℓ,h}`` (``g_ℓ = π_ℓ⁻¹(h)``, ``tab_ref[ℓ, h]``) stacked as one
    ``(κ·B_r, B_c)`` scratch, in one dot per chunk.  Level ℓ's rows land
    in the write-once slot ``slot_ref[ℓ, g_ℓ]`` (π_ℓ is a permutation, so
    every slot is written exactly once per column tile); at the last
    input block the slots are folded in ℓ order and scaled —
    ``_fused_sum``'s order, so the result is the output-stationary
    kernel's bit for bit wherever a dot's rows do not depend on how many
    rows it has (on a v5e they do not; the CPU interpreter's 512-deep
    chunks do)."""
    h = pl.program_id(1)
    Br, tn = plan.Br, a_ref.shape[1]
    for ell in range(plan.kappa):
        _build_phi(phi_ref.at[pl.ds(ell * Br, Br)], plan, phi_fn,
                   tab_ref[ell, h], h)
    y = _phi_dot(phi_ref, lambda c0, ch: a_ref[pl.ds(c0, ch), :],
                 plan.Bc, (plan.kappa * Br, tn))
    for ell in range(plan.kappa):
        slot_ref[ell, tab_ref[ell, h]] = y[ell * Br:(ell + 1) * Br]

    @pl.when(h == pl.num_programs(1) - 1)
    def _fold():
        def body(g, carry):
            acc = slot_ref[0, g]
            for ell in range(1, plan.kappa):
                acc = acc + slot_ref[ell, g]
            o_ref[g] = acc * scale
            return carry

        jax.lax.fori_loop(0, plan.M, body, 0)


def _fused_transpose_kernel(tab_ref, *refs, plan: BlockPermPlan, scale,
                            phi_fn):
    y_refs = refs[: plan.kappa]
    o_ref = refs[plan.kappa]
    phi_ref = refs[plan.kappa + 1]          # (κ, Br, Bc) VMEM scratch
    hb = pl.program_id(0)                   # input-block index (output of Sᵀ)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _build():
        for ell in range(plan.kappa):
            # g = π_{ℓ+1}^{-1}(hb)
            _build_phi(phi_ref.at[ell], plan, phi_fn, tab_ref[ell, hb], hb)

    # X_hb = Σ_ℓ Φ_ℓᵀ · Y_ℓ, written in (ch, tn) row chunks of the output
    # (the contraction depth is B_r; chunking the B_c output rows keeps
    # the transposed-Φ temporaries small)
    ch = phi_chunk(plan.Bc)

    def body(c, carry):
        c0 = pl.multiple_of(c * ch, ch)
        acc = None
        for ell in range(plan.kappa):
            x = _dot(phi_ref[ell, :, pl.ds(c0, ch)], y_refs[ell][...],
                     transpose=True)
            acc = x if acc is None else acc + x
        o_ref[pl.ds(c0, ch), :] = acc * scale
        return carry

    jax.lax.fori_loop(0, plan.Bc // ch, body, 0)


def _fused_gather_kernel(tab_ref, rmap_ref, a_any, o_ref, gat_ref, phi_ref,
                         sem, *, plan: BlockPermPlan, scale, phi_fn, tn: int):
    """Gather-fused fwd/blockrow body: Y[g, j] = Φ* · A[rmap[blocks], j·tn:].

    The operand ``a_any`` is the FULL source matrix left in HBM
    (``memory_space=ANY``) as the ``(d_src, 1, n_pad)`` fp32 row stack of
    ``_row_stack``: the chip lays a 2-D array out in (8, 128) tiles, from
    which its DMA engine cannot cut a single row, while a unit
    second-minor dimension is laid out one row per (1, 128) tile; and the
    row stack's columns are padded to the tile grid, so every row DMA
    moves a whole lane-aligned tile.  ``rmap_ref`` is the scalar-prefetched
    per-row index map of the *masked* input (length d_pad, padding entries
    pointing at any valid row).  Each program DMAs its κ·B_c gathered rows
    into ``gat_ref`` (VMEM, ``(κ·B_c, 1, tn)``) row by row — the TPU
    analogue of the coalesced index-streamed gather — then runs the v2
    single-write contraction against the Φ scratch cached across column
    tiles, term for term as the non-gather kernel does (bit-exact to it on
    a materialized gather).
    """
    g = pl.program_id(0)
    j = pl.program_id(1)
    # a lone column tile is taken whole: no (dynamic) window offset for
    # the compiler to prove aligned
    cols = (slice(None) if a_any.shape[2] == tn
            else pl.ds(pl.multiple_of(j * tn, tn), tn))

    @pl.when(j == 0)
    def _build():
        for ell in range(plan.kappa):
            _build_phi(phi_ref.at[ell], plan, phi_fn, g, tab_ref[ell, g])

    def _row_dma(ell, h, r):
        return pltpu.make_async_copy(
            a_any.at[rmap_ref[h * plan.Bc + r], :, cols],
            gat_ref.at[ell * plan.Bc + r], sem)

    # Issue every row copy before waiting on any: the destinations are
    # disjoint scratch rows and the DMA semaphore counts completions, so
    # up to κ·B_c transfers are in flight at once instead of paying κ·B_c
    # serialized HBM round-trips per program.
    for ell in range(plan.kappa):
        h = tab_ref[ell, g]
        jax.lax.fori_loop(
            0, plan.Bc,
            lambda r, _, _ell=ell, _h=h: (_row_dma(_ell, _h, r).start(), 0)[1],
            0)
    for ell in range(plan.kappa):
        h = tab_ref[ell, g]
        jax.lax.fori_loop(
            0, plan.Bc,
            lambda r, _, _ell=ell, _h=h: (_row_dma(_ell, _h, r).wait(), 0)[1],
            0)

    def _operand(ell, c0, ch):
        blk = gat_ref[pl.ds(ell * plan.Bc + c0, ch), :, :].reshape(ch, tn)
        if plan.d == plan.d_pad:
            return blk
        # Padded masked rows (global index ≥ plan.d) gathered a placeholder
        # source row; zero them here so padding contributes exact zeros —
        # bit-identical to zero-padding a materialized gather, without ever
        # copying A to append a zero row.
        rows = tab_ref[ell, g] * plan.Bc + c0 + jax.lax.broadcasted_iota(
            jnp.int32, (ch, 1), 0)
        return jnp.where(rows < plan.d, blk, jnp.zeros_like(blk))

    o_ref[...] = _fused_sum(phi_ref, _operand, plan, o_ref.shape) * scale


def _partial_fwd_kernel(tab_ref, a_ref, o_ref, phi_ref, *,
                        plan: BlockPermPlan, phi_fn):
    """Per-ℓ COMPACT partial sketch over an owned contiguous block slab.

    The multi-device building block (``repro.distributed``): a device that
    owns input blocks ``[lo, lo + M_loc)`` of a row-sharded A computes, for
    every owned pair, the UNSCALED contribution ``Φ_{g,h} · A_h``.  The
    wiring π_ℓ is a permutation, so each owned input block ``h`` feeds
    exactly ONE output block ``g = π_ℓ⁻¹(h)`` per level — the grid is
    ``(M_loc, κ, n/tn)`` over owned pairs ONLY (per-chip MXU, HBM-input
    and Φ-build work all shard 1/P; this is what
    ``roofline.sketch_model.dist_sketch_cost`` charges), and the caller
    scatters the compact ``(κ, M_loc·B_r, n)`` result into the zero-padded
    global ``(κ, k_pad, n)`` layout.  The per-ℓ slices stay separate so
    the cross-device ``psum`` adds exactly one nonzero contributor per
    element (block ownership is a partition) — an EXACT fp32 reduction —
    and the κ-fold happens after, in the reference oracle's summation
    order.

    ``j`` innermost; the (B_r, B_c) Φ tile is cached in VMEM scratch
    across the column tiles (rebuilt at ``j == 0``).  Each output tile is
    written exactly once (v2's single-write property).

    ``tab_ref`` is the (2, κ, M_loc) prefetch table
    ``[global output block g, global input block h]`` — both GLOBAL block
    ids feed the Φ hashes, which is what makes the partials globally
    consistent; the input gather is just the local slab position ``m``.
    """
    m = pl.program_id(0)
    ell = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _build():
        _build_phi(phi_ref, plan, phi_fn, tab_ref[0, ell, m],
                   tab_ref[1, ell, m])

    o_ref[0] = _phi_dot(phi_ref, lambda c0, ch: a_ref[pl.ds(c0, ch), :],
                        plan.Bc, o_ref.shape[1:])


def _partial_masked_kernel(tab_ref, a_ref, o_ref, phi_ref, *,
                           plan: BlockPermPlan, phi_fn):
    """Ownership-MASKED per-ℓ partial over a block slab (full (M, κ, n/tn)
    grid, Φ zeroed for non-owned pairs).

    Kept for the FLASHBLOCKROW wiring, which is iid (NOT a permutation):
    an owned input block may feed zero or several output blocks per level,
    so there is no compact owned-pair grid.  Appendix-variant / eval-only
    — the per-chip work does not shard (every device walks the full grid).

    ``tab_ref`` is the (3, κ, M) prefetch table
    ``[local gather index, global h (hash input), owned flag]``.
    """
    g = pl.program_id(0)
    ell = pl.program_id(1)
    j = pl.program_id(2)
    owned = tab_ref[2, ell, g]

    @pl.when((j == 0) & (owned == 1))
    def _build():
        _build_phi(phi_ref, plan, phi_fn, g, tab_ref[1, ell, g])

    @pl.when((j == 0) & (owned == 0))
    def _zero_phi():
        # non-owned pairs still skip the s hash passes
        phi_ref[...] = jnp.zeros_like(phi_ref)

    o_ref[0] = _phi_dot(phi_ref, lambda c0, ch: a_ref[pl.ds(c0, ch), :],
                        plan.Bc, o_ref.shape[1:])


# ---------------------------------------------------------------------------
# pallas_call wrappers (raw; user-facing API with padding/custom_vjp in ops.py)
# ---------------------------------------------------------------------------

def _compiler_params(semantics):
    """Mosaic parameters of every launch here: the grid's dimension
    semantics, and the scoped-VMEM limit the engine budgets its tiles
    against (``blockperm.VMEM_BUDGET_BYTES`` — one number for both)."""
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_BUDGET_BYTES)


def _k_view(plan: BlockPermPlan, x: jnp.ndarray) -> jnp.ndarray:
    """The ``(M, B_r, n)`` view of a k-side array (S's output, Sᵀ's
    input).  The chip takes a block's second-minor dimension only as a
    multiple of 8 or the array's full extent, and B_r can be as small as
    s = 2; in this view every ``(None, B_r, tn)`` block spans the full
    second-minor extent.  A layout-preserving reshape whenever B_r fills
    whole sublane tiles."""
    return x.reshape(plan.M, plan.Br, x.shape[-1])


def _d_spec(plan: BlockPermPlan, tn: int, index):
    """(B_c, tn) block of a d-side array (S's input, Sᵀ's output);
    ``index(*program_ids)`` gives its (row block, column tile)."""
    return pl.BlockSpec((plan.Bc, tn), index)


def _k_spec(plan: BlockPermPlan, tn: int, index):
    """(B_r, tn) block of a k-side array's ``_k_view``; ``index`` as in
    ``_d_spec``."""
    def k_index(*ids):
        r, j = index(*ids)
        return r, 0, j
    return pl.BlockSpec((None, plan.Br, tn), k_index)


def _io_specs(transpose: bool):
    """(input, output) block-spec makers of a forward or transpose launch."""
    return (_k_spec, _d_spec) if transpose else (_d_spec, _k_spec)


def _launch(name: str, kernel, args, **call_kwargs):
    """One ``pallas_call`` launch named ``name``, under the program span
    and scope ``repro.kernel.<name>``.  This runs while JAX traces the
    launch (in a jitted caller, or the first eager call of a record, which
    builds its memoized executor), so the span covers the kernel body's
    trace.  A warm eager call never reaches it: ``lowering.execute`` opens
    the same span around the compiled executor, so there it covers only
    the enqueue."""
    with scope(f"repro.kernel.{name}"):
        return pl.pallas_call(kernel, name=name, **call_kwargs)(*args)


def _run_v1(plan, kernel, tab, operand, n, tn, interpret, transpose=False, *,
            name: str):
    """v1 launcher.  ``n`` may be ragged (``n % tn != 0``): the grid covers
    ⌈n/tn⌉ column tiles and the edge tile is handled by the Pallas
    machinery itself (masked loads/stores on TPU, internal pad+slice in
    interpret mode).  The contraction is column-local, so edge-tile
    garbage never leaks into valid columns — the operand is NEVER padded
    at trace level (no HBM copy of A just to round n up).  Returns the
    (k_pad, n) forward or, with ``transpose``, the (d_pad, n) result."""
    grid = (-(-n // tn), plan.M, plan.kappa)
    in_spec, out_spec = _io_specs(transpose)
    if transpose:
        operand = _k_view(plan, operand)
        out_shape = (plan.d_pad, n)
    else:
        out_shape = (plan.M, plan.Br, n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[in_spec(plan, tn,
                          lambda j, g, l, tab_ref: (tab_ref[l, g], j))],
        out_specs=out_spec(plan, tn, lambda j, g, l, tab_ref: (g, j)),
        scratch_shapes=[pltpu.VMEM((plan.Br, plan.Bc), jnp.float32)],
    )
    out = _launch(
        name, kernel, (jnp.asarray(tab), operand),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        interpret=interpret,
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
    )
    return out if transpose else out.reshape(plan.k_pad, n)


def _run_fused(plan, kernel, tab, operand, n, tn, interpret, transpose=False,
               *, name: str):
    """v2 launcher: grid (M, n/tn), κ pipelined views of one operand, Φ scratch.

    The same operand is passed κ times — each view has its own BlockSpec whose
    index_map picks input block ``tab[ℓ, ·]``, so the pipeline prefetches all
    κ gathered blocks for program (g, j) without any HBM-side gather copy.
    The Φ scratch holds the κ (B_r, B_c) tiles of the program's block row
    (forward) or block column (transpose) as a ``(κ, B_r, B_c)`` stack.

    ``n`` may be ragged (``n % tn != 0``): the grid covers ⌈n/tn⌉ column
    tiles and the edge tile rides the Pallas machinery (masked loads/stores
    on TPU, internal pad+slice in interpret mode).  Output columns of the
    edge tile beyond ``n`` are garbage but are dropped by the machinery;
    the contraction is column-local so valid columns are untouched.  The
    operand is NEVER column-padded at trace level.  Returns the (k_pad, n)
    forward or, with ``transpose``, the (d_pad, n) result.
    """
    grid = (plan.M, -(-n // tn))
    # Φ scratch lives in the MXU compute dtype: identical to the streamed
    # dtype for fp32/bf16, bf16 for the fp8 policies (whose operand tiles
    # are upcast to it in-kernel; ±1/0 entries are exact either way).
    cdt = plan.precision.compute_dtype

    def _gather_map(ell):
        return lambda g, j, tab_ref: (tab_ref[ell, g], j)

    in_spec, out_spec = _io_specs(transpose)
    if transpose:
        operand = _k_view(plan, operand)
        out_shape = (plan.d_pad, n)
    else:
        out_shape = (plan.M, plan.Br, n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[in_spec(plan, tn, _gather_map(ell))
                  for ell in range(plan.kappa)],
        out_specs=out_spec(plan, tn, lambda g, j, tab_ref: (g, j)),
        scratch_shapes=[pltpu.VMEM((plan.kappa, plan.Br, plan.Bc), cdt)],
    )
    out = _launch(
        name, kernel, (jnp.asarray(tab), *([operand] * plan.kappa)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        interpret=interpret,
        # j must run sequentially per g (Φ scratch is built at j == 0);
        # g tiles are independent and may be megacore-partitioned.
        compiler_params=_compiler_params(("parallel", "arbitrary")),
    )
    return out if transpose else out.reshape(plan.k_pad, n)


def _run_stationary(plan, kernel, tab, operand, n, tn, interpret, *,
                    name: str):
    """Input-stationary launcher: grid (n/tn, M) over column tiles ``j``
    (outer) and input blocks ``h`` (inner), ONE pipelined view of the
    operand, so each (B_c, tn) block is read from HBM once.  The stacked
    Φ scratch ``(κ·B_r, B_c)`` is built by every program; the ``(κ, M,
    B_r, tn)`` fp32 slots and the ``(M, B_r, tn)`` output block are
    resident across ``h``.  ``n`` may be ragged: the edge tile rides the
    Pallas machinery as in ``_run_fused``.  Returns the (k_pad, n)
    forward."""
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(-(-n // tn), plan.M),
        in_specs=[_d_spec(plan, tn, lambda j, h, tab_ref: (h, j))],
        out_specs=pl.BlockSpec((plan.M, plan.Br, tn),
                               lambda j, h, tab_ref: (0, 0, j)),
        scratch_shapes=[
            pltpu.VMEM((plan.kappa * plan.Br, plan.Bc),
                       plan.precision.compute_dtype),       # stacked Φ
            pltpu.VMEM((plan.kappa, plan.M, plan.Br, tn),
                       jnp.float32),                        # slots
        ],
    )
    out = _launch(
        name, kernel, (jnp.asarray(tab), operand),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((plan.M, plan.Br, n), jnp.float32),
        interpret=interpret,
        # h must run in order per j (the slots are folded at the last h);
        # column tiles are independent and may be megacore-partitioned.
        compiler_params=_compiler_params(("parallel", "arbitrary")),
    )
    return out.reshape(plan.k_pad, n)


def _run_fused_gather(plan, kernel, tab, row_map, rows, tn, interpret, *,
                      name: str):
    """Gather launcher: grid (M, n_pad/tn) over the ``_row_stack`` operand
    ``rows`` (``(d_src, 1, n_pad)``, left in HBM — ANY memory space);
    masked rows arrive via in-kernel DMA driven by the scalar-prefetched
    ``row_map``; Φ scratch is cached across j as in v2.  Returns the
    ``(k_pad, n_pad)`` output; the caller slices off the padded
    columns (exact zeros)."""
    n_pad = rows.shape[2]
    grid = (plan.M, n_pad // tn)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=_k_spec(plan, tn,
                          lambda g, j, tab_ref, rmap_ref: (g, j)),
        scratch_shapes=[
            # gather scratch holds the raw DMA'd fp32 rows (``_row_stack``);
            # Φ scratch holds the MXU compute dtype (gathered chunks are
            # cast to it at the contraction)
            pltpu.VMEM((plan.kappa * plan.Bc, 1, tn), rows.dtype),
            pltpu.VMEM((plan.kappa, plan.Br, plan.Bc),
                       plan.precision.compute_dtype),      # Φ*
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    out = _launch(
        name, kernel,
        (jnp.asarray(tab), jnp.asarray(row_map, jnp.int32), rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((plan.M, plan.Br, n_pad), jnp.float32),
        interpret=interpret,
        compiler_params=_compiler_params(("parallel", "arbitrary")),
    )
    return out.reshape(plan.k_pad, n_pad)


@scoped("repro.kernel.stream")
def _stream(plan: BlockPermPlan, operand: jnp.ndarray) -> jnp.ndarray:
    """Quantize the operand into the plan's streaming dtype.

    THE streaming cast (``core.precision.quantize_stream``): nearest
    rounding for fp32/bf16/fp8 policies, seeded value-keyed stochastic
    rounding for the ``*_sr`` fp8 policies (keyed on ``plan.seed`` so a
    draw's quantization is as reproducible as its wiring).  The kernels
    stream the result from HBM at ``plan.stream_itemsize`` bytes/elem and
    upcast to ``plan.precision.compute_dtype`` in VMEM for the MXU."""
    return precision_mod.quantize_stream(
        operand, plan.precision, seed=plan.seed)


@scoped("repro.kernel.row_stack")
def _row_stack(plan: BlockPermPlan, operand: jnp.ndarray,
               tn: int) -> jnp.ndarray:
    """The gather kernels' source: the stream-quantized operand as a
    ``(d, 1, n_pad)`` fp32 stack of rows, columns zero-padded to a whole
    number of ``tn`` tiles — the HBM layout they DMA single rows from (see
    ``_fused_gather_kernel``).  The chip packs 2 (bf16) or 4 (fp8) rows
    into one tile row, so sub-32-bit streams are gathered as their
    exactly-representable fp32 values: the policy's rounding holds bit for
    bit, its HBM saving does not.  One XLA fusion writes the stack (with
    the batched fold when there is one): the fused gather makes one pass
    over A and never materializes the gathered ``A[row_map]``."""
    q = _stream(plan, operand).astype(jnp.float32)
    d, n = q.shape
    q = q.reshape(d, 1, n)
    n_pad = -(-n // tn) * tn
    return q if n_pad == n else jnp.pad(q, ((0, 0), (0, 0), (0, n_pad - n)))


def flashsketch_pallas(
    plan: BlockPermPlan,
    A: jnp.ndarray,
    *,
    tn: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Y = S A via the fused v2 kernel. A must be (d_pad, n); n may be
    ragged (the ⌈n/tn⌉ edge tile is handled by the Pallas machinery)."""
    d_pad, n = A.shape
    assert d_pad == plan.d_pad, (d_pad, plan.d_pad)
    phi_fn, tab = _fwd_phi_and_table(plan)
    kernel = functools.partial(
        _fused_fwd_kernel, plan=plan, scale=plan.scale, phi_fn=phi_fn
    )
    return _run_fused(plan, kernel, tab, _stream(plan, A), n=n, tn=tn,
                      interpret=interpret, name="flashsketch_fwd")


def flashsketch_pallas_stationary(
    plan: BlockPermPlan,
    A: jnp.ndarray,
    *,
    tn: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Y = S A via the input-stationary kernel: each block of A is read
    once, against its κ Φ tiles stacked.  Permutation wiring only (a
    ``blockperm``-family plan); bit-identical to ``flashsketch_pallas``.
    A must be (d_pad, n); n may be ragged."""
    d_pad, n = A.shape
    assert d_pad == plan.d_pad, (d_pad, plan.d_pad)
    assert not plan.is_global, plan.family
    # the transpose's table: g = π_ℓ⁻¹(h), the one output block per level
    phi_fn, tab = _transpose_phi_and_table(plan)
    kernel = functools.partial(_stationary_fwd_kernel, plan=plan,
                               scale=plan.scale, phi_fn=phi_fn)
    return _run_stationary(plan, kernel, tab, _stream(plan, A), n=n, tn=tn,
                           interpret=interpret, name="flashsketch_fwd")


def flashsketch_transpose_pallas(
    plan: BlockPermPlan,
    Y: jnp.ndarray,
    *,
    tn: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """X = Sᵀ Y via the fused v2 kernel. Y must be (k_pad, n); ragged n ok."""
    k_pad, n = Y.shape
    assert k_pad == plan.k_pad, (k_pad, plan.k_pad)
    phi_fn, tab = _transpose_phi_and_table(plan)
    kernel = functools.partial(_fused_transpose_kernel, plan=plan,
                               scale=plan.scale, phi_fn=phi_fn)
    return _run_fused(plan, kernel, tab, _stream(plan, Y), n=n, tn=tn,
                      interpret=interpret, transpose=True,
                      name="flashsketch_transpose")


def flashsketch_pallas_gather(
    plan: BlockPermPlan,
    A: jnp.ndarray,
    row_map: jnp.ndarray,
    *,
    tn: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Y = S · A[row_map, :] in ONE launch — the gather-fused v2 kernel.

    Args:
      plan: frozen plan for the *masked* input dim (``plan.d`` = rows kept).
      A: ``(d_src, n)`` source matrix, any ``n``.  Rewritten once as the
        kernel's row stack (``_row_stack``); the kernel DMAs only the
        masked rows of it.
      row_map: ``(d_pad,)`` int32 — source row of A feeding each padded
        masked row.  Entries beyond ``plan.d`` may point at any valid row
        (``lowering.row_map_for`` uses 0); the kernel zeroes those
        contributions before the contraction.
      tn: column-tile width, a multiple of 128 (the row DMAs move whole
        lane tiles).

    Returns:
      ``(k_pad, ⌈n/tn⌉·tn)`` fp32 — the caller slices off the padded
      output columns (they are exact zeros).
    """
    assert row_map.shape == (plan.d_pad,), (row_map.shape, plan.d_pad)
    phi_fn, tab = _fwd_phi_and_table(plan)
    kernel = functools.partial(
        _fused_gather_kernel, plan=plan, scale=plan.scale, phi_fn=phi_fn,
        tn=tn)
    return _run_fused_gather(plan, kernel, tab, row_map,
                             _row_stack(plan, A, tn), tn=tn,
                             interpret=interpret,
                             name="flashsketch_fwd_gather")


def blockrow_pallas_gather(
    plan: BlockPermPlan,
    A: jnp.ndarray,
    row_map: jnp.ndarray,
    *,
    tn: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """FLASHBLOCKROW over gathered rows: Y = S_row · A[row_map, :], fused.

    Same operand contract as ``flashsketch_pallas_gather``.
    """
    assert row_map.shape == (plan.d_pad,), (row_map.shape, plan.d_pad)
    scale = plan.scale * math.sqrt(plan.d_pad / plan.k_pad)
    kernel = functools.partial(
        _fused_gather_kernel, plan=plan, scale=scale, phi_fn=_phi_rows_tile,
        tn=tn)
    return _run_fused_gather(plan, kernel, _blockrow_table(plan), row_map,
                             _row_stack(plan, A, tn), tn=tn,
                             interpret=interpret,
                             name="flashsketch_blockrow_gather")


def blockrow_pallas(
    plan: BlockPermPlan,
    A: jnp.ndarray,
    *,
    tn: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """FLASHBLOCKROW forward via the fused v2 kernel. A: (d_pad, n); ragged n ok."""
    d_pad, n = A.shape
    assert d_pad == plan.d_pad
    h_np = _blockrow_table(plan)                            # (κ, M) static
    scale = plan.scale * math.sqrt(plan.d_pad / plan.k_pad)
    kernel = functools.partial(
        _fused_fwd_kernel, plan=plan, scale=scale, phi_fn=_phi_rows_tile
    )
    return _run_fused(plan, kernel, h_np, _stream(plan, A), n=n, tn=tn,
                      interpret=interpret, name="flashsketch_blockrow")


def flashsketch_pallas_partial(
    plan: BlockPermPlan,
    A_local: jnp.ndarray,
    tables: jnp.ndarray,
    *,
    tn: int = 128,
    rows_pattern: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-ℓ UNSCALED partial sketch of a contiguous block slab.

    Args:
      plan: the frozen GLOBAL plan (full M-block grid).
      A_local: ``(M_loc·B_c, n)`` slab of the padded input owned by this
        device (a contiguous range of ``M_loc`` of the M input blocks);
        ``n`` may be ragged (``n % tn != 0`` — the edge column tile rides
        the Pallas machinery, the slab is never column-padded).  Streamed
        in ``plan.stream_dtype``.
      tables: from ``repro.distributed.sharded_apply.partial_tables`` —
        ``(2, κ, M_loc)`` int32 ``[global g, global h]`` for the default
        COMPACT owned-pair kernel, or ``(3, κ, M)`` ``[local gather index,
        global h, owned]`` for the masked FLASHBLOCKROW form
        (``rows_pattern=True``).  May be traced arrays — ownership depends
        on ``lax.axis_index`` under ``shard_map``.
      tn: column-tile width.
      rows_pattern: use the FLASHBLOCKROW per-row Φ pattern (iid wiring ⇒
        masked full-grid kernel instead of the compact one).

    Returns:
      fp32 per-ℓ partials, UNSCALED: compact ``(κ, M_loc·B_r, n)`` for the
      default path (caller scatters rows ``m`` to output blocks
      ``tables[0, ℓ, m]``), or global ``(κ, k_pad, n)`` with exact zeros
      at non-owned positions for ``rows_pattern``.  Either way, ``psum``
      over the shard axis then an ℓ-ordered fold recovers the full
      ``S·A / scale`` bit-exactly (one nonzero contributor per element).
    """
    rows_loc, n = A_local.shape
    assert rows_loc % plan.Bc == 0, (rows_loc, plan.Bc)
    M_loc = rows_loc // plan.Bc
    assert plan.M % M_loc == 0, (plan.M, M_loc)
    n_tiles = -(-n // tn)
    operand = _stream(plan, A_local)
    if rows_pattern:
        assert tables.shape == (3, plan.kappa, plan.M), tables.shape
        kernel = functools.partial(
            _partial_masked_kernel, plan=plan, phi_fn=_phi_rows_tile)
        grid = (plan.M, plan.kappa, n_tiles)
        in_spec = pl.BlockSpec(
            (plan.Bc, tn), lambda g, l, j, tab_ref: (tab_ref[0, l, g], j))
        out_blocks = plan.M
        out_map = lambda g, l, j, tab_ref: (l, g, 0, j)    # noqa: E731
    else:
        assert tables.shape == (2, plan.kappa, M_loc), tables.shape
        kernel = functools.partial(
            _partial_fwd_kernel, plan=plan, phi_fn=_phi_tile)
        grid = (M_loc, plan.kappa, n_tiles)
        in_spec = pl.BlockSpec(
            (plan.Bc, tn), lambda m, l, j, tab_ref: (m, j))
        out_blocks = M_loc
        out_map = lambda m, l, j, tab_ref: (l, m, 0, j)    # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[in_spec],
        # each output block its own leading entry (``_k_view``)
        out_specs=pl.BlockSpec((1, None, plan.Br, tn), out_map),
        scratch_shapes=[pltpu.VMEM((plan.Br, plan.Bc),
                                   plan.precision.compute_dtype)],
    )
    out = _launch(
        "flashsketch_partial_blockrow" if rows_pattern
        else "flashsketch_partial",
        kernel, (jnp.asarray(tables, jnp.int32), operand),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (plan.kappa, out_blocks, plan.Br, n), jnp.float32),
        interpret=interpret,
        # j must run sequentially per (block, ℓ) — the Φ scratch is built
        # at j == 0; block tiles are independent.
        compiler_params=_compiler_params(
            ("parallel", "arbitrary", "arbitrary")),
    )
    return out.reshape(plan.kappa, out_blocks * plan.Br, n)


# ---------------------------------------------------------------------------
# v1 kernels — output-revisiting grid reduction.  Reference oracle for the
# equivalence tests, the baseline for kernel_bench, and the engine's rung
# for plans whose stacked Φ cannot fit VMEM; always fp32.
# ---------------------------------------------------------------------------

def _fwd_kernel_v1(tab_ref, a_ref, o_ref, phi_ref, *, plan: BlockPermPlan,
                   scale, phi_fn):
    """Forward (and FLASHBLOCKROW, via ``phi_fn``) v1 body: one (g, ℓ) pair
    per program, Φ rebuilt into its (B_r, B_c) scratch every program."""
    g = pl.program_id(1)
    ell = pl.program_id(2)
    _build_phi(phi_ref, plan, phi_fn, g, tab_ref[ell, g])
    contrib = _phi_dot(phi_ref, lambda c0, ch: a_ref[pl.ds(c0, ch), :],
                       plan.Bc, o_ref.shape) * scale

    @pl.when(ell == 0)
    def _init():
        o_ref[...] = contrib

    @pl.when(ell > 0)
    def _acc():
        o_ref[...] += contrib


def _transpose_kernel_v1(tab_ref, y_ref, o_ref, phi_ref, *,
                         plan: BlockPermPlan, scale, phi_fn):
    hb = pl.program_id(1)               # input block index (output of Sᵀ)
    ell = pl.program_id(2)
    _build_phi(phi_ref, plan, phi_fn, tab_ref[ell, hb], hb)  # g = f^{-ℓ}(hb)
    ch = phi_chunk(plan.Bc)

    def body(c, carry):
        c0 = pl.multiple_of(c * ch, ch)
        contrib = _dot(phi_ref[:, pl.ds(c0, ch)], y_ref[...],
                       transpose=True) * scale

        @pl.when(ell == 0)
        def _init():
            o_ref[pl.ds(c0, ch), :] = contrib

        @pl.when(ell > 0)
        def _acc():
            o_ref[pl.ds(c0, ch), :] += contrib

        return carry

    jax.lax.fori_loop(0, plan.Bc // ch, body, 0)


def flashsketch_pallas_v1(
    plan: BlockPermPlan,
    A: jnp.ndarray,
    *,
    tn: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Y = S A via the v1 grid-reduction kernel (fp32 only; ragged n ok)."""
    d_pad, n = A.shape
    assert d_pad == plan.d_pad, (d_pad, plan.d_pad)
    phi_fn, tab = _fwd_phi_and_table(plan)
    kernel = functools.partial(_fwd_kernel_v1, plan=plan, scale=plan.scale,
                               phi_fn=phi_fn)
    return _run_v1(plan, kernel, tab, A, n=n, tn=tn, interpret=interpret,
                   name="flashsketch_fwd_v1")


def flashsketch_transpose_pallas_v1(
    plan: BlockPermPlan,
    Y: jnp.ndarray,
    *,
    tn: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """X = Sᵀ Y via the v1 grid-reduction kernel (fp32 only; ragged n ok)."""
    k_pad, n = Y.shape
    assert k_pad == plan.k_pad, (k_pad, plan.k_pad)
    phi_fn, tab = _transpose_phi_and_table(plan)
    kernel = functools.partial(_transpose_kernel_v1, plan=plan,
                               scale=plan.scale, phi_fn=phi_fn)
    return _run_v1(plan, kernel, tab, Y, n=n, tn=tn, interpret=interpret,
                   transpose=True, name="flashsketch_transpose_v1")


def blockrow_pallas_v1(
    plan: BlockPermPlan,
    A: jnp.ndarray,
    *,
    tn: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """FLASHBLOCKROW forward via the v1 grid-reduction kernel (fp32 only;
    ragged n ok)."""
    d_pad, n = A.shape
    assert d_pad == plan.d_pad
    h_np = _blockrow_table(plan)                            # (κ, M) static
    scale = plan.scale * math.sqrt(plan.d_pad / plan.k_pad)
    kernel = functools.partial(_fwd_kernel_v1, plan=plan, scale=scale,
                               phi_fn=_phi_rows_tile)
    return _run_v1(plan, kernel, h_np, A, n=n, tn=tn, interpret=interpret,
                   name="flashsketch_blockrow_v1")
