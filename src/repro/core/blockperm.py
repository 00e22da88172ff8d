"""BLOCKPERM-SJLT parameterization (paper §4) and shared randomness helpers.

A plan freezes every static quantity of the sketch: logical dims (d, k),
padded dims, block grid (M, B_r, B_c), wiring params (a, b), intra-block
sparsity s, degree κ, and the seed.  The plan is hashable/pytree-static so it
can parameterize jitted functions and Pallas kernels.

Intra-block construction (row-partitioned SJLT, Kane–Nelson "block
construction", used by the paper's theory in App. A.3): the B_r rows of a
block are divided into s chunks of size B_r/s; nonzero i ∈ [s] of column u
lands in chunk i at row  ``i·(B_r/s) + hash(seed,g,h,u,i) mod (B_r/s)`` with
sign from an independent hash bit.  Exactly s nonzeros per column, one per
chunk ⇒ exactly κs nonzeros per column of S, magnitude 1/√(κs).

Competitor GLOBAL families (``plan.family``): CountSketch (Higgins & Boman,
arXiv:2508.14209) and sparse-graph sketches (Hu et al., arXiv:2102.05758)
place their s nonzeros per column anywhere in [k_pad] — no block-permutation
structure.  They are realized on the SAME plan record by forcing κ = M (every
input block may feed every output block; the wiring table is all-blocks) with
a global row partition: nonzero i of global column u lands in global chunk i
at row ``i·(k_pad/s) + hash(seed, TAG, u, i) mod (k_pad/s)``, magnitude 1/√s.
CountSketch is the s = 1 case; the sparse-graph family is a column-degree-s
bipartite expander (s = 4 default).  Because κ == M, every downstream
consumer — kernels, VMEM ladders, tuner keys, the roofline — prices the
all-blocks structure honestly with zero family-specific branches.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import hashing, wiring
from repro.core import precision as precision_mod


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


# Scoped-VMEM budget of every kernel launch (a v5e core has 128 MiB of
# VMEM; Mosaic's default scoped limit is 16 MiB).  The ONE number the
# planner sizes blocks against, the tuner sizes tiles against, and the
# kernels pass to Mosaic as ``vmem_limit_bytes``.  34 MiB is the smallest
# whole MiB at which every default plan of the paper's (d, k) grid stays
# fused at the narrowest tile: d=262144, k=64 is pinned at Br = s = 2
# with Bc = 8192, and its κ=4 double-buffered (Bc, 128) input blocks alone
# take 32 MiB.
VMEM_BUDGET_BYTES = 34 * 2**20

# TPU lane width: a block's last dimension must be a multiple of it or
# the array's full extent.
LANE = 128

# Widest column chunk the in-kernel Φ build materializes at once.
PHI_CHUNK = 512


def phi_chunk(Bc: int) -> int:
    """Column-chunk width of the in-kernel Φ build: the whole block when
    ``Bc <= PHI_CHUNK``, else the widest lane multiple ≤ ``PHI_CHUNK``
    dividing ``Bc`` (block widths above one lane are lane multiples)."""
    if Bc <= PHI_CHUNK:
        return Bc
    for ch in range(PHI_CHUNK, 0, -LANE):
        if Bc % ch == 0:
            return ch
    return Bc


# The kernel variants (single source; tune/sketch_model/benchmarks reuse it).
SKETCH_VARIANTS = ("fwd", "transpose", "blockrow")

# Sketch families a plan can describe.  "blockperm" is the paper's
# BLOCKPERM-SJLT; the GLOBAL families (CountSketch / sparse-graph) have no
# block-permutation structure and are realized as κ == M plans (see module
# docstring).  The blockrow op and the row-sharded partial are
# blockperm-wiring-specific and reject global-family plans in lowering.
GLOBAL_FAMILIES = ("countsketch", "graph")
FAMILIES = ("blockperm",) + GLOBAL_FAMILIES

# Canonical per-column nonzero count of each family (the construction the
# name PROMISES: CountSketch is s=1 by definition, the sparse-graph sketch
# is the s=4 expander).  Single source for the variants registry and the
# family-parametric solver entry points — a caller asking for
# family="countsketch" without pinning s must get THE CountSketch, not a
# blockperm-default s riding a global plan.
FAMILY_DEFAULT_S = {"blockperm": 2, "countsketch": 1, "graph": 4}

# Hash tag separating the global-family row/sign stream from every other
# stream in the repo (0xB10C blockrow wiring, 0x5EED blockrow rows,
# 0x5117 unstructured SJLT, 0xFAD/0x5A3 SRHT).
GLOBAL_FAMILY_TAG = 0x610B

# Gather-fused variants: the input stays in HBM and masked rows are DMA'd
# straight into a VMEM gather scratch (no A[mask] intermediate), so the
# pipelined input blocks are replaced by one (κ·B_c, tn) scratch buffer.
GATHER_VARIANTS = ("fwd_gather", "blockrow_gather")

# The input-stationary forward (permutation wiring only): one pipelined
# view of the input, each block read once against its κ Φ tiles stacked,
# with write-once per-level slots and an (M, B_r, tn) output block
# resident across the input blocks of a column tile.
STATIONARY_VARIANT = "fwd_stationary"


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) array as the chip lays it out: rows
    padded to the dtype's sublane tile (8 rows of 32 bits), columns to
    the 128-lane tile."""
    sub = 8 * (4 // itemsize)
    return (-(-rows // sub) * sub) * (-(-cols // LANE) * LANE) * itemsize


def fused_variant_bytes(kappa: int, Br: int, Bc: int, tn: int,
                        itemsize: int = 4, variant: str = "fwd",
                        phi_itemsize: Optional[int] = None,
                        gather_mask: bool = False, *,
                        M: Optional[int] = None) -> int:
    """VMEM the chip's compiler allocates for one launch of a kernel
    variant at tile width ``tn``: the (κ, Br, Bc) Φ scratch, the
    double-buffered pipelined input and output blocks (or, for the
    ``*_gather`` variants, the single (κ, Bc, tn) row-gather scratch), and
    the in-kernel temporaries of the chunked Φ build and contraction.
    Every array is counted at its padded chip layout (``_tile_bytes``).
    Must track the scratch/pipeline layout in kernels/flashsketch.py;
    ``tests/test_tpu_compile.py`` compiles the main-path launches with
    this number as the Mosaic limit.

    ``itemsize`` is the streamed-operand width (``precision.itemsize``);
    ``phi_itemsize`` the Φ-scratch width, which differs under fp8
    policies (Φ is held in the compute dtype the fp8 stream is upcast
    to, ``precision.compute_itemsize``) — defaults to ``itemsize``.
    ``gather_mask``: the gather kernel zeroes padded rows (plan ``d <
    d_pad``), one more (ch, tn) temporary.  ``M`` (the plan's output blocks) is
    required for ``STATIONARY_VARIANT``, whose slots and output block
    span every output block; no variant's working set depends on n."""
    ci = itemsize if phi_itemsize is None else phi_itemsize
    ch = phi_chunk(Bc)
    # Φ build: iota, compare, select and accumulator at (Br, ch) fp32
    build = 4 * _tile_bytes(Br, ch, 4)
    if variant == STATIONARY_VARIANT:
        if M is None:
            raise ValueError(f"{variant!r} needs M, the plan's output blocks")
        phi = _tile_bytes(kappa * Br, Bc, ci)
        # one double-buffered input block; the (κ, M, Br, tn) fp32 slots
        # and the double-buffered (M, Br, tn) output block
        ins = 2 * _tile_bytes(Bc, tn, itemsize)
        out = (kappa + 2) * M * _tile_bytes(Br, tn, 4)
        # the loaded (ch, tn) chunk, its compute-dtype copy and fp32
        # pieces, the (κ·Br, tn) fp32 accumulators, the fold's (Br, tn)
        temps = (3 * _tile_bytes(ch, tn, 4)
                 + 4 * _tile_bytes(kappa * Br, tn, 4)
                 + 3 * _tile_bytes(Br, tn, 4))
        return phi + ins + out + build + temps
    phi = kappa * _tile_bytes(Br, Bc, ci)
    if variant == "transpose":
        ins = 2 * kappa * _tile_bytes(Br, tn, itemsize)
        out = 2 * _tile_bytes(Bc, tn, 4)
        # κ loaded (Br, tn) operands, the (ch, tn) fp32 chunk results and
        # the transposed (ch, Br) Φ slice
        temps = (kappa * _tile_bytes(Br, tn, ci)
                 + 3 * _tile_bytes(ch, tn, 4) + 2 * _tile_bytes(ch, Br, 4))
    else:
        if variant in GATHER_VARIANTS:
            # input lives in HBM; fp32 rows are DMA'd into a
            # single-buffered (κ·Bc, 1, tn) gather scratch
            ins = kappa * _tile_bytes(Bc, tn, 4)
        else:                               # fwd / blockrow
            ins = 2 * kappa * _tile_bytes(Bc, tn, itemsize)
        out = 2 * _tile_bytes(Br, tn, 4)
        # the loaded (ch, tn) operand chunk, its compute-dtype copy and
        # fp32 pieces, plus the (Br, tn) fp32 accumulators
        temps = (3 * _tile_bytes(ch, tn, 4) + 4 * _tile_bytes(Br, tn, 4)
                 + (_tile_bytes(ch, tn, 4) if gather_mask else 0))
    return phi + ins + out + build + temps


def fused_working_set_bytes(kappa: int, Br: int, Bc: int, tn: int,
                            itemsize: int = 4,
                            phi_itemsize: Optional[int] = None) -> int:
    """Worst case of ``fused_variant_bytes`` over the output-stationary
    forward and the transpose: the kernels every plan must be able to run.
    (The gather and input-stationary variants fall back to them when they
    do not fit.)"""
    return max(
        fused_variant_bytes(kappa, Br, Bc, tn, itemsize, v, phi_itemsize)
        for v in ("fwd", "transpose")
    )


def _aligned_bc(d: int, M: int) -> int:
    """Input block width for M blocks, lane-aligned (TPU lane = 128)."""
    Bc = max(1, math.ceil(d / M))
    if Bc > 128:
        Bc = ((Bc + 127) // 128) * 128
    return Bc


@dataclasses.dataclass(frozen=True)
class BlockPermPlan:
    """Static description of one BLOCKPERM-SJLT draw.

    Per the paper (§4, "we deal with general cases in practice by padding"),
    a requested sketch dimension ``k_req`` is rounded *up* to ``k = M·B_r``:
    the effective sketch has exactly κs nonzeros per column and is unbiased
    (truncating rows instead would break both properties).  The input dim d
    is zero-padded to ``d_pad = M·B_c`` (exact — padded coordinates are 0).
    """

    d: int                 # logical input dim
    k: int                 # effective sketch dim (= k_pad = M * Br)
    k_req: int             # sketch dim the caller asked for (k >= k_req)
    d_pad: int             # padded input dim  = M * Bc
    k_pad: int             # padded sketch dim = M * Br (== k)
    M: int                 # number of blocks per side (power of two)
    Br: int                # output block rows
    Bc: int                # input block cols
    kappa: int             # block degree (number of permutations)
    s: int                 # intra-block nonzeros per column (divides Br)
    seed: int
    a: int                 # wiring LCG multiplier
    b: int                 # wiring LCG offset
    dtype: str = "float32"  # streaming-precision POLICY (canonical name in
                            # core.precision.POLICIES: "float32", "bfloat16",
                            # "fp8_e4m3", "fp8_e5m2", "fp8_e4m3_sr",
                            # "fp8_e5m2_sr"; accumulation is always fp32 —
                            # low-precision streams justified by Jeendgar
                            # et al., PAPERS.md arXiv 2606.20195)
    family: str = "blockperm"  # "blockperm" | "countsketch" | "graph";
                               # global families carry kappa == M (all-blocks
                               # wiring) and a k_pad-wide row partition.

    @property
    def is_global(self) -> bool:
        """Whether the plan is a global (non-block-permutation) family."""
        return self.family in GLOBAL_FAMILIES

    @property
    def nnz_per_col(self) -> int:
        # global families: exactly s nonzeros per column of the FULL S
        # (one per k_pad/s chunk); blockperm: κ·s (s per participating block).
        return self.s if self.is_global else self.kappa * self.s

    @property
    def precision(self) -> precision_mod.Precision:
        """The resolved :class:`~repro.core.precision.Precision` record —
        the single source for every dtype/itemsize/rounding/band question
        about this plan's streaming policy."""
        return precision_mod.resolve(self.dtype)

    @property
    def stream_dtype(self):
        """jnp dtype the input is streamed in (accumulate is always fp32)."""
        return self.precision.stream_dtype

    @property
    def stream_itemsize(self) -> int:
        """Bytes per streamed element (1 for fp8, 2 for bf16, 4 for fp32)."""
        return self.precision.itemsize

    @property
    def scale(self) -> float:
        # 1/√(nnz per column): 1/√s for the global families, 1/√(κs) else.
        return 1.0 / math.sqrt(self.nnz_per_col)

    @property
    def chunk(self) -> int:
        """Row-partition chunk height: B_r/s per block for blockperm,
        k_pad/s globally for the global families."""
        return self.k_pad // self.s if self.is_global else self.Br // self.s

    def neighbors(self, g: int) -> Tuple[int, ...]:
        if self.is_global:
            return tuple(range(self.M))        # every input block feeds g
        return tuple(
            wiring.neighbor_fused(g, ell + 1, self.a, self.b, self.M)
            for ell in range(self.kappa)
        )

    def describe(self) -> str:
        fam = "" if self.family == "blockperm" else f"family={self.family}, "
        return (
            f"BlockPermPlan({fam}d={self.d}->pad{self.d_pad}, k={self.k}->pad{self.k_pad}, "
            f"M={self.M}, Br={self.Br}, Bc={self.Bc}, kappa={self.kappa}, s={self.s}, "
            f"nnz/col={self.nnz_per_col}, dtype={self.dtype}, seed={self.seed})"
        )

    def with_dtype(self, dtype) -> "BlockPermPlan":
        """Same sketch draw, different streaming-precision policy.

        Accepts a canonical policy name, a registered alias (``"fp32"``,
        ``"bf16"``) or a :class:`~repro.core.precision.Precision` record;
        the plan stores the canonical name (tuner-cache/snapshot-stable)."""
        return dataclasses.replace(self, dtype=_check_dtype(dtype))


def _check_dtype(dtype) -> str:
    """Validate a streaming-precision policy; returns its canonical name."""
    return precision_mod.canonical(dtype)


def make_plan(
    d: int,
    k: int,
    *,
    kappa: int = 4,
    s: int = 2,
    seed: int = 0,
    block_rows: Optional[int] = None,
    max_block_rows: int = 256,
    dtype: str = "float32",
    family: str = "blockperm",
) -> BlockPermPlan:
    """Choose a hardware-aligned block grid for (d, k) and freeze the plan.

    Strategy: pick M as a power of two so that B_r = k/M is ≤ max_block_rows
    (keeps the one-hot MXU contraction below the v5e ridge point, see
    DESIGN.md §2) while M ≥ κ (edge-disjointness needs κ ≤ M) and B_r ≥ s.
    d and k are padded up to M·B_c and M·B_r.

    Args:
      d: logical input dimension (rows of the matrices to be sketched).
      k: REQUESTED sketch dimension; the effective ``plan.k`` is rounded
        UP to ``M·B_r`` (never truncated — truncation would break the
        exactly-κs-nonzeros-per-column property and unbiasedness).
      kappa: block degree κ ≥ 1 — number of permuted block patterns whose
        union forms S.  More κ → better embedding, more HBM traffic
        (input streamed κ times).
      s: intra-block nonzeros per column; must divide the resulting B_r
        (powers of two always do).  κ·s is the total nonzeros per column
        of S, each of magnitude 1/√(κs).
      seed: master seed; all randomness (wiring + intra-block hashes)
        derives from it deterministically.
      block_rows: pin B_r explicitly (rounded up to a power of two);
        disables the VMEM-budget auto-shrink.  The pin is HONORED: the
        effective ``plan.Br`` is exactly the rounded pin (M grows as
        needed to keep ``M·B_r ≥ k`` and ``κ ≤ M``), and an unrealizable
        pin (``s`` does not divide the rounded value) raises
        ``ValueError`` instead of being silently clamped.
      max_block_rows: cap on the auto-chosen B_r.
      dtype: streaming-precision policy — any name registered in
        ``repro.core.precision`` (``"float32"`` default, ``"bfloat16"``,
        ``"fp8_e4m3"``, ``"fp8_e5m2"``, ``"fp8_e4m3_sr"``,
        ``"fp8_e5m2_sr"``; aliases ``"fp32"``/``"bf16"`` accepted and
        canonicalized).  Controls only how kernels STREAM the input from
        HBM (``plan.stream_dtype``, rounded per the policy's mode) —
        Φ entries (±1/0) are exact in every policy and accumulation is
        always fp32, so bf16 halves and fp8 quarters the dominant memory
        term at a rounding cost on A.  Unknown policies raise
        ``ValueError``.
      family: ``"blockperm"`` (default), or a GLOBAL family —
        ``"countsketch"`` / ``"graph"``.  Global families place their s
        nonzeros per column anywhere in [k_pad] (no block structure), so
        the plan is frozen with κ = M (all-blocks wiring; the ``kappa``
        argument is ignored) and ``s`` must be a power of two so the
        global row partition k_pad/s is exact.

    Returns:
      A frozen, hashable ``BlockPermPlan`` suitable as a static jit
      argument; pass it to ``repro.kernels.ops.sketch_apply`` (valid
      ``impl=`` values there: ``"auto" | "pallas" | "pallas_v1" | "xla"``).
    """
    if d <= 0 or k <= 0:
        raise ValueError("d and k must be positive")
    if kappa < 1 or s < 1:
        raise ValueError("kappa and s must be >= 1")
    dtype = _check_dtype(dtype)
    if family not in FAMILIES:
        raise ValueError(f"family must be one of {FAMILIES}, got {family!r}")

    if family in GLOBAL_FAMILIES:
        return _make_global_plan(d, k, s=s, seed=seed, block_rows=block_rows,
                                 max_block_rows=max_block_rows, dtype=dtype,
                                 family=family)

    if block_rows is not None:
        # Honor the pin (rounded up to a power of two).  A pin that cannot
        # host the row partition raises — it must never be silently clamped
        # (autotune_plan's B_r sweep relies on distinct pins producing
        # distinct grids).
        Br = _next_pow2(block_rows)
        if Br % s != 0:
            raise ValueError(
                f"block_rows={block_rows} (rounded to Br={Br}) is not "
                f"realizable: s={s} must divide Br")
        M = _next_pow2(max(1, math.ceil(k / Br)))
        # κ ≤ M is required for edge-disjoint wiring; with Br pinned the
        # only degree of freedom is M (k_pad = M·Br grows accordingly).
        while M < kappa:
            M *= 2
    else:
        Br = min(_next_pow2(max(s, min(max_block_rows, k))), max_block_rows)
        Br = max(Br, _next_pow2(s))
        M = _next_pow2(max(1, math.ceil(k / Br)))
        # Ensure κ ≤ M: grow M (shrinking Br) until the wiring is realizable.
        while M < kappa:
            M *= 2
        Br = max(_next_pow2(math.ceil(k / M)), _next_pow2(s))
        if Br % s != 0:
            # s must divide Br for the row partition; round s down to a divisor.
            raise ValueError(f"s={s} must divide Br={Br} (both powers of two ok)")
    Bc = _aligned_bc(d, M)
    # Keep the fused v2 working set (stacked Φ ∝ κ·Br·Bc plus pipelined
    # blocks ∝ Bc, see kernels/flashsketch) resident in VMEM at the
    # narrowest lane-aligned tile by trading Br for M: halving Br doubles M
    # and halves Bc, shrinking both terms while k_pad = M·Br is unchanged.
    # Only when the caller did not pin block_rows.
    if block_rows is None:
        while (fused_working_set_bytes(kappa, Br, Bc, tn=LANE)
               > VMEM_BUDGET_BYTES
               and Br // 2 >= max(_next_pow2(s), 1)):
            Br //= 2
            M *= 2
            Bc = _aligned_bc(d, M)
    k_pad = M * Br
    d_pad = M * Bc
    a, b = wiring.derive_affine_params(seed, M)
    return BlockPermPlan(
        d=d, k=k_pad, k_req=k, d_pad=d_pad, k_pad=k_pad, M=M, Br=Br, Bc=Bc,
        kappa=kappa, s=s, seed=seed, a=a, b=b, dtype=dtype,
    )


def _make_global_plan(d: int, k: int, *, s: int, seed: int,
                      block_rows: Optional[int], max_block_rows: int,
                      dtype: str, family: str) -> BlockPermPlan:
    """Grid selection for the GLOBAL families (CountSketch / sparse-graph).

    Same hardware alignment as the blockperm path, but the frozen degree is
    κ = M: the wiring is all-blocks (kernels use a tiled-arange table), so
    the fused working set carries a full-width stacked Φ of (B_r, M·B_c) =
    (B_r, d_pad).  The VMEM shrink loop still converges — halving B_r
    doubles M and halves B_c, shrinking the Φ term — and the downstream
    v2→v1 ladder covers plans it cannot save.  ``s`` must be a power of
    two with ``s ≤ k_pad`` so the global row partition k_pad/s is exact
    (``hash_mod``'s power-of-two mask path then applies everywhere).
    """
    if s & (s - 1):
        raise ValueError(
            f"family={family!r} requires s to be a power of two "
            f"(the global row partition is k_pad/s), got s={s}")
    if block_rows is not None:
        Br = _next_pow2(block_rows)
        M = _next_pow2(max(1, math.ceil(k / Br)))
    else:
        Br = min(_next_pow2(max(1, min(max_block_rows, k))), max_block_rows)
        M = _next_pow2(max(1, math.ceil(k / Br)))
    Bc = _aligned_bc(d, M)
    if block_rows is None:
        # κ = M tracks the split: the working set is evaluated at the
        # CURRENT M each iteration (Φ = M·Br·Bc shrinks as Br halves).
        while (fused_working_set_bytes(M, Br, Bc, tn=LANE)
               > VMEM_BUDGET_BYTES and Br // 2 >= 1):
            Br //= 2
            M *= 2
            Bc = _aligned_bc(d, M)
    k_pad = M * Br
    if s > k_pad:
        raise ValueError(
            f"family={family!r}: s={s} exceeds the padded sketch dim "
            f"k_pad={k_pad} — the row partition needs s <= k_pad")
    d_pad = M * Bc
    a, b = wiring.derive_affine_params(seed, M)   # unused by the family,
    return BlockPermPlan(                         # kept for record parity
        d=d, k=k_pad, k_req=k, d_pad=d_pad, k_pad=k_pad, M=M, Br=Br, Bc=Bc,
        kappa=M, s=s, seed=seed, a=a, b=b, dtype=dtype, family=family,
    )


# ---------------------------------------------------------------------------
# Shared randomness: destination rows and signs for the intra-block SJLT.
# These functions are used verbatim by ref.py and by the Pallas kernel body;
# tests assert bit-identical streams.
# ---------------------------------------------------------------------------

def block_rows_signs(plan: BlockPermPlan, g, h, u, i):
    """Destination row in [Br] and sign for nonzero i of column u of block (g,h).

    Args:
      plan: the frozen sketch draw (supplies seed and chunk height B_r/s).
      g, h: output/input block indices in [M].
      u: column index within the block, in [B_c].
      i: nonzero index within the column, in [s] (selects the row chunk).
      All of (g, h, u, i) may be arrays (broadcastable against each other);
      integer dtypes are cast to uint32 for hashing.

    Returns:
      ``(rows, signs)``: int32 rows in ``[0, B_r)`` (nonzero i lands in
      chunk i, i.e. ``rows // (B_r/s) == i``) and float32 signs in {±1}.
      Both the jnp reference oracle and the Pallas kernel body call THIS
      function, so the streams are bit-identical by construction.
    """
    hsh = hashing.hash_words(
        np.uint32(plan.seed),
        jnp.asarray(g, jnp.uint32),
        jnp.asarray(h, jnp.uint32),
        jnp.asarray(u, jnp.uint32),
        jnp.asarray(i, jnp.uint32),
    )
    chunk = plan.chunk
    rows = jnp.asarray(i, jnp.int32) * chunk + hashing.hash_mod(hsh, chunk)
    signs = hashing.hash_to_unit_sign(hsh)
    return rows, signs


def dense_block(plan: BlockPermPlan, g, h) -> jnp.ndarray:
    """Materialize Φ_{g,h} ∈ R^{Br×Bc} via one-hot sum.

    Args:
      plan: the frozen sketch draw.
      g, h: scalar block indices in [M] (python ints or traced scalars).

    Returns:
      ``(Br, Bc)`` float32 array with entries in {-1, 0, +1} — exactly s
      nonzeros per column, one per B_r/s-row chunk — WITHOUT the global
      1/√(κs) scale.  Used by the reference oracle and (tile-wise) inside
      the Pallas kernel; bit-exactness between the two is tested.
    """
    u = jnp.arange(plan.Bc, dtype=jnp.int32)            # (Bc,)
    i = jnp.arange(plan.s, dtype=jnp.int32)             # (s,)
    rows, signs = block_rows_signs(
        plan, g, h, u[None, :], i[:, None]
    )                                                    # (s, Bc) each
    row_iota = jnp.arange(plan.Br, dtype=jnp.int32)      # (Br,)
    onehot = (row_iota[None, :, None] == rows[:, None, :]).astype(jnp.float32)
    phi = jnp.sum(onehot * signs[:, None, :], axis=0)    # (Br, Bc)
    return phi


def global_rows_signs(plan: BlockPermPlan, u, i):
    """Destination GLOBAL row in [k_pad] and sign for nonzero i of global
    column u — the CountSketch / sparse-graph construction.

    Args:
      plan: a GLOBAL-family plan (supplies seed and the global chunk
        height k_pad/s).
      u: GLOBAL column index in [d_pad].
      i: nonzero index within the column, in [s] (selects the row chunk;
        CountSketch is the s = 1 case).
      Both may be arrays (broadcastable against each other).

    Returns:
      ``(rows, signs)``: int32 global rows in ``[0, k_pad)`` (nonzero i
      lands in chunk i: ``rows // (k_pad/s) == i``) and float32 signs in
      {±1}.  The jnp oracle, ``dense_global_block`` and the Pallas kernel
      body all call THIS function — bit-identical streams by construction.
    """
    hsh = hashing.hash_words(
        np.uint32(plan.seed),
        np.uint32(GLOBAL_FAMILY_TAG),
        jnp.asarray(u, jnp.uint32),
        jnp.asarray(i, jnp.uint32),
    )
    chunk = plan.chunk                                   # k_pad // s
    rows = jnp.asarray(i, jnp.int32) * chunk + hashing.hash_mod(hsh, chunk)
    signs = hashing.hash_to_unit_sign(hsh)
    return rows, signs


def dense_global_block(plan: BlockPermPlan, g, h) -> jnp.ndarray:
    """Block (g, h) of the GLOBAL family's S as a dense ``(Br, Bc)`` tile
    (unscaled): the rows of S in ``[g·Br, (g+1)Br)`` restricted to columns
    ``[h·Bc, (h+1)Bc)``.  Nonzeros whose global row lands outside block g
    are masked out by the row comparison — the fused kernel sums these
    tiles over all M values of h, recovering every nonzero exactly once.
    """
    u = h * plan.Bc + jnp.arange(plan.Bc, dtype=jnp.int32)   # global columns
    i = jnp.arange(plan.s, dtype=jnp.int32)                  # (s,)
    rows, signs = global_rows_signs(plan, u[None, :], i[:, None])  # (s, Bc)
    local = rows - g * plan.Br
    row_iota = jnp.arange(plan.Br, dtype=jnp.int32)          # (Br,)
    onehot = (row_iota[None, :, None] == local[:, None, :]).astype(jnp.float32)
    return jnp.sum(onehot * signs[:, None, :], axis=0)       # (Br, Bc)


def materialize_sketch_matrix(plan: BlockPermPlan) -> jnp.ndarray:
    """Full S ∈ R^{k_pad × d_pad} as a DENSE fp32 array — tests and tiny
    benchmarks only (O(k_pad · d_pad) memory defeats the whole point of
    the sketch at real sizes).  Includes the 1/√(κs) scale (1/√s for the
    global families), so ``S @ A_padded`` equals
    ``ops.sketch_apply(plan, A)`` up to fp32 rounding regardless of impl;
    the streaming ``dtype`` knob does not apply here (dense math is fp32
    throughout).
    """
    if plan.is_global:
        u = jnp.arange(plan.d_pad, dtype=jnp.int32)
        i = jnp.arange(plan.s, dtype=jnp.int32)
        rows, signs = global_rows_signs(plan, u[None, :], i[:, None])
        S = jnp.zeros((plan.k_pad, plan.d_pad), dtype=jnp.float32)
        for ii in range(plan.s):
            S = S.at[rows[ii], u].add(signs[ii])
        return S * plan.scale
    pi = wiring.wiring_table(plan.seed, plan.M, plan.kappa)  # (κ, M)
    S = jnp.zeros((plan.k_pad, plan.d_pad), dtype=jnp.float32)
    for g in range(plan.M):
        for ell in range(plan.kappa):
            h = int(pi[ell, g])
            phi = dense_block(plan, g, h)
            S = S.at[
                g * plan.Br:(g + 1) * plan.Br,
                h * plan.Bc:(h + 1) * plan.Bc,
            ].add(phi)
    return S * plan.scale
