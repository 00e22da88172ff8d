"""Analytic roofline model for the FlashSketch kernel generations.

Models one application ``Y = S A`` (or the transpose) on a single TPU chip
as ``max(MXU term, VPU term, HBM term)``:

  * MXU — the one-hot Φ contraction: ``2·κ·B_r·d_pad·n`` MACs·2, identical
    for v1 and v2 (fusing the κ reduction moves *where* the adds happen,
    not how many).
  * VPU — Φ construction from counter-based hashes.  v1 rebuilds the
    (B_r, B_c) tile for every program ``(j, g, ℓ)`` ⇒ n/T_n rebuilds per
    block pair; v2 caches the stacked Φ in VMEM scratch and rebuilds only
    at ``j == 0`` ⇒ exactly κ·M tile builds per launch, an n/T_n-fold
    saving.
  * HBM — the dominant term in the paper's d ≫ k regime.  Both versions
    stream each input block κ times (every input block feeds κ output
    blocks).  v1's κ-revisiting grid reduction charges a read-modify-write
    of the fp32 output tile per revisit (``(2κ−1)·k_pad·n`` fp32 accesses,
    the semantics the paper ascribes to scatter-style sketches); v2 writes
    each output tile exactly once.  v2 streams the input at the plan's
    precision-policy width on top — bf16 halves it, the fp8 policies
    quarter it (1 byte/elem; fp32 accumulate in-register, per Jeendgar
    et al. sketching is robust to this rounding).  v1 is fp32-only.

These terms feed ``benchmarks/kernel_bench.py`` (modeled speedups alongside
measured interpret-mode ones) and ``core.variants`` cost models.
"""
from __future__ import annotations

import dataclasses

from repro.core.blockperm import SKETCH_VARIANTS, BlockPermPlan
from repro.roofline import hw

# ~ops per hashed word: 5-word hash_words chain, ~6 ALU ops per mix/combine.
HASH_OPS_PER_WORD = 30.0

VARIANTS = SKETCH_VARIANTS


@dataclasses.dataclass(frozen=True)
class KernelCost:
    """Per-chip cost terms for one kernel launch (plus any collective).

    ``ici_bytes`` is the per-chip interconnect traffic of a trailing
    collective (0 for single-chip launches).  Collectives do not overlap
    the compute of the same launch in this first-order model, so the ICI
    term ADDS to the roofline max instead of joining it.
    """

    mxu_flops: float
    vpu_flops: float
    hbm_bytes: float
    # bf16-streaming kernels feed the MXU bf16 inputs (fp32 accumulate);
    # fp32 streams run at the half-rate fp32 MXU throughput.
    mxu_peak: float = hw.PEAK_FLOPS_FP32
    ici_bytes: float = 0.0

    @property
    def compute_s(self) -> float:
        return self.mxu_flops / self.mxu_peak

    @property
    def vpu_s(self) -> float:
        return self.vpu_flops / hw.PEAK_FLOPS_VPU

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / hw.HBM_BW

    @property
    def ici_s(self) -> float:
        return self.ici_bytes / hw.ICI_BW

    @property
    def modeled_us(self) -> float:
        return 1e6 * (max(self.compute_s, self.vpu_s, self.memory_s)
                      + self.ici_s)

    @property
    def bottleneck(self) -> str:
        terms = {"mxu": self.compute_s, "vpu": self.vpu_s,
                 "hbm": self.memory_s, "ici": self.ici_s}
        return max(terms, key=terms.get)


def kernel_cost(
    plan: BlockPermPlan,
    n: int,
    *,
    version: str = "v2",
    variant: str = "fwd",
    tn: int = 128,
    gather: bool = False,
    batch: int = 1,
    stationary: bool = False,
) -> KernelCost:
    """Single-launch cost terms.

    ``stationary`` models the input-stationary forward: A is read from
    HBM once instead of κ times, and Φ is built once per column tile (as
    v1 does); the MXU term is unchanged.

    ``batch`` folds a B-stack into the column axis (the batched apply):
    every term scales with ``n·batch`` but the Φ build cost stays per-launch
    — the cached Φ tile is reused across the whole batch.

    ``gather`` models the gather-fused load (``fwd``/``blockrow`` only):
    each of the κ·d_pad gathered rows is a non-contiguous HBM read of
    ``tn·itemsize`` bytes per column tile, charged at transaction
    granularity (``hw.HBM_TRANSACTION_BYTES`` floor) — wide tiles amortize
    the transaction, skinny per-example launches eat it whole.

    Global families (countsketch/graph) need NO special casing: their plans
    carry ``kappa == M`` (every input block feeds every output block), so
    the formulas below price them verbatim — MXU work becomes the dense-like
    ``2·k_pad·d_pad·n`` (the structural reason BlockPerm wins the Pareto
    race on the matrix unit), the input is streamed M times, and the Φ build
    count ``κ·M = M²`` matches the M² tiles the fused kernel materializes.
    """
    if version not in ("v1", "v2"):
        raise ValueError(f"version must be 'v1' or 'v2', got {version!r}")
    if variant not in VARIANTS:
        raise ValueError(f"variant must be one of {VARIANTS}, got {variant!r}")
    if gather and variant == "transpose":
        raise ValueError("gather-fused loads exist for fwd/blockrow only")
    p = plan
    # v1 predates the mixed-precision path: always streams fp32.  v2
    # streams at the precision policy's width (1 B fp8, 2 B bf16, 4 B
    # fp32) and feeds the MXU at the policy's compute width (fp8 upcasts
    # to bf16 in VMEM — HBM pays 1 B/elem, the MXU runs at the bf16 rate).
    prec = p.precision
    in_itemsize = prec.itemsize if version == "v2" else 4
    mxu_itemsize = prec.compute_itemsize if version == "v2" else 4
    n_eff = n * max(1, batch)
    n_tiles = max(1, (n_eff + tn - 1) // tn)

    mxu = 2.0 * p.kappa * p.Br * p.d_pad * n_eff

    # Φ tile build: s hash passes over the hashed axis (Bc words for the
    # column-pattern kernels, Br for blockrow's per-row pattern).
    words = p.Br if variant == "blockrow" else p.Bc
    per_tile = HASH_OPS_PER_WORD * p.s * words
    tile_builds = p.kappa * p.M * (n_tiles if version == "v1" or stationary
                                   else 1)
    vpu = per_tile * tile_builds

    if variant == "transpose":
        in_elems = p.kappa * p.k_pad * n_eff  # Y gathered κ× via inverse maps
        out_elems = p.d_pad * n_eff
    else:
        # A streamed κ× (once by the input-stationary forward)
        in_elems = (1 if stationary else p.kappa) * p.d_pad * n_eff
        out_elems = p.k_pad * n_eff
    out_accesses = (2 * p.kappa - 1) * out_elems if version == "v1" else out_elems
    if gather:
        # κ·d_pad row reads per column tile, each at transaction granularity
        row_bytes = max(float(tn * in_itemsize), hw.HBM_TRANSACTION_BYTES)
        in_bytes = p.kappa * p.d_pad * n_tiles * row_bytes
    else:
        in_bytes = in_itemsize * in_elems
    hbm = in_bytes + 4.0 * out_accesses

    peak = hw.PEAK_FLOPS_BF16 if mxu_itemsize == 2 else hw.PEAK_FLOPS_FP32
    return KernelCost(mxu_flops=mxu, vpu_flops=vpu, hbm_bytes=hbm,
                      mxu_peak=peak)


def cost_of(lw) -> KernelCost:
    """Per-chip cost of a ``kernels.lowering.Lowering`` record.

    THE bridge between dispatch and model: the terms are derived from the
    record that actually launches — the resolved kernel generation
    (``pallas_v1`` models v1, everything else the v2 formulation the
    lowering would run on TPU), the resolved tile, the *launched* gather
    organization (``gather_fused`` — a materialized fallback is charged as
    the regular kernel it runs), the per-device workload under sharding —
    so the model cannot drift from the kernel without the Lowering record
    itself changing (which the golden-snapshot test turns into an explicit
    diff).

    Sharding: ``shard="row"`` routes to ``dist_sketch_cost`` (1/P compact
    partial + ring psum); ``"col"``/``"batch"`` are collective-free and
    charge the per-device slab (``n_loc``/``batch_loc``); ``"none"`` is
    ``kernel_cost`` verbatim.  A row-sharded record downgraded to the jnp
    oracle partial is still charged as the sharded ORGANIZATION (1/P slab
    streams + the psum): the roofline describes the data movement of the
    organization, and the oracle einsum moves the same slab — executor
    overhead is out of the first-order model's scope.

    Note: a ``tn=None`` record (the xla oracle) is charged at the default
    128-wide tile — the modeled hardware is a TPU regardless of which
    backend traced the lowering.
    """
    tn = lw.tn if lw.tn is not None else 128
    if lw.shard == "row":
        return dist_sketch_cost(lw.plan, lw.n_eff, lw.devices,
                                variant=lw.op, tn=tn)
    return kernel_cost(
        lw.plan, lw.n_loc,
        version=lw.version, variant=lw.op, tn=tn,
        gather=lw.gather_fused, batch=lw.batch_loc,
        stationary=lw.stationary,
    )


def modeled_speedup(
    plan: BlockPermPlan,
    n: int,
    *,
    variant: str = "fwd",
    tn: int = 128,
) -> float:
    """Modeled-TPU speedup of v2 (at the plan's dtype) over fp32 v1."""
    v1 = kernel_cost(plan, n, version="v1", variant=variant, tn=tn)
    v2 = kernel_cost(plan, n, version="v2", variant=variant, tn=tn)
    return v1.modeled_us / v2.modeled_us


def psum_bytes_per_chip(payload_bytes: float, devices: int) -> float:
    """Per-chip ICI traffic of a ring all-reduce of ``payload_bytes``:
    reduce-scatter + all-gather each move ``(P-1)/P`` of the payload."""
    if devices <= 1:
        return 0.0
    return 2.0 * (devices - 1) / devices * payload_bytes


def dist_sketch_cost(
    plan: BlockPermPlan,
    n: int,
    devices: int,
    *,
    variant: str = "fwd",
    tn: int = 128,
    exact_reduction: bool = True,
) -> KernelCost:
    """Per-chip cost of the ROW-SHARDED sketch (``distributed.sharded_apply``).

    Each of ``devices`` chips runs the partial kernel on its ``d_pad/P``
    row slab — the dominant HBM input stream scales 1/P — then psums the
    partials.  With ``exact_reduction`` (the implemented protocol) the
    per-ℓ partials stay stacked, so the collective payload AND the local
    partial writes are κ·k_pad·n fp32 (the price of bit-exactness);
    ``exact_reduction=False`` models a plain (k_pad, n) psum.  MXU,
    HBM-input and Φ-build (VPU) all shard 1/P because the partial kernel's
    grid is COMPACT — ``(M_loc, κ, n/tn)`` over the κ·M/P owned (g, ℓ)
    pairs only (ownership is a partition, π_ℓ a permutation); the model
    charges exactly what ``flashsketch_pallas_partial`` executes.

    Only ``variant="fwd"`` is modeled: the FLASHBLOCKROW partial is
    masked full-grid (iid wiring is no permutation) and does NOT shard
    its per-chip compute — returning 1/P terms for it would certify
    scaling the kernel cannot deliver, so anything else raises.
    """
    if devices < 1:
        raise ValueError(f"devices must be >= 1, got {devices}")
    if variant != "fwd":
        raise ValueError(
            f"dist_sketch_cost models the compact 'fwd' partial only; "
            f"variant={variant!r} has no sharded-compute formulation")
    base = kernel_cost(plan, n, version="v2", variant=variant, tn=tn)
    if devices == 1:
        return base
    p = plan
    kappa_out = p.kappa if exact_reduction else 1
    in_bytes = p.stream_itemsize * p.kappa * (p.d_pad / devices) * n
    out_bytes = 4.0 * kappa_out * p.k_pad * n
    payload = 4.0 * kappa_out * p.k_pad * n
    return KernelCost(
        mxu_flops=base.mxu_flops / devices,
        vpu_flops=base.vpu_flops / devices,
        hbm_bytes=in_bytes + out_bytes,
        mxu_peak=base.mxu_peak,
        ici_bytes=psum_bytes_per_chip(payload, devices),
    )


def modeled_dist_speedup(
    plan: BlockPermPlan,
    n: int,
    devices: int,
    *,
    variant: str = "fwd",
    tn: int = 128,
    exact_reduction: bool = True,
) -> float:
    """Modeled multi-chip scaling: single-chip v2 time over per-chip
    row-sharded time (local partial + psum).  The number the
    ``dist_bench`` gate holds ≥ 1.5× at 8 devices — in the paper's d ≫ k
    regime the 1/P HBM saving dominates the κ·k·n psum."""
    single = kernel_cost(plan, n, version="v2", variant=variant, tn=tn)
    dist = dist_sketch_cost(plan, n, devices, variant=variant, tn=tn,
                            exact_reduction=exact_reduction)
    return single.modeled_us / dist.modeled_us


def grass_sketch_cost(
    plan: BlockPermPlan,
    batch: int,
    *,
    fused: bool = True,
    batched: bool = True,
    version: str = "v2",
    tn: int = 128,
    variant: str = "fwd",
) -> float:
    """Modeled us to sketch ``batch`` sparsified per-example gradients.

    The GraSS inner loop (sparsify → sketch, §7.4/App. E), in its four
    organizations:

      * ``fused & batched`` — ONE gather-fused launch over the whole batch
        folded into the column axis (the PR-3 path).
      * ``fused, not batched`` — B gather-fused single-column launches.
      * ``not fused, batched`` — a gather pass materializes ``A[mask]``
        (transaction-granular read + contiguous write), then one batched
        sketch launch re-reads it κ×.
      * ``not fused, not batched`` — the seed pipeline: per example, a
        materializing gather + a skinny (n = 1) sketch launch.  Every
        gathered element pays a full HBM transaction and every example
        pays two kernel launches.

    ``plan.d`` is the sparsified dim d_keep; the source dim only enters
    through the transaction-granular gather term (index-independent).
    """
    if fused:
        eff_tn = min(tn, max(1, batch)) if batched else 1
        kc = kernel_cost(plan, 1, version=version, variant=variant,
                         tn=max(8, eff_tn), gather=True,
                         batch=batch if batched else 1)
        if batched:
            return kc.modeled_us + hw.KERNEL_LAUNCH_US
        return batch * (kc.modeled_us + hw.KERNEL_LAUNCH_US)
    # unfused: materialize A[mask] first, then run the regular kernel on it
    cols = batch if batched else 1
    row_read = max(4.0 * cols, hw.HBM_TRANSACTION_BYTES)   # per gathered row
    gather_us = 1e6 * (plan.d * row_read + 4.0 * plan.d * cols) / hw.HBM_BW
    kc = kernel_cost(plan, 1, version=version, variant=variant,
                     tn=max(8, min(tn, cols)), batch=cols)
    per_pass = gather_us + kc.modeled_us + 2 * hw.KERNEL_LAUNCH_US
    return per_pass if batched else batch * per_pass
