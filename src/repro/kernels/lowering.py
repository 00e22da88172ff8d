"""The sketch lowering engine: every launch decision in ONE record.

The paper's sketch–kernel co-design means *how* a sketch launches — which
kernel generation, which tile, which precision, whether the row gather is
fused, whether the batch is folded, how a mesh shards it — IS the product.
This module is the single place those decisions are made:

  * ``lower(plan, spec) -> Lowering`` — resolve a ``LaunchSpec`` (the
    caller's request: op, n, impl/tn/dtype knobs, gather/batch/shard) into
    a frozen ``Lowering`` record holding every decision: the resolved
    impl (plus the reason for any downgrade), the tile width and where it
    came from (explicit / tuned / heuristic / v1 default), the effective
    streaming dtype, whether the gather stays fused, the per-device
    workload under sharding, the VMEM footprint, and the padding plan.
  * ``execute(lowering, operand, row_index=None)`` — run a single-device
    lowering.  ``kernels.ops`` entry points are thin ``custom_vjp`` shells
    around ``lower`` + ``execute``; ``repro.distributed`` lowers its
    per-device partial through the same ``lower`` and executes it inside
    ``shard_map``.
  * ``explain(plan, ...)`` — the human-readable decision trace (chosen
    tile, rejected candidates, downgrade reasons); also behind
    ``tools/explain_lowering.py``.
  * ``roofline.sketch_model.cost_of(lowering)`` — the modeled cost of the
    record *that launches*, so model/kernel drift is structural, not
    review-caught.

``lower`` is memoized process-wide, keyed like the tuner cache — the plan
(which carries the shape class: d_pad/k_pad/M/Br/Bc/κ/s/dtype), the full
spec, the backend tag, and ``tune.cache_generation()`` so freshly tuned
winners invalidate stale records.  Every lookup counts
``lowering.memo_hit`` or ``lowering.memo_miss`` in the counter registry
(``repro.health.report``), which ``explain`` prints.  ``lower`` and
``execute`` run under the program spans ``repro.lowering.lower`` and
``repro.lowering.execute`` (``repro.tracing``).

``execute`` on concrete arrays (no ``jax.core.Tracer`` among the operand
and row index) runs a compiled executor from a second process-wide memo:
``jax.jit`` of the launch body (validation, row map, pad, stream cast,
kernel, slice) closed over the record, keyed by the ``Lowering`` and the
operand's and row index's shape and dtype.  A warm eager call is then one
jit dispatch of one compiled program — no trace of the kernel body, no
Mosaic lowering, no program load — under the span ``repro.kernel.<name>``
of the kernel it launches.  Each concrete call counts
``lowering.exec_memo_hit`` or ``lowering.exec_memo_miss``;
``executor_cache_size`` reports the memo's size, and it is flushed with
the lowering memo (``clear_lowering_cache`` and the tuner-generation
flush).  Traced calls (under ``jit``, ``grad``, ``scan``, ``shard_map``)
and the ``xla`` oracle run the body inline, as part of the caller's
program.

The downgrade ladder (each step recorded in ``Lowering.downgrade``):

  1. ``pallas`` + gather, fused scratch over budget → materialize the
     gather, continue as the non-gather op (PR-3 semantics).
  2. ``pallas`` (v2), stacked Φ scratch over budget at the minimum tile →
     ``pallas_v1`` (the revisiting kernel's working set is per-pair).
  3. row-sharded partial, (B_r, B_c) Φ tile over budget at the minimum
     tile → the jnp oracle partial (there is no v1 partial formulation).

One rung picks between the two v2 forwards; it is a choice, not a
downgrade.  A ``pallas`` forward (not gathered, not v1) of a plan whose
wiring is a permutation per level (the ``blockperm`` family) runs the
input-stationary kernel (``Lowering.stationary``, variant
``fwd_stationary``: each block of A read once) when its per-level slots,
output block and stacked Φ fit the budget at the minimum tile
(``stationary_fits``; they grow with k, not with n); every other forward
keeps the output-stationary kernel.  Each record that takes it counts
``lowering.fwd_input_stationary``.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import precision as precision_mod
from repro.core.blockperm import (STATIONARY_VARIANT, VMEM_BUDGET_BYTES,
                                  BlockPermPlan, fused_variant_bytes)
from repro.health import report as health_report
from repro.kernels import flashsketch as fsk
from repro.kernels import ref as kref
from repro.kernels import tune
from repro.tracing import scope, scoped

OPS = ("fwd", "transpose", "blockrow")
SHARDS = ("none", "row", "col", "batch")
IMPLS = ("auto", "pallas", "pallas_v1", "xla")
GATHER_OPS = ("fwd", "blockrow")

_PALLAS_IMPLS = ("pallas", "pallas_v1")


@dataclasses.dataclass(frozen=True)
class LaunchSpec:
    """A caller's launch request, before any resolution.

    Attributes:
      op: ``"fwd"`` (``Y = S A``), ``"transpose"`` (``X = Sᵀ Y``) or
        ``"blockrow"`` (FLASHBLOCKROW forward).
      n: per-matrix logical column count of the operand.
      impl: requested dispatch — ``"auto" | "pallas" | "pallas_v1" |
        "xla"``.  ``auto`` resolves per backend; the rest may still be
        downgraded (recorded in ``Lowering.downgrade``).
      tn: requested column-tile width, or ``None`` to defer to the tuner
        cache / VMEM heuristic.
      dtype: streaming-precision POLICY override — any name registered
        in ``repro.core.precision`` (``"float32"``, ``"bfloat16"``, the
        fp8 policies, or an alias); ``None`` keeps the plan's knob.
      gather: fuse a per-row gather into the kernel load (``fwd`` /
        ``blockrow`` only — the ``row_index=`` paths).
      batch: batched-apply fold factor (a B-stack folded into the column
        axis: the launch sees ``n·batch`` effective columns, the tuner its
        batched shape class).
      shard: ``"none"`` (single device), ``"row"`` (psum'd per-ℓ partial
        kernel), ``"col"`` / ``"batch"`` (collective-free slabs).
      devices: shard degree P (ignored for ``shard="none"``).
    """

    op: str = "fwd"
    n: int = 1
    impl: str = "auto"
    tn: Optional[int] = None
    dtype: Optional[str] = None
    gather: bool = False
    batch: int = 1
    shard: str = "none"
    devices: int = 1


@dataclasses.dataclass(frozen=True)
class Lowering:
    """Every decision of one sketch launch, frozen.

    Field groups:

      * identity — ``plan`` (effective: the ``dtype`` override already
        applied), ``op``, ``dtype``.
      * dispatch — ``impl_requested`` → ``impl``, with ``downgrade``
        holding the human-readable reason for any forced change (``None``
        when the request ran as asked).
      * tiling — ``tn`` (``None`` for the xla oracle) and ``tn_source``
        (``"explicit" | "tuned" | "loaded" | "heuristic" | "v1_default"``),
        ``grid_cols`` = number of column tiles of the launch.
      * fusion — ``gather`` (requested) vs ``gather_fused`` (what runs:
        ``False`` means the gather is materialized first), ``batch``.
      * sharding — ``shard``, ``devices``, and the per-device workload
        ``n_loc``/``batch_loc``/``n_eff = n_loc·batch_loc`` that the
        kernel (and the cost model) actually sees.
      * footprint — ``vmem_bytes`` of the launched kernel's working set
        (``None`` for xla), which the chip's compiler must not exceed;
        ``pad_rows`` = zero rows added to the operand
        before launch, ``pad_cols`` = columns padded in HBM — ALWAYS 0:
        ragged column tails are handled in-kernel (masked edge tiles /
        clipped gather DMA), never by copying the operand.
      * ``interpret`` — the Pallas kernel runs in interpret mode.  Set
        only for a Pallas impl off the TPU backend, and shown by
        ``describe()``: on a TPU every Pallas lowering compiles.
      * ``stationary`` — the forward runs the input-stationary kernel
        (``stationary_fits``); ``False`` for every other launch.
    """

    plan: BlockPermPlan
    op: str
    impl: str
    impl_requested: str
    downgrade: Optional[str]
    tn: Optional[int]
    tn_source: str
    dtype: str
    gather: bool
    gather_fused: bool
    batch: int
    shard: str
    devices: int
    n: int
    n_loc: int
    batch_loc: int
    n_eff: int
    grid_cols: Optional[int]
    vmem_bytes: Optional[int]
    pad_rows: int
    pad_cols: int
    interpret: bool = False
    stationary: bool = False

    @property
    def variant(self) -> str:
        """Tuner/VMEM shape-class name of the kernel that runs."""
        if self.stationary:
            return STATIONARY_VARIANT
        return self.op + ("_gather" if self.gather_fused else "")

    @property
    def version(self) -> str:
        """Cost-model kernel generation of the launch (xla models v2)."""
        return "v1" if self.impl == "pallas_v1" else "v2"

    def describe(self) -> str:
        bits = [f"{self.op}", f"impl={self.impl}"]
        if self.impl != self.impl_requested:
            bits[-1] += f"(req {self.impl_requested})"
        if self.interpret:
            bits.append("interpret")
        if self.stationary:
            bits.append("input-stationary")
        bits.append(f"tn={self.tn}:{self.tn_source}")
        bits.append(f"dtype={self.dtype}")
        if self.gather:
            bits.append("gather=" + ("fused" if self.gather_fused
                                     else "materialized"))
        if self.batch > 1:
            bits.append(f"batch={self.batch}")
        if self.shard != "none":
            bits.append(f"shard={self.shard}x{self.devices}")
        bits.append(f"n={self.n}->eff{self.n_eff}")
        if self.vmem_bytes is not None:
            bits.append(f"vmem={self.vmem_bytes}B")
        if self.downgrade:
            bits.append(f"downgrade[{self.downgrade}]")
        return "Lowering(" + ", ".join(bits) + ")"

    def to_json(self) -> Dict:
        """Stable JSON form (the golden-snapshot serialization)."""
        p = self.plan
        return {
            "op": self.op,
            "impl": self.impl,
            "impl_requested": self.impl_requested,
            "downgrade": self.downgrade,
            "tn": self.tn,
            "tn_source": self.tn_source,
            "dtype": self.dtype,
            "gather": self.gather,
            "gather_fused": self.gather_fused,
            "batch": self.batch,
            "shard": self.shard,
            "devices": self.devices,
            "n": self.n,
            "n_loc": self.n_loc,
            "batch_loc": self.batch_loc,
            "n_eff": self.n_eff,
            "grid_cols": self.grid_cols,
            "vmem_bytes": self.vmem_bytes,
            "pad_rows": self.pad_rows,
            "pad_cols": self.pad_cols,
            "interpret": self.interpret,
            "stationary": self.stationary,
            "variant": self.variant,
            "version": self.version,
            "plan": {"d": p.d, "d_pad": p.d_pad, "k_pad": p.k_pad,
                     "M": p.M, "Br": p.Br, "Bc": p.Bc,
                     "kappa": p.kappa, "s": p.s, "dtype": p.dtype,
                     "family": p.family},
        }


# ---------------------------------------------------------------------------
# VMEM footprint models (single source; the sharded path re-exports its
# predicate from here so kernels and distributed share one budget model).
# ---------------------------------------------------------------------------

def v1_working_set_bytes(plan: BlockPermPlan, tn: int,
                         op: str = "fwd") -> int:
    """v1 revisiting kernel working set at tile width ``tn``: one (Br, Bc)
    fp32 Φ scratch, one double-buffered fp32 block pair and the chunked
    contraction's temporaries — the κ=1 fused footprint of its op."""
    return fused_variant_bytes(1, plan.Br, plan.Bc, tn, 4,
                               "transpose" if op == "transpose" else "fwd", 4)


def partial_vmem_bytes(plan: BlockPermPlan, tn: int) -> int:
    """Row-sharded partial kernel working set at tile width ``tn``: one
    (B_r, B_c) Φ scratch + one double-buffered pipelined input view + the
    output tile — exactly the κ=1 fused-fwd footprint (the per-ℓ grid
    carries ONE Φ tile and ONE input block per program, regardless of the
    plan's κ)."""
    return fused_variant_bytes(1, plan.Br, plan.Bc, tn,
                               plan.stream_itemsize, "fwd",
                               plan.precision.compute_itemsize)


def partial_fits_vmem(plan: BlockPermPlan, tn: int) -> bool:
    """Whether the partial kernel's working set fits the VMEM budget."""
    return partial_vmem_bytes(plan, tn) <= VMEM_BUDGET_BYTES


def stationary_fits(plan: BlockPermPlan, n_eff: int) -> bool:
    """Whether a ``pallas`` forward of ``plan`` over ``n_eff`` columns (on
    one device, or per device of a column- or batch-sharded launch) runs
    the input-stationary kernel: the wiring must be a permutation per
    level (a ``blockperm`` plan — the global families' all-blocks wiring
    is not), and its ``(κ, M, B_r, tn)`` slots, ``(M, B_r, tn)`` output
    block and stacked Φ must fit the VMEM budget at the minimum tile.
    None of these grows with n, so for a given plan the answer changes
    only where the minimum tile does (n below one lane).
    Level ℓ's tile sits at row ℓ·B_r of the stacked Φ, a slice the chip
    takes only at whole sublane tiles of Φ's dtype (8 rows of 32 bits),
    so B_r must be a multiple of that tile."""
    sublanes = 8 * (4 // plan.precision.compute_itemsize)
    return (not plan.is_global and plan.Br % sublanes == 0
            and tune.fused_fits_vmem(plan, n_eff, STATIONARY_VARIANT))


# ---------------------------------------------------------------------------
# lower(): spec -> Lowering
# ---------------------------------------------------------------------------

def _validate(plan: BlockPermPlan, spec: LaunchSpec) -> None:
    if spec.op not in OPS:
        raise ValueError(f"op must be one of {OPS}, got {spec.op!r}")
    if spec.impl not in IMPLS:
        raise ValueError(
            f"impl must be one of ('auto', 'pallas', 'pallas_v1', 'xla'), "
            f"got {spec.impl!r}")
    if spec.shard not in SHARDS:
        raise ValueError(f"shard must be one of {SHARDS}, got {spec.shard!r}")
    if spec.n < 1:
        raise ValueError(f"n must be >= 1, got {spec.n}")
    if spec.batch < 1:
        raise ValueError(f"batch must be >= 1, got {spec.batch}")
    if spec.tn is not None and spec.tn < 1:
        raise ValueError(f"tn must be >= 1, got {spec.tn}")
    if spec.gather and spec.op not in GATHER_OPS:
        raise ValueError(
            f"gather-fused loads exist for {GATHER_OPS} only, got "
            f"op={spec.op!r}")
    if plan.is_global and spec.op == "blockrow":
        raise ValueError(
            f"FLASHBLOCKROW is a blockperm-wiring construction; family "
            f"{plan.family!r} has no blockrow formulation")
    if spec.shard != "none":
        if spec.devices < 1:
            raise ValueError(f"devices must be >= 1, got {spec.devices}")
        if spec.shard == "row":
            if plan.is_global:
                raise ValueError(
                    f"row-sharding has no compact partial for global "
                    f"family {plan.family!r}: every input block feeds "
                    f"every output block, so a per-device block slab "
                    f"still touches the full output (shard the column "
                    f"or batch axis instead)")
            if spec.op == "transpose":
                raise ValueError(
                    "row-sharding has no partial transpose formulation")
            if spec.gather:
                raise ValueError(
                    "row-sharding does not compose with the fused gather "
                    "(shard the batch axis instead — see "
                    "distributed.sketch_apply_batched_sharded)")
            if spec.impl == "pallas_v1":
                raise ValueError(
                    "pallas_v1 has no partial formulation; row-sharded "
                    "impl must be 'auto', 'pallas' or 'xla'")
            if plan.M % spec.devices != 0:
                raise ValueError(
                    f"row-sharding needs the shard count to divide the "
                    f"block grid: P={spec.devices} does not divide "
                    f"M={plan.M} (rebuild the plan with block_rows= so "
                    f"that P | M)")
        elif spec.shard == "col" and spec.n % spec.devices != 0:
            raise ValueError(
                f"column sharding needs P | n: P={spec.devices}, "
                f"n={spec.n}")
        elif spec.shard == "batch" and spec.batch % spec.devices != 0:
            raise ValueError(
                f"batch sharding needs P | B: P={spec.devices}, "
                f"B={spec.batch}")


def _lower(plan: BlockPermPlan, spec: LaunchSpec,
           trace: Optional[List[str]]) -> Lowering:
    def t(line: str) -> None:
        if trace is not None:
            trace.append(line)

    _validate(plan, spec)
    eff = plan
    if spec.dtype is not None and spec.dtype != plan.dtype:
        eff = plan.with_dtype(spec.dtype)
        t(f"dtype: plan {plan.dtype!r} overridden -> {eff.dtype!r}")
    t(f"plan: {eff.describe()}")

    impl_req = spec.impl
    impl = impl_req
    on_tpu = jax.default_backend() == "tpu"
    if impl == "auto":
        impl = "pallas" if on_tpu else "xla"
        t(f"impl: 'auto' -> {impl!r} (backend={jax.default_backend()!r})")
    else:
        t(f"impl: {impl!r} requested")

    # per-device workload under sharding
    n_loc, batch_loc = spec.n, spec.batch
    if spec.shard == "col":
        n_loc = spec.n // spec.devices
        t(f"shard=col x{spec.devices}: per-device columns n_loc={n_loc}")
    elif spec.shard == "batch":
        batch_loc = spec.batch // spec.devices
        t(f"shard=batch x{spec.devices}: per-device fold "
          f"batch_loc={batch_loc}")
    elif spec.shard == "row":
        t(f"shard=row x{spec.devices}: per-device block slab "
          f"M_loc={eff.M // spec.devices} of M={eff.M}")
    n_eff = n_loc * batch_loc

    downgrade: Optional[str] = None
    gather_fused = False
    stationary = False
    tn: Optional[int] = spec.tn
    tn_source = "explicit" if spec.tn is not None else "n/a"
    vmem: Optional[int] = None
    pad_rows = 0

    if spec.shard == "row":
        # the psum'd-partials path: compact per-ℓ partial kernel (pallas)
        # or the jnp oracle partial (xla) — mirror of ops dispatch so
        # sharded and single-device runs use the same backend family.
        if impl == "pallas":
            tclass = "blockrow" if spec.op == "blockrow" else "fwd"
            if tn is None:
                tn, tn_source = tune.resolve(eff, n_eff, tclass, trace=trace)
            # the partial kernel's own (Br, Bc)-Φ working set may exceed
            # the budget even when the resolved class tile fits: shrink
            # the tile through the narrower legal widths first, then fall
            # back to the oracle — there is no v1 partial formulation.
            tn_req = tn
            narrower = [w for w in tune.legal_tns(n_eff) if w < tn]
            while narrower and not partial_fits_vmem(eff, tn):
                t(f"tn={tn} rejected: partial working set "
                  f"{partial_vmem_bytes(eff, tn)} B > VMEM budget "
                  f"{VMEM_BUDGET_BYTES} B")
                tn = narrower.pop()
            if tn != tn_req:
                # the record must not claim the request ran as asked — an
                # explicit/tuned tile that was shrunk is a forced change
                tn_source = f"{tn_source}:vmem_shrunk"
                downgrade = (
                    f"vmem: partial working set over budget at "
                    f"tn={tn_req} — tile shrunk to {tn}")
                t(f"tn: {tn_req} -> {tn} (partial working set over "
                  f"budget; provenance {tn_source!r})")
            if not partial_fits_vmem(eff, tn):
                downgrade = (
                    "vmem: the (Br, Bc) Φ tile alone exceeds the VMEM "
                    "budget at the minimum tile width — no tile can save "
                    "the partial kernel; jnp oracle partial")
                t(f"impl: 'pallas' -> 'xla' ({downgrade})")
                impl, tn, tn_source = "xla", None, "n/a"
            else:
                vmem = partial_vmem_bytes(eff, tn)
        grid_cols = (None if tn is None else -(-n_eff // tn))
        if downgrade:
            health_report.record("lowering.downgrade", detail=downgrade)
        return Lowering(
            plan=eff, op=spec.op, impl=impl, impl_requested=impl_req,
            downgrade=downgrade, tn=tn, tn_source=tn_source,
            dtype=eff.dtype, gather=False, gather_fused=False,
            batch=spec.batch, shard="row", devices=spec.devices,
            n=spec.n, n_loc=n_loc, batch_loc=batch_loc, n_eff=n_eff,
            grid_cols=grid_cols, vmem_bytes=vmem, pad_rows=0, pad_cols=0,
            interpret=impl == "pallas" and not on_tpu)

    if impl in _PALLAS_IMPLS:
        variant = spec.op + ("_gather" if spec.gather else "")
        if spec.gather:
            if impl == "pallas_v1":
                downgrade = (
                    "gather: pallas_v1 has no fused gather formulation — "
                    "the row gather is materialized, then the v1 kernel "
                    "runs on A[row_index]")
                t(f"gather: materialized ({downgrade})")
            elif not tune.fused_fits_vmem(eff, n_eff, variant):
                downgrade = (
                    f"vmem: the {variant!r} gather working set exceeds "
                    f"the budget at the minimum tile — gather "
                    f"materialized, then the regular dispatch runs on "
                    f"A[row_index]")
                t(f"gather: materialized ({downgrade})")
            else:
                gather_fused = True
                t("gather: fused in-kernel (row DMA from HBM)")
        if not gather_fused:
            variant = spec.op
            if impl == "pallas" and not tune.fused_fits_vmem(
                    eff, n_eff, variant):
                reason = (
                    f"vmem: stacked Φ (Br, κ·Bc) + pipelined blocks of "
                    f"{variant!r} exceed the budget at the minimum tile — "
                    f"v1 revisiting kernel")
                downgrade = (downgrade + "; " + reason) if downgrade \
                    else reason
                t(f"impl: 'pallas' -> 'pallas_v1' ({reason})")
                impl = "pallas_v1"
        if impl == "pallas" and spec.op == "fwd" and not gather_fused:
            stationary = stationary_fits(eff, n_eff)
            if stationary:
                variant = STATIONARY_VARIANT
                t("fwd: input-stationary kernel (permutation wiring; "
                  "slots, output and stacked Φ fit at the minimum tile)")
            else:
                t("fwd: output-stationary kernel (global wiring, B_r not "
                  "whole sublane tiles, or the input-stationary slots do "
                  "not fit at the minimum tile)")

        if tn is None:
            if impl == "pallas_v1":
                tn = tune.v1_default_tn(eff, n_eff)
                tn_source = "v1_default"
                t(f"tn: {tn} (v1 default — narrowest legal tile)")
            else:
                tn, tn_source = tune.resolve(eff, n_loc, variant, batch_loc,
                                             trace=trace)
        else:
            t(f"tn: {tn} (explicit)")

        if impl == "pallas_v1":
            vmem = v1_working_set_bytes(eff, tn, spec.op)
        else:
            vmem = tune._vmem_footprint(eff, tn, variant)
        if not gather_fused:
            if spec.op == "transpose":
                pad_rows = 0                      # plan.k == plan.k_pad
            else:
                pad_rows = eff.d_pad - eff.d
        t(f"pad: rows +{pad_rows}, cols +0 (ragged column tail handled "
          f"in-kernel — the operand is never column-padded in HBM)")
        grid_cols = -(-n_eff // tn)
    else:
        assert impl == "xla", impl
        t("xla: pure-jnp oracle (no tiling, no VMEM)")
        tn, tn_source = None, "n/a"
        grid_cols = None

    if downgrade:
        # downgrades are health events: a request that could not run as
        # asked.  The counter makes forced rungs visible process-wide
        # (explain(), the fault-injection suite, long-running jobs).
        health_report.record("lowering.downgrade", detail=downgrade)
    return Lowering(
        plan=eff, op=spec.op, impl=impl, impl_requested=impl_req,
        downgrade=downgrade, tn=tn, tn_source=tn_source, dtype=eff.dtype,
        gather=spec.gather, gather_fused=gather_fused, batch=spec.batch,
        shard=spec.shard, devices=spec.devices if spec.shard != "none" else 1,
        n=spec.n, n_loc=n_loc, batch_loc=batch_loc, n_eff=n_eff,
        grid_cols=grid_cols, vmem_bytes=vmem, pad_rows=pad_rows, pad_cols=0,
        interpret=impl in _PALLAS_IMPLS and not on_tpu, stationary=stationary)


_LOWERING_CACHE: Dict[Tuple, Lowering] = {}
# tuner-cache generation the memoized records were resolved against; a
# mismatch flushes the whole dict (the counter is monotone, so records
# from older generations can never be valid again — keeping them keyed
# by generation would only leak dead entries per tuner mutation).
_CACHE_GEN: int = -1
# Serializes the generation-check → flush → get/insert sequence: serving
# workers lower concurrently, and an unguarded flush racing an insert can
# resurrect a stale-tile record or die iterating a resizing dict.
_MEMO_LOCK = threading.RLock()
# Compiled executors of concrete ``execute`` calls, keyed by (record,
# operand shape/dtype, row-index shape/dtype).  The record is in the key,
# so an entry never serves another tile; flushed with the memo above.
_EXEC_MEMO: Dict[Tuple, Callable] = {}


@scoped("repro.lowering.lower")
def lower(plan: BlockPermPlan, spec: LaunchSpec) -> Lowering:
    """Resolve a launch request into a frozen ``Lowering`` record.

    Pure trace-time python (no jax ops) — safe to call while tracing, like
    ``tune.resolve_tn``.  Memoized process-wide, keyed like the tuner
    cache (plan carries the shape class; plus the spec and backend tag);
    a freshly tuned/loaded winner bumps ``tune.cache_generation()``,
    which flushes the memo wholesale so stale tiles are never served.
    """
    global _CACHE_GEN
    with _MEMO_LOCK:
        gen = tune.cache_generation()
        if gen != _CACHE_GEN:
            _LOWERING_CACHE.clear()
            _EXEC_MEMO.clear()
            _CACHE_GEN = gen
        hit = _LOWERING_CACHE.get((plan, spec, tune._backend_tag()))
        health_report.record("lowering.memo_hit" if hit is not None
                             else "lowering.memo_miss")
    if hit is not None:
        return hit
    hit = _lower(plan, spec, None)      # pure; safe outside the lock
    if hit.stationary:
        health_report.record("lowering.fwd_input_stationary")
    with _MEMO_LOCK:
        # only memoize against the generation we resolved under — if the
        # tuner mutated mid-resolve, serve the result but do not cache it
        if tune.cache_generation() == gen and _CACHE_GEN == gen:
            _LOWERING_CACHE[(plan, spec, tune._backend_tag())] = hit
    return hit


def clear_lowering_cache() -> None:
    """Empty the lowering memo and the compiled executors built from it."""
    with _MEMO_LOCK:
        _LOWERING_CACHE.clear()
        _EXEC_MEMO.clear()


def lowering_cache_size() -> int:
    with _MEMO_LOCK:
        return len(_LOWERING_CACHE)


def executor_cache_size() -> int:
    """Number of compiled executors memoized by concrete ``execute`` calls."""
    with _MEMO_LOCK:
        return len(_EXEC_MEMO)


def explain(plan: BlockPermPlan, spec: Optional[LaunchSpec] = None,
            **spec_kwargs) -> str:
    """Human-readable decision trace of one lowering.

    Pass a ``LaunchSpec`` or its keyword fields::

        print(lowering.explain(plan, n=512, dtype="bfloat16"))

    The trace lists the dtype/impl resolution, every rejected tile
    candidate (with its VMEM footprint), any downgrade and its reason, the
    padding plan, and the final record — plus the process-wide counters
    (``repro.health.report``: guard and recovery events, lowering- and
    executor-memo hits and misses), so one explain shows both how the
    launch resolves and what the process has recorded so far.
    """
    if spec is None:
        spec = LaunchSpec(**spec_kwargs)
    elif spec_kwargs:
        spec = dataclasses.replace(spec, **spec_kwargs)
    trace: List[str] = []
    lw = _lower(plan, spec, trace)
    head = (f"lower(op={spec.op!r}, n={spec.n}, impl={spec.impl!r}, "
            f"tn={spec.tn}, dtype={spec.dtype!r}, gather={spec.gather}, "
            f"batch={spec.batch}, shard={spec.shard!r}x{spec.devices})")
    lines = [head] + ["  " + ln for ln in trace] + ["=> " + lw.describe()]
    lines.append("counters: " + health_report.summarize_counters())
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# execute(): run a single-device lowering.
# ---------------------------------------------------------------------------

def _emulate_stream(plan: BlockPermPlan, A: jnp.ndarray) -> jnp.ndarray:
    """Round through the streaming precision so the XLA oracle / fp32 v1
    kernels see the same input quantization the Pallas v2 path streams
    from HBM (including the seeded stochastic rounding of the ``*_sr``
    policies — value-keyed, so it matches the kernel cast bit-for-bit)."""
    if plan.dtype == "float32":
        return A
    return precision_mod.emulate_stream(A, plan.precision, seed=plan.seed)


def row_map_for(plan: BlockPermPlan, row_index: jnp.ndarray) -> jnp.ndarray:
    """(d_pad,) int32 source-row map.  Padding entries point at row 0 — a
    placeholder valid source; the gather kernel zeroes the corresponding
    scratch rows itself (rows ≥ ``plan.d``), so A is never copied just to
    host a zero row and padding still contributes exact zeros."""
    ri = jnp.asarray(row_index, jnp.int32).reshape(-1)
    pad = plan.d_pad - ri.shape[0]
    if pad == 0:
        return ri
    return jnp.concatenate([ri, jnp.zeros((pad,), jnp.int32)])


_ORACLES = {
    "fwd": kref.flashsketch_ref,
    "transpose": kref.flashsketch_transpose_ref,
    "blockrow": kref.blockrow_ref,
}

# keyed by ``Lowering.variant``
_V2_KERNELS = {
    "fwd": fsk.flashsketch_pallas,
    STATIONARY_VARIANT: fsk.flashsketch_pallas_stationary,
    "transpose": fsk.flashsketch_transpose_pallas,
    "blockrow": fsk.blockrow_pallas,
}

_V1_KERNELS = {
    "fwd": fsk.flashsketch_pallas_v1,
    "transpose": fsk.flashsketch_transpose_pallas_v1,
    "blockrow": fsk.blockrow_pallas_v1,
}

_GATHER_KERNELS = {
    "fwd": fsk.flashsketch_pallas_gather,
    "blockrow": fsk.blockrow_pallas_gather,
}


def _kernel_name(lw: Lowering) -> str:
    """The ``pallas_call`` name of the kernel a single-device Pallas
    record launches (its span is ``repro.kernel.<name>``)."""
    name = {"fwd": "flashsketch_fwd", "transpose": "flashsketch_transpose",
            "blockrow": "flashsketch_blockrow"}[lw.op]
    if lw.gather_fused:
        return name + "_gather"
    return name + "_v1" if lw.impl == "pallas_v1" else name


def _aval_key(x) -> Optional[Tuple]:
    return None if x is None else (x.shape, x.dtype)


@scoped("repro.lowering.execute")
def execute(lw: Lowering, operand: jnp.ndarray,
            row_index: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Run a single-device ``Lowering`` on its operand.

    Concrete Pallas calls run a compiled executor memoized per record and
    operand shape/dtype (module docstring); traced calls and the ``xla``
    oracle run the launch body inline.

    Args:
      lw: the record from ``lower`` (must have ``shard == "none"`` — the
        sharded layouts are executed by ``repro.distributed`` inside
        ``shard_map``, from the same record).
      operand: ``(d, n)`` for ``fwd``/``blockrow`` (``(d_src, n)`` with a
        gather), ``(k, n)`` for ``transpose``.
      row_index: ``(plan.d,)`` int rows when ``lw.gather`` — required then,
        forbidden otherwise.

    Returns:
      ``(k, n)`` fp32 for the forwards, ``(d, n)`` for the transpose.
    """
    if lw.shard != "none":
        raise ValueError(
            f"execute() runs single-device lowerings; shard={lw.shard!r} "
            f"records are executed by repro.distributed inside shard_map")
    if lw.impl == "xla" or isinstance(operand, jax.core.Tracer) \
            or isinstance(row_index, jax.core.Tracer):
        return _launch_body(lw, operand, row_index)
    key = (lw, _aval_key(operand), _aval_key(row_index))
    with _MEMO_LOCK:
        exe = _EXEC_MEMO.get(key)
        health_report.record("lowering.exec_memo_hit" if exe is not None
                             else "lowering.exec_memo_miss")
    if exe is None:
        # the first call traces the body (its spans nest here, as in a
        # traced call), compiles it and runs it; memoize once it succeeded
        def sketch_launch(operand, row_index):
            return _launch_body(lw, operand, row_index)
        exe = jax.jit(sketch_launch)
        out = exe(operand, row_index)
        with _MEMO_LOCK:
            _EXEC_MEMO[key] = exe
        return out
    with scope(f"repro.kernel.{_kernel_name(lw)}"):
        return exe(operand, row_index)


def _launch_body(lw: Lowering, operand: jnp.ndarray,
                 row_index: Optional[jnp.ndarray]) -> jnp.ndarray:
    """``execute``'s launch: validation, row map, pad, stream cast,
    kernel and slice — traced into the caller's program or into a memoized
    executor."""
    plan = lw.plan
    if lw.gather:
        if row_index is None:
            raise ValueError("gather lowering requires row_index")
        d_keep = row_index.shape[0]
        if d_keep != plan.d:
            raise ValueError(
                f"row_index has {d_keep} entries but plan.d == {plan.d}; "
                f"build the plan for the masked dim (make_plan(d_keep, k, "
                f"...))")
        if not lw.gather_fused:
            # materialize-then-dispatch fallback (v1 / VMEM overflow / xla)
            operand = operand[jnp.asarray(row_index)]
    elif row_index is not None:
        raise ValueError("row_index passed to a non-gather lowering")

    n = operand.shape[1]
    if lw.impl == "xla":
        return _ORACLES[lw.op](plan, _emulate_stream(plan, operand))

    if lw.gather_fused:
        with scope("repro.lowering.row_map"):
            rmap = row_map_for(plan, row_index)
        Y = _GATHER_KERNELS[lw.op](plan, operand, rmap, tn=lw.tn,
                                   interpret=lw.interpret)
        return Y[: plan.k, :n]

    with scope("repro.lowering.pad"):
        if lw.op == "transpose":
            if operand.shape[0] != plan.k_pad:
                operand = jnp.pad(
                    operand, ((0, plan.k_pad - operand.shape[0]), (0, 0)))
        else:
            operand = kref.pad_input(plan, operand)

    if lw.impl == "pallas_v1":
        # v1 computes in fp32; keep the plan's streaming-precision contract
        # by rounding the input exactly as the bf16 stream would.
        out = _V1_KERNELS[lw.op](plan, _emulate_stream(plan, operand),
                                 tn=lw.tn, interpret=lw.interpret)
    else:
        out = _V2_KERNELS[lw.variant](plan, operand, tn=lw.tn,
                                      interpret=lw.interpret)
    rows = plan.d if lw.op == "transpose" else plan.k
    return out[:rows, :n]
