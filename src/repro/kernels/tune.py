"""Tile autotuner for the FlashSketch v2 kernels.

Two layers:

  * ``resolve(plan, n, variant)`` — the cheap path the lowering engine
    takes whenever the caller passes ``tn=None``.  Pure python: returns
    the cached tuned width for this shape class if it is a legal tile
    (``legal_tns``), else a VMEM-budget heuristic.  Safe to call at trace
    time (no timing, no jit).
  * ``autotune(plan, n, ...)`` / ``autotune_plan(d, k, n, ...)`` — the
    active path: times real kernel launches over a sweep of ``tn`` (and,
    for ``autotune_plan``, the ``M``/``B_r`` split via ``block_rows``),
    then populates the cache so subsequent ``resolve_tn`` calls return the
    measured winner.

Cache entries are keyed by the *shape class* ``(backend, variant, d_pad,
k_pad, M, Br, kappa, s, bucket(n), dtype, gather, bucket(batch))`` — ``n``
is bucketed to its next power of two so nearby column counts share a
winner, and the backend tag ("interpret" off-TPU) keeps interpreter
timings from ever being served as compiled-TPU winners.  ``cache_key`` is
the ONE key builder: every consult (``lookup``/``resolve_tn``) and every
write (``autotune``, ``autotune_plan``) routes through it, including the
batched fields — a write under one spelling of a batched shape is
guaranteed visible to every reader.  The cache is a process-global
dict with optional JSON persistence (``save_cache``/``load_cache``) so
benchmark runs can ship winners to serving jobs; ``cache_generation()``
counts mutations so trace-time consumers (the lowering engine's record
cache) can invalidate when new winners land.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import threading
import time
import warnings
from typing import Dict, Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blockperm import (GATHER_VARIANTS, LANE, SKETCH_VARIANTS,
                                  STATIONARY_VARIANT, BlockPermPlan,
                                  VMEM_BUDGET_BYTES, _next_pow2,
                                  fused_variant_bytes, make_plan)
from repro.kernels import flashsketch as fsk

VARIANTS = SKETCH_VARIANTS + GATHER_VARIANTS + (STATIONARY_VARIANT,)

_MAX_TN = 1024


@dataclasses.dataclass(frozen=True)
class TuneResult:
    tn: int
    block_rows: Optional[int] = None   # set by autotune_plan sweeps
    time_us: float = float("nan")
    source: str = "heuristic"          # "heuristic" | "tuned" | "loaded"


_CACHE: Dict[Tuple, TuneResult] = {}

# Serializes every _CACHE mutation and keeps _bump_generation atomic with
# the mutation it describes: the serving layer tunes/loads/saves from
# worker threads, and an unguarded save_cache iterating _CACHE while
# autotune inserts a winner dies with "dict changed size during
# iteration".  RLock because load_cache(merge=False) calls clear_cache.
_CACHE_LOCK = threading.RLock()

# Bumped on every cache mutation (tuned win, JSON load, clear) so consumers
# that memoize *derived* trace-time decisions — ``kernels.lowering``'s
# record cache — know when a cached decision may have gone stale.
_GENERATION: int = 0


def cache_generation() -> int:
    """Monotone counter of tuner-cache mutations (see module docstring)."""
    return _GENERATION


def _bump_generation() -> None:
    global _GENERATION
    with _CACHE_LOCK:
        _GENERATION += 1


def _n_bucket(n: int) -> int:
    return _next_pow2(max(1, n))


def _is_better(candidate: TuneResult, incumbent: Optional[TuneResult]) -> bool:
    """Timed results beat untimed (NaN) ones; among timed, lower wins."""
    if incumbent is None:
        return True
    if math.isnan(candidate.time_us):
        return False
    if math.isnan(incumbent.time_us):
        return True
    return candidate.time_us < incumbent.time_us


def _backend_tag(interpret: Optional[bool] = None) -> str:
    """Interpret-mode timings say nothing about compiled-TPU behavior, so
    winners tuned on one backend must never be served to the other."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return "interpret" if interpret else jax.default_backend()


def cache_key(plan: BlockPermPlan, n: int, variant: str,
              interpret: Optional[bool] = None, *, batch: int = 1) -> Tuple:
    """Shape-class key.  Beyond the PR-1 fields it carries the gather/batch
    dims of the fused-batched path: whether the kernel does an in-kernel
    row gather (``*_gather`` variants tile differently — no pipelined input
    blocks, one DMA'd gather scratch) and the bucketed batch count folded
    into the column axis (a B-example batched launch has B·n effective
    columns, which moves the tile-width sweet spot)."""
    return (_backend_tag(interpret), variant, plan.family, plan.d_pad,
            plan.k_pad, plan.M, plan.Br, plan.kappa, plan.s, _n_bucket(n),
            plan.dtype, variant in GATHER_VARIANTS, _n_bucket(batch))


def clear_cache() -> None:
    with _CACHE_LOCK:
        _CACHE.clear()
        _bump_generation()


def cache_size() -> int:
    return len(_CACHE)


def _vmem_footprint(plan: BlockPermPlan, tn: int, variant: str) -> int:
    return fused_variant_bytes(plan.kappa, plan.Br, plan.Bc, tn,
                               plan.stream_itemsize, variant,
                               plan.precision.compute_itemsize,
                               gather_mask=plan.d < plan.d_pad, M=plan.M)


def legal_tns(n: int, variant: str = "fwd") -> Tuple[int, ...]:
    """Every tile width the engine may launch at ``n`` effective columns,
    ascending — the ONE tile rule, on every backend.

    The chip's compiler takes a block whose last dimension is a multiple
    of the 128-lane tiling or the array's full extent, so a launch of
    ``n ≤ 128`` columns runs at ``tn = n`` and a wider one at powers of
    two from 128 up to ``_MAX_TN`` (never past the next power of two of
    ``n``).  The gather kernels DMA whole lane tiles of a column-padded
    row stack, so their tiles are always lane multiples."""
    if n <= LANE and variant not in GATHER_VARIANTS:
        return (n,)
    cap = min(_MAX_TN, max(LANE, _n_bucket(n)))
    return tuple(LANE << i for i in range((cap // LANE).bit_length()))


def fused_fits_vmem(plan: BlockPermPlan, n: int, variant: str = "fwd") -> bool:
    """Whether the v2 fused working set (stacked Φ scratch + pipelined
    blocks + temporaries) fits the VMEM budget at the narrowest legal tile
    for ``n`` columns.

    The Φ scratch is (κ, Br, Bc) — independent of ``tn`` — so for very
    large d_pad/M the fused kernel cannot fit no matter how the tuner
    shrinks the tile; dispatch falls back to the v1 revisiting kernel in
    that case.
    """
    return (_vmem_footprint(plan, legal_tns(n, variant)[0], variant)
            <= VMEM_BUDGET_BYTES)


def heuristic_tn(plan: BlockPermPlan, n: int, variant: str = "fwd",
                 batch: int = 1, trace: Optional[list] = None) -> int:
    """Widest legal tile (``legal_tns``) that fits the VMEM budget.

    ``n·batch`` is the effective column count (a batched launch folds the
    batch into the column axis).  When no legal tile fits, the narrowest
    one is returned and ``fused_fits_vmem`` routes the launch to a
    recorded downgrade.  ``trace`` (a list, appended in place) records
    every rejected candidate width for ``lowering.explain``.
    """
    cands = legal_tns(n * max(1, batch), variant)
    for tn in reversed(cands):
        fp = _vmem_footprint(plan, tn, variant)
        if fp <= VMEM_BUDGET_BYTES:
            return tn
        if trace is not None:
            trace.append(
                f"tn={tn} rejected: {variant!r} working set "
                f"{fp} B > VMEM budget {VMEM_BUDGET_BYTES} B")
    return cands[0]


def lookup(plan: BlockPermPlan, n: int, variant: str = "fwd",
           batch: int = 1,
           interpret: Optional[bool] = None) -> Optional[TuneResult]:
    """The ONE cache consult: the tuned/loaded winner for this shape class,
    or ``None``.  Every reader (``resolve_tn``, the lowering engine) and
    every writer (``autotune``/``autotune_plan``) shares ``cache_key``, so
    a batched write is never invisible to a batched read."""
    with _CACHE_LOCK:
        return _CACHE.get(cache_key(plan, n, variant, interpret, batch=batch))


def resolve(plan: BlockPermPlan, n: int, variant: str = "fwd",
            batch: int = 1, trace: Optional[list] = None) -> Tuple[int, str]:
    """``(tn, source)`` for a launch that did not pin its tile: the tuned or
    loaded winner of the shape class when it is a legal tile for this
    exact column count (a class spans a bucket of ``n``) and its working
    set fits the VMEM budget (a loaded cache may come from another
    budget or be damaged), else the VMEM heuristic.  Pure python, safe at
    trace time; ``trace`` collects the reasoning for
    ``lowering.explain``."""
    hit = lookup(plan, n, variant, batch=batch)
    if hit is not None:
        fp = _vmem_footprint(plan, hit.tn, variant)
        if hit.tn not in legal_tns(n * max(1, batch), variant):
            why = f"is not a legal tile at {n * max(1, batch)} columns"
        elif fp > VMEM_BUDGET_BYTES:
            why = (f"needs {fp} B of VMEM > budget {VMEM_BUDGET_BYTES} B")
        else:
            if trace is not None:
                trace.append(f"tn: {hit.tn} ({hit.source} winner, class "
                             f"{variant!r}, batch={batch})")
            return hit.tn, hit.source
        if trace is not None:
            trace.append(f"tn: {hit.source} winner {hit.tn} {why}")
    tn = heuristic_tn(plan, n, variant, batch, trace=trace)
    if trace is not None:
        trace.append(f"tn: {tn} (heuristic, class {variant!r}, "
                     f"batch={batch})")
    return tn, "heuristic"


def resolve_tn(plan: BlockPermPlan, n: int, variant: str = "fwd",
               batch: int = 1) -> int:
    """The tile ``resolve`` picks (the engine's choice, no timing)."""
    return resolve(plan, n, variant, batch)[0]


def v1_default_tn(plan: BlockPermPlan, n: int) -> int:
    """Tile width for the v1 revisiting kernel (always fp32): the narrowest
    legal tile, since the plans that take the v2→v1 rung are the ones
    whose Φ alone nearly fills the budget."""
    return legal_tns(n)[0]


# ---------------------------------------------------------------------------
# Active tuning
# ---------------------------------------------------------------------------

def _with_identity_row_map(kernel):
    """Adapt a gather kernel to the (plan, X, tn, interpret) timing shape:
    tuning uses the identity row map (the gather cost is index-independent;
    only the DMA count and tile shapes matter)."""
    def run(plan, X, *, tn, interpret=None):
        rmap = jnp.arange(plan.d_pad, dtype=jnp.int32)
        return kernel(plan, X, rmap, tn=tn, interpret=interpret)
    return run


_KERNELS = {
    "fwd": fsk.flashsketch_pallas,
    "transpose": fsk.flashsketch_transpose_pallas,
    "blockrow": fsk.blockrow_pallas,
    STATIONARY_VARIANT: fsk.flashsketch_pallas_stationary,
    "fwd_gather": _with_identity_row_map(fsk.flashsketch_pallas_gather),
    "blockrow_gather": _with_identity_row_map(fsk.blockrow_pallas_gather),
}


def _candidate_tns(plan: BlockPermPlan, n: int, variant: str,
                   batch: int = 1) -> Tuple[int, ...]:
    """The legal tiles whose working set fits the budget (the narrowest
    legal tile when none does)."""
    cands = legal_tns(n * max(1, batch), variant)
    fits = tuple(tn for tn in cands
                 if _vmem_footprint(plan, tn, variant) <= VMEM_BUDGET_BYTES)
    return fits or cands[:1]


def _time_call(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall-time in microseconds of a blocking call."""
    for _ in range(warmup):
        fn(*args).block_until_ready()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        times.append(time.perf_counter() - t0)
    return 1e6 * float(np.median(times))


def _make_operand(plan: BlockPermPlan, n_pad: int, variant: str) -> jnp.ndarray:
    rows = plan.k_pad if variant == "transpose" else plan.d_pad
    # Deterministic pseudo-data: tuning only measures time, not quality.
    x = np.linspace(-1.0, 1.0, num=rows * n_pad, dtype=np.float32)
    return jnp.asarray(x.reshape(rows, n_pad))


def autotune(
    plan: BlockPermPlan,
    n: int,
    variant: str = "fwd",
    *,
    batch: int = 1,
    tns: Optional[Sequence[int]] = None,
    warmup: int = 1,
    iters: int = 3,
    interpret: Optional[bool] = None,
) -> TuneResult:
    """Time the v2 kernel over a ``tn`` sweep and cache the winner.

    ``batch`` is the batched-apply fold factor: a B-stack sketched in one
    launch runs on ``B·n`` effective columns, so it is timed (and keyed)
    that way rather than at the per-matrix width.
    """
    if variant not in VARIANTS:
        raise ValueError(f"variant must be one of {VARIANTS}, got {variant!r}")
    key = cache_key(plan, n, variant, interpret, batch=batch)
    with _CACHE_LOCK:
        hit = _CACHE.get(key)
    if hit is not None and hit.source in ("tuned", "loaded"):
        return hit
    kernel = _KERNELS[variant]
    n_eff = n * max(1, batch)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    best: Optional[TuneResult] = None
    for tn in (tns or _candidate_tns(plan, n, variant, batch)):
        n_pad = ((n_eff + tn - 1) // tn) * tn
        operand = _make_operand(plan, n_pad, variant)
        fn = jax.jit(lambda x, _tn=tn: kernel(plan, x, tn=_tn, interpret=interpret))
        # every candidate is a legal tile that fits the budget, so a
        # failure is a kernel or model bug: it propagates instead of
        # silently narrowing the sweep
        us = _time_call(fn, operand, warmup=warmup, iters=iters)
        cand = TuneResult(tn=tn, time_us=us, source="tuned")
        if _is_better(cand, best):
            best = cand
    with _CACHE_LOCK:
        _CACHE[key] = best
        _bump_generation()
    return best


def autotune_plan(
    d: int,
    k: int,
    n: int,
    *,
    kappa: int = 4,
    s: int = 2,
    seed: int = 0,
    dtype: str = "float32",
    variant: str = "fwd",
    batch: int = 1,
    block_rows_candidates: Optional[Iterable[int]] = None,
    tns: Optional[Sequence[int]] = None,
    warmup: int = 1,
    iters: int = 3,
) -> Tuple[BlockPermPlan, TuneResult]:
    """Sweep the ``M``/``B_r`` split *and* ``tn``; return the fastest pair.

    The ``B_r`` sweep changes the padded shapes, so the returned plan must be
    used in place of a ``make_plan`` default for the win to apply.

    Only candidates with the SAME effective ``k_pad`` as the default plan
    are timed: a pin that inflates ``k_pad`` (e.g. ``B_r·2`` when M is
    already at the κ floor) would sketch a different statistical object —
    more rows, different embedding — and raw launch time cannot rank it
    against the requested-size plans.  Such candidates are skipped, as are
    duplicates of an already-timed effective ``(M, B_r)`` grid.

    ``batch`` is the batched-apply fold factor, forwarded to ``autotune``
    and — crucially — to the winner's ``cache_key``, so a batched sweep's
    winner is served back to batched ``resolve_tn``/``lookup`` consults
    (one key builder for writers and readers; regression-tested).
    """
    base = make_plan(d, k, kappa=kappa, s=s, seed=seed, dtype=dtype)
    if block_rows_candidates is None:
        block_rows_candidates = sorted(
            {br for br in (base.Br // 2, base.Br, base.Br * 2)
             if br >= max(s, 1) and br % max(s, 1) == 0}
        )
    best_plan: Optional[BlockPermPlan] = None
    best: Optional[TuneResult] = None
    seen_grids: set = set()
    for br in block_rows_candidates:
        try:
            plan = make_plan(d, k, kappa=kappa, s=s, seed=seed,
                             block_rows=br, dtype=dtype)
        except ValueError:
            continue
        # Dedupe by the EFFECTIVE grid: two pins that resolve to the same
        # (M, Br) would time the identical kernel twice.  Skip candidates
        # whose k_pad differs from the default plan's — not comparable.
        if plan.k_pad != base.k_pad or (plan.M, plan.Br) in seen_grids:
            continue
        seen_grids.add((plan.M, plan.Br))
        res = autotune(plan, n, variant, batch=batch, tns=tns, warmup=warmup,
                       iters=iters)
        if _is_better(res, best):
            best_plan, best = plan, dataclasses.replace(res, block_rows=plan.Br)
    if best_plan is None or best is None:
        best_plan = make_plan(d, k, kappa=kappa, s=s, seed=seed, dtype=dtype)
        best = TuneResult(tn=resolve_tn(best_plan, n, variant, batch),
                          block_rows=best_plan.Br, source="heuristic")
    # the winner's key MUST be built by the same cache_key spelling that
    # resolve_tn/lookup consult — including the batched fields (a batched
    # sweep cached under a batch-less key would never be served again)
    with _CACHE_LOCK:
        _CACHE[cache_key(best_plan, n, variant, batch=batch)] = best
        _bump_generation()
    return best_plan, best


# ---------------------------------------------------------------------------
# Persistence (JSON; keys serialized as strings)
# ---------------------------------------------------------------------------

def save_cache(path: str) -> int:
    """Persist the cache to ``path`` ATOMICALLY (tmp file + rename).

    A crash mid-write must never leave a half-written JSON where the next
    process expects a cache — ``os.replace`` makes the new file appear all
    at once (same-filesystem rename is atomic on POSIX), so readers only
    ever see the old complete file or the new complete file.
    """
    def _row(v: TuneResult) -> Dict:
        d = dataclasses.asdict(v)
        # NaN is not valid JSON — untimed entries serialize time_us as null.
        if not math.isfinite(v.time_us):
            d["time_us"] = None
        return d

    with _CACHE_LOCK:       # snapshot: a concurrent tuned win must not
        snap = list(_CACHE.items())   # resize the dict mid-iteration
    payload = {json.dumps(list(k)): _row(v) for k, v in snap}
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True, allow_nan=False)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return len(payload)


def load_cache(path: str, *, merge: bool = True) -> int:
    """Load tuned winners from ``path``; returns the number of entries kept.

    Hardened against corruption: a truncated / garbage / malformed cache
    file warns and falls back to the heuristic (returns 0 or skips the bad
    rows) instead of raising — a stale or damaged cache must never take
    down a job whose correctness does not depend on it (the tile heuristic
    is always available).  Every skipped file/row is counted under
    ``tune.cache_corrupt`` in the health registry.
    """
    from repro.health import report as health_report
    try:
        with open(path) as f:
            payload = json.load(f)
        if not isinstance(payload, dict):
            raise ValueError(f"expected a JSON object, got "
                             f"{type(payload).__name__}")
    except (json.JSONDecodeError, ValueError, OSError, UnicodeDecodeError) as e:
        health_report.record("tune.cache_corrupt", detail=f"{path}: {e}")
        warnings.warn(
            f"tuner cache {path!r} is unreadable ({e}); ignoring it — tile "
            f"selection falls back to the VMEM heuristic", RuntimeWarning,
            stacklevel=2)
        return 0
    kept = 0
    bad = 0
    with _CACHE_LOCK:   # replace-or-merge lands atomically w.r.t. readers
        if not merge:
            clear_cache()
        for ks, vd in payload.items():
            try:
                key = tuple(json.loads(ks))
                t = vd.get("time_us")
                row = TuneResult(
                    tn=int(vd["tn"]),
                    block_rows=vd.get("block_rows"),
                    time_us=float(t) if t is not None else float("nan"),
                    source="loaded",
                )
            except (json.JSONDecodeError, ValueError, TypeError, KeyError,
                    AttributeError) as e:
                bad += 1
                health_report.record("tune.cache_corrupt",
                                     detail=f"{path} entry {ks!r}: {e}")
                continue
            _CACHE[key] = row
            kept += 1
        if kept:
            _bump_generation()
    if bad:
        warnings.warn(
            f"tuner cache {path!r}: skipped {bad} malformed entr"
            f"{'y' if bad == 1 else 'ies'} (kept {kept})", RuntimeWarning,
            stacklevel=2)
    return kept
