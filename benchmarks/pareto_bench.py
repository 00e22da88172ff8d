"""Pareto tournament: every registered sketch family, quality × speed.

    PYTHONPATH=src python -m benchmarks.pareto_bench             # full grid
    PYTHONPATH=src python -m benchmarks.pareto_bench --tiny      # CI smoke

The paper's headline claim is positional: BlockPerm-SJLT sits ON the
quality-vs-speed Pareto frontier of sparse sketching — faster than anything
of equal quality, better than anything of equal speed.  That claim is only
falsifiable against strong competitors, so this bench scores EVERY family
in ``repro.core.variants.SKETCH_FAMILIES`` (including the fused CountSketch
of Higgins & Boman arXiv:2508.14209 and the sparse-graph sketch of Hu et
al. arXiv:2102.05758, both lowered through the same engine) on four axes,
all lower-is-better:

  quality:
    * ``ose_err``    — OSE distortion ‖UᵀSᵀSU − I‖₂ on U = orth(A)
                       (the PR 6 ``ose_probe`` statistic, family-generic),
                       averaged over ``--trials`` independent draws so the
                       axis measures the FAMILY, not one lucky seed;
    * ``lsqr_iters`` — preconditioned-LSQR iterations to tol on a
                       controlled-cond consistent system (the
                       ``randnla_bench`` solver protocol).
  speed:
    * ``modeled_us`` — idealized TPU time from the family's ``cost_model``
                       (for engine families: the roofline of the Lowering
                       record that would actually launch);
    * ``measured_us``— wall-clock of the jitted apply on THIS host
                       (interpret/XLA off-TPU — real, but a CPU number).

Per regime (a (d, n, k, dataset) point) the bench reports the 4-axis
Pareto front.  The TOURNAMENT GATE is narrower and deliberately robust:
it replays the paper's own figure axes — mean OSE distortion × modeled
TPU time — and fails (non-zero exit) iff some non-kin family strictly
dominates ``blockperm`` there with a ≥``MARGIN`` relative win on the
strict axis, in a regime the paper claims (``claimed: true``).  Claimed
regimes use k large enough that a global family's plan has M ≥ κ row
blocks — the paper's setting, where CountSketch-style sketches pay M
full streams of A against BlockPerm's κ.  The CPU ``measured_us`` axis
and the (noisy, integer-quantized) iteration axis stay out of the gate:
they are evidence, not the claim.  BlockPerm's own ablations
(``blockperm_bf16``, ``localized``) are kin, not competitors — they
never count as dominators.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List

import jax

jax.config.update("jax_enable_x64", True)   # solver iterations in f64

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import make_dataset, time_fn, modeled_tpu_us  # noqa: E402
from benchmarks.randnla_bench import make_ls_problem  # noqa: E402
from repro.core import coherence  # noqa: E402
from repro.core.variants import SKETCH_FAMILIES, make_sketch  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.solvers import lsqr  # noqa: E402
from repro.compile_cache import enable_compile_cache

TOL = 1e-6

# A >= 5% relative win on the strict axis is required to call a family
# DOMINATED in the gate — differences inside the band are draw noise, not
# a Pareto ordering.
MARGIN = 0.05

# One entry per REGISTERED family — adding a family to SKETCH_FAMILIES and
# not here is a hard error (the tournament must stay exhaustive).
FAMILY_KWARGS = {
    "dense_gaussian": {},
    "dense_rademacher": {},
    "sjlt": {"s": 8},
    "srht": {},
    "blockperm": {"kappa": 4, "s": 2},
    "blockperm_bf16": {"kappa": 4, "s": 2},
    "blockperm_fp8": {"kappa": 4, "s": 2},
    "localized": {"s": 2},
    "blockrow": {"kappa": 4, "s": 2},
    "countsketch": {},
    "graph": {},
}

# BlockPerm's own ablation/precision variants — never counted as dominators
# of "blockperm" (beating yourself is not losing the tournament).
BLOCKPERM_KIN = ("blockperm", "blockperm_bf16", "blockperm_fp8",
                 "localized")

# The four reported axes (ALL lower-is-better) and the subset the gate
# replays (the paper's figure axes).
AXES = ("ose_err", "lsqr_iters", "modeled_us", "measured_us")
GATE_AXES = ("ose_err", "modeled_us")


def regimes(tiny: bool) -> List[Dict]:
    """(d, n, k, dataset) grid; ``claimed`` marks the regimes the paper's
    Pareto figure covers — tall operands with k large enough that global
    families split into M >= κ row blocks (k >= κ·256 under the default
    block cap).  The small-k and sparse regimes are reported but
    unclaimed: at M < κ a global sketch genuinely streams A fewer times
    than BlockPerm, and a near-empty operand rewards scan baselines."""
    if tiny:
        return [
            dict(name="tiny_claimed", d=2048, n=64, k=1024,
                 dataset="gaussian", cond=1e3, claimed=True),
            dict(name="tiny_smallk", d=1024, n=32, k=128,
                 dataset="gaussian", cond=1e3, claimed=False),
        ]
    return [
        dict(name="tall_gaussian", d=4096, n=64, k=1024,
             dataset="gaussian", cond=1e4, claimed=True),
        dict(name="tall_lowrank", d=4096, n=96, k=1024,
             dataset="lowrank_noise", cond=1e4, claimed=True),
        dict(name="llm_weights", d=8192, n=128, k=1024,
             dataset="llm_weights", cond=1e4, claimed=True),
        dict(name="smallk_gaussian", d=4096, n=64, k=256,
             dataset="gaussian", cond=1e4, claimed=False),
        dict(name="sparse", d=4096, n=64, k=1024,
             dataset="sparse", cond=1e4, claimed=False),
    ]


def score_family(name: str, kwargs: Dict, reg: Dict, *, seed: int,
                 trials: int, timing_iters: int, max_iters: int) -> Dict:
    """One family × one regime -> the 4-axis score row."""
    d, n, k = reg["d"], reg["n"], reg["k"]
    # independent draws: one sketch per trial seed (trial 0 also serves the
    # solver and timing axes — those are far less draw-sensitive).
    sketches = [make_sketch(name, d, k, seed=seed + 1000 * t, **kwargs)
                for t in range(trials)]
    sk = sketches[0]

    # quality axis 1: mean OSE distortion on U = orth(dataset operand).
    A_data = make_dataset(reg["dataset"], d, n, seed=seed)
    U, _ = np.linalg.qr(A_data)
    Uj = jnp.asarray(U, jnp.float32)
    ose_draws = [coherence.ose_spectral_error(
        U, np.asarray(s.apply(Uj), np.float64)) for s in sketches]
    ose_err = float(np.mean(ose_draws))

    # quality axis 2: preconditioned-LSQR iterations on a controlled-cond
    # CONSISTENT system (randnla_bench protocol, family-parametric R).
    A_np, b_np, _ = make_ls_problem(d, n, reg["cond"], seed=seed)
    A, b = jnp.asarray(A_np), jnp.asarray(b_np)
    SA = sk.apply(A.astype(jnp.float32))
    R = kops.triangular_factor(SA.astype(jnp.float32), "qr")
    res = lsqr(A, b, R=R.astype(b.dtype), tol=TOL, max_iters=max_iters)
    lsqr_iters = res.iterations if res.converged else max_iters

    # speed axes: modeled TPU roofline + measured host wall-clock.
    modeled_us = modeled_tpu_us(sk, n)
    Aj = jnp.asarray(A_data)
    apply_jit = jax.jit(lambda X: sk.apply(X))
    measured_us = 1e6 * time_fn(apply_jit, Aj, iters=timing_iters)

    return dict(
        family=name, params=json.dumps(kwargs, sort_keys=True),
        regime=reg["name"], d=d, n=n, k=sk.k,
        ose_err=ose_err, ose_draws=[float(x) for x in ose_draws],
        lsqr_iters=int(lsqr_iters),
        lsqr_converged=bool(res.converged), lsqr_relres=float(res.relres),
        modeled_us=float(modeled_us), measured_us=float(measured_us),
    )


def dominates(x: Dict, y: Dict, axes=AXES, margin: float = 0.0) -> bool:
    """x beats-or-ties y on every axis AND strictly beats it on >= 1
    (by a relative ``margin`` on the strict axis when given)."""
    return (all(x[a] <= y[a] for a in axes)
            and any(x[a] < (1.0 - margin) * y[a] for a in axes))


def pareto_front(rows: List[Dict], axes=AXES) -> List[str]:
    """Families not dominated by ANY other row of the regime."""
    return sorted(r["family"] for r in rows
                  if not any(dominates(o, r, axes) for o in rows
                             if o is not r))


def gate_dominators(target: str, rows: List[Dict]) -> List[str]:
    """Non-kin families that strictly dominate ``target`` on the GATE
    axes with the robustness margin."""
    tgt = next(r for r in rows if r["family"] == target)
    return sorted(r["family"] for r in rows
                  if r["family"] not in BLOCKPERM_KIN
                  and dominates(r, tgt, GATE_AXES, MARGIN))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke grid (small shapes, 1 timing rep)")
    ap.add_argument("--out", default="BENCH_pareto.json")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trials", type=int, default=None,
                    help="independent OSE draws per row (default 3 tiny/5)")
    ap.add_argument("--iters", type=int, default=None,
                    help="timing repetitions per row (default 1 tiny / 3)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    missing = sorted(set(SKETCH_FAMILIES) - set(FAMILY_KWARGS))
    if missing:
        raise SystemExit(
            f"pareto_bench: families registered but not scored: {missing} "
            f"— add them to FAMILY_KWARGS (the tournament is exhaustive "
            f"by contract)")

    trials = args.trials or (3 if args.tiny else 5)
    timing_iters = args.iters or (1 if args.tiny else 3)
    max_iters = 100 if args.tiny else 200
    regs = regimes(args.tiny)

    all_rows: List[Dict] = []
    fronts: Dict[str, Dict[str, List[str]]] = {}
    gate_failures: List[Dict] = []
    for reg in regs:
        rows = []
        for fam, kw in sorted(FAMILY_KWARGS.items()):
            row = score_family(fam, kw, reg, seed=args.seed, trials=trials,
                               timing_iters=timing_iters,
                               max_iters=max_iters)
            rows.append(row)
            print(f"[{reg['name']}] {fam:>16}: ose={row['ose_err']:.3f} "
                  f"iters={row['lsqr_iters']:>3} "
                  f"modeled={row['modeled_us']:8.2f}us "
                  f"measured={row['measured_us']:10.1f}us")
        fronts[reg["name"]] = {
            "all_axes": pareto_front(rows, AXES),
            "gate_axes": pareto_front(rows, GATE_AXES),
        }
        doms = gate_dominators("blockperm", rows)
        print(f"[{reg['name']}] front(4-axis): "
              f"{fronts[reg['name']]['all_axes']}")
        print(f"[{reg['name']}] front(gate):   "
              f"{fronts[reg['name']]['gate_axes']}")
        if doms and reg["claimed"]:
            gate_failures.append(dict(regime=reg["name"], dominators=doms))
            print(f"[{reg['name']}] GATE FAIL: blockperm strictly "
                  f"dominated by {doms}")
        elif doms:
            print(f"[{reg['name']}] (unclaimed regime) blockperm "
                  f"dominated by {doms}")
        all_rows.extend(rows)

    gate_pass = not gate_failures
    payload = {
        "meta": {
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "interpret": jax.default_backend() != "tpu",
            "tiny": args.tiny,
            "seed": args.seed,
            "trials": trials,
            "tol": TOL,
            "axes": list(AXES),
            "gate_axes": list(GATE_AXES),
            "margin": MARGIN,
            "families": {f: json.dumps(kw, sort_keys=True)
                         for f, kw in sorted(FAMILY_KWARGS.items())},
            "blockperm_kin": list(BLOCKPERM_KIN),
            "note": ("all axes lower-is-better; modeled_us is the TPU-v5e "
                     "roofline of the launch the family would issue, "
                     "measured_us is host wall-clock (interpret off-TPU); "
                     "the gate replays the paper's figure axes "
                     "(mean-OSE x modeled) with a strict-win margin"),
        },
        "regimes": regs,
        "rows": all_rows,
        "pareto_fronts": fronts,
        "gate": {
            "pass": gate_pass,
            "rule": (f"fail iff blockperm is dominated on {GATE_AXES} "
                     f"(<= on both, < by a {MARGIN:.0%} relative margin "
                     f"on one) by a non-kin family in a claimed regime"),
            "failures": gate_failures,
        },
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"\nwrote {args.out}: {len(all_rows)} rows over "
          f"{len(regs)} regimes; gate {'PASS' if gate_pass else 'FAIL'}")
    return 0 if gate_pass else 1


if __name__ == "__main__":
    sys.exit(main())
