"""GraSS sparsify→sketch benchmark: gather-fused batched FlashSketch vs the
seed pipeline (materialized gather + per-example sketch launches).

    PYTHONPATH=src python -m benchmarks.grass_bench               # paper grid
    PYTHONPATH=src python -m benchmarks.grass_bench --tiny        # CI smoke

Writes ``BENCH_grass.json``.  Each row covers one (B, sparse_dim, κ) cell:

  * measured_* — interpret-mode wall-clock on THIS host.  Real, and the
    per-example column shows the launch-count pathology directly, but the
    DMA emulation overhead makes interpret-mode gather *kernels* slow —
    not TPU time.
  * modeled_*  — TPU-v5e numbers from ``roofline.sketch_model.
    grass_sketch_cost`` (transaction-granular gather reads + per-launch
    overhead); the trustworthy number off-TPU and the one the acceptance
    geomean is computed from.

The run FAILS (non-zero exit) if the fused kernel is not bit-exact against
gather-then-``pallas`` on any variant/dtype, or if the modeled geomean
speedup of fused-batched over gather-then-sketch-per-example drops below
1.5× — CI runs ``--tiny`` as a regression gate.

``grass_rows`` (the Fig.-4 LDS-vs-time rows used by ``benchmarks.run``) is
kept unchanged at the bottom.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import geomean, time_fn
from repro import engine
from repro.attribution.grass import sparsify_mask
from repro.core.blockperm import make_plan
from repro.kernels import ops
from repro.roofline import sketch_model
from repro.compile_cache import enable_compile_cache

DTYPES = (None, "bfloat16")          # None = fp32 (the plan default)


def _bit_exact(plan, G, mask, tn, dtype) -> Dict[str, bool]:
    """Fused S·G[mask] vs gather-then-pallas on every gatherable variant."""
    Gm = G[mask]
    out = {}
    for variant in ("fwd", "blockrow"):
        if variant == "fwd":
            fused = ops.sketch_apply(plan, G, "pallas", tn, dtype,
                                     row_index=mask)
            ref = ops.sketch_apply(plan, Gm, "pallas", tn, dtype)
        else:
            fused = ops.blockrow_apply(plan, G, "pallas", tn, dtype,
                                       row_index=mask)
            ref = ops.blockrow_apply(plan, Gm, "pallas", tn, dtype)
        out[f"{variant}_{dtype or 'float32'}"] = bool(
            np.array_equal(np.asarray(fused), np.asarray(ref)))
    return out


def bench_grid(B_values, sparse_dims, kappas, *, k, d_total_of, s=2, seed=0,
               iters=3, max_measured_examples=8) -> List[Dict]:
    rows: List[Dict] = []
    rng = np.random.default_rng(seed)
    for sparse_dim in sparse_dims:
        d_total = d_total_of(sparse_dim)
        mask = sparsify_mask(d_total, sparse_dim, seed)
        for kappa in kappas:
            plan = make_plan(sparse_dim, k, kappa=kappa, s=s, seed=seed)
            for B in B_values:
                # B per-example gradient vectors as columns of one (D, B)
                G = jnp.asarray(
                    rng.normal(size=(d_total, B)).astype(np.float32))
                # each kernel shape class gets its own VMEM-fitting tile —
                # the fused gather scratch is smaller than the fwd kernel's
                # double-buffered pipeline, so their budgets differ; the
                # bit-exact check runs both at the common (smaller) width.
                # Tiles come from the lowering records of the two launches
                # being compared (the engine is the single decision layer).
                lw_fused = engine.lower(plan, engine.LaunchSpec(
                    op="fwd", n=1, impl="pallas", gather=True, batch=B))
                lw_ref = engine.lower(plan, engine.LaunchSpec(
                    op="fwd", n=B, impl="pallas"))
                tn = lw_fused.tn
                tn_ref = lw_ref.tn
                tn_check = min(tn, tn_ref)

                # -------- bit-exactness gate (all variants × dtypes)
                exact = {}
                for dtype in DTYPES:
                    exact.update(_bit_exact(plan, G, mask, tn_check, dtype))

                # -------- measured (interpret mode off-TPU)
                fused = jax.jit(lambda X: ops.sketch_apply(
                    plan, X, "pallas", tn, None, row_index=mask))
                fused_us = 1e6 * time_fn(fused, G, iters=iters)

                unf_batched = jax.jit(
                    lambda X: ops.sketch_apply(plan, X[mask], "pallas",
                                               tn_ref))
                unf_batched_us = 1e6 * time_fn(unf_batched, G, iters=iters)

                # per-example: B materializing-gather + skinny-sketch passes
                # (the gather happens INSIDE the timed fn, as in the seed
                # pipeline) — measure a capped number of examples and
                # extrapolate (the passes are identical; interpret-mode
                # python overhead is per-launch)
                n_meas = min(B, max_measured_examples)
                one = jax.jit(lambda g_col: ops.sketch_apply(
                    plan, g_col[mask], "pallas", min(8, tn_ref)))
                cols = [G[:, b:b + 1] for b in range(n_meas)]

                def per_example_pass(cols=cols):
                    outs = [one(c) for c in cols]
                    return outs[-1]

                per_meas_us = 1e6 * time_fn(per_example_pass, iters=iters)
                per_example_us = per_meas_us * (B / n_meas)

                # -------- modeled (TPU v5e)
                m = {
                    kind: sketch_model.grass_sketch_cost(
                        plan, B, fused=f, batched=b)
                    for kind, (f, b) in {
                        "fused_batched": (True, True),
                        "fused_per_example": (True, False),
                        "unfused_batched": (False, True),
                        "unfused_per_example": (False, False),
                    }.items()
                }
                row = dict(
                    B=B, d_total=d_total, sparse_dim=sparse_dim, k=plan.k_pad,
                    kappa=kappa, s=s, tn=tn, tn_ref=tn_ref,
                    M=plan.M, Br=plan.Br, Bc=plan.Bc,
                    lowering_fused=lw_fused.describe(),
                    bit_exact=exact,
                    measured_fused_batched_us=fused_us,
                    measured_unfused_batched_us=unf_batched_us,
                    measured_unfused_per_example_us=per_example_us,
                    measured_examples=n_meas,
                    measured_speedup=per_example_us / fused_us,
                    modeled_fused_batched_us=m["fused_batched"],
                    modeled_fused_per_example_us=m["fused_per_example"],
                    modeled_unfused_batched_us=m["unfused_batched"],
                    modeled_unfused_per_example_us=m["unfused_per_example"],
                    modeled_speedup=(m["unfused_per_example"]
                                     / m["fused_batched"]),
                    modeled_speedup_vs_unfused_batched=(
                        m["unfused_batched"] / m["fused_batched"]),
                )
                rows.append(row)
                ok = all(exact.values())
                print(f"B={B:>4} d_keep={sparse_dim:>6} kappa={kappa} "
                      f"tn={tn:<4} bit_exact={'OK' if ok else 'FAIL'} "
                      f"measured x{row['measured_speedup']:.2f} "
                      f"modeled x{row['modeled_speedup']:.1f} "
                      f"(vs unfused-batched x"
                      f"{row['modeled_speedup_vs_unfused_batched']:.2f})")
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke grid (seconds, still gates bit-exactness)")
    ap.add_argument("--out", default="BENCH_grass.json")
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.tiny:
        B_values, sparse_dims, kappas = (8,), (512,), (1,)
        k, d_total_of = 128, lambda d: 4 * d
    else:
        B_values, sparse_dims, kappas = (32, 256), (4096, 16_384), (1, 2)
        k, d_total_of = 1024, lambda d: 4 * d

    rows = bench_grid(B_values, sparse_dims, kappas, k=k,
                      d_total_of=d_total_of, iters=args.iters)

    all_exact = all(all(r["bit_exact"].values()) for r in rows)
    geo_modeled = geomean([r["modeled_speedup"] for r in rows])
    geo_measured = geomean([r["measured_speedup"] for r in rows])
    payload = {
        "meta": {
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "interpret": jax.default_backend() != "tpu",
            "tiny": args.tiny,
            "grid": {"B": list(B_values), "sparse_dim": list(sparse_dims),
                     "kappa": list(kappas), "k": k},
            "note": ("fused-gather-batched vs gather-then-sketch; "
                     "measured_* is interpret-mode wall-clock off-TPU "
                     "(per-example column extrapolated from "
                     "measured_examples launches); modeled_* is "
                     "roofline.sketch_model.grass_sketch_cost on TPU v5e"),
        },
        "rows": rows,
        "all_bit_exact": all_exact,
        "geomean_modeled_speedup": geo_modeled,
        "geomean_measured_speedup": geo_measured,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"\nwrote {args.out}: modeled geomean x{geo_modeled:.1f}, "
          f"measured geomean x{geo_measured:.2f}, "
          f"bit_exact={'OK' if all_exact else 'FAIL'}")

    if not all_exact:
        print("FAIL: fused path lost bit-exactness vs the unfused reference",
              file=sys.stderr)
        return 1
    if geo_modeled < 1.5:
        print(f"FAIL: modeled geomean {geo_modeled:.2f}x < 1.5x",
              file=sys.stderr)
        return 1
    return 0


# ---------------------------------------------------------------------------
# Fig. 4 analogue: end-to-end GraSS — LDS vs per-sample sketch time,
# across sketch families × k (paper App. E: MLP, sketch 4k -> k).
# Used by ``benchmarks.run --only grass``.
# ---------------------------------------------------------------------------

def grass_rows(scale: str = "smoke") -> List[str]:
    from repro.attribution.grass import GrassPipelineConfig, run_grass_lds
    from repro.attribution.mlp import MLPConfig

    if scale == "full":
        mcfg = MLPConfig(d_in=784, hidden=(256, 256), steps=120)
        n_train, n_test, m = 1024, 32, 50
        sparse, ks = 4096, (1024, 2048)
    else:
        mcfg = MLPConfig(d_in=128, hidden=(32, 32), steps=80)
        n_train, n_test, m = 256, 24, 24
        sparse, ks = 1024, (256,)
    rows = []
    for fam in ("blockperm", "dense_gaussian", "sjlt", "srht", "blockrow"):
        for k in ks:
            res = run_grass_lds(
                GrassPipelineConfig(sparse_dim=sparse, sketch_dim=k,
                                    sketch_family=fam),
                mcfg, n_train=n_train, n_test=n_test, m_subsets=m)
            rows.append(
                f"grass,{fam},k={k},,,,{res['lds']:.4f},"
                f"{res['per_sample_us']:.1f},lds_vs_us_per_sample")
    return rows


if __name__ == "__main__":
    sys.exit(main())
