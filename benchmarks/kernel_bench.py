"""Kernel-level microbenchmark: FlashSketch v1 vs v2.

    PYTHONPATH=src python -m benchmarks.kernel_bench            # smoke grid
    PYTHONPATH=src python -m benchmarks.kernel_bench --full     # paper grid
    PYTHONPATH=src python -m benchmarks.kernel_bench --autotune # tn sweep first

Times the Pallas kernels (interpret mode off-TPU) for fwd / transpose /
blockrow, fp32 and bf16, across a (d, k) grid, and writes a machine-readable
``BENCH_kernel.json`` so future PRs have a perf trajectory to regress
against.  Each row carries both:

  * measured v1/v2 wall-times on THIS host (interpret-mode python overhead
    scales with grid steps, so the κ-fused v2 launch shows up directly);
  * modeled TPU-v5e times from ``roofline.sketch_model`` (single-write +
    bf16-streaming HBM terms) — the trustworthy number off-TPU.

v1 is fp32-only; bf16 rows therefore compare v2-bf16 against the fp32 v1
baseline, which is exactly the upgrade a user of the old kernel gets.
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, List

import jax
import numpy as np

from benchmarks.common import geomean, time_fn
from repro import engine
from repro.core.blockperm import SKETCH_VARIANTS as VARIANTS
from repro.core.blockperm import make_plan
from repro.kernels import ops, tune
from repro.compile_cache import enable_compile_cache

DTYPES = ("float32", "bfloat16")


def _apply_fn(variant: str, impl: str, plan, tn, dtype):
    if variant == "fwd":
        return jax.jit(lambda X: ops.sketch_apply(plan, X, impl, tn, dtype))
    if variant == "transpose":
        return jax.jit(lambda X: ops.sketch_apply_t(plan, X, impl, tn, dtype))
    return jax.jit(lambda X: ops.blockrow_apply(plan, X, impl, tn, dtype))


def _operand(variant: str, plan, n: int, rng) -> np.ndarray:
    rows = plan.k_pad if variant == "transpose" else plan.d
    return rng.normal(size=(rows, n)).astype(np.float32)


def bench_grid(d_values, k_values, n_for, *, kappa=4, s=2, seed=0,
               tn=None, iters=3, autotune_first=False,
               check_allclose=True) -> List[Dict]:
    rows: List[Dict] = []
    rng = np.random.default_rng(seed)
    for d in d_values:
        for k in k_values:
            if k * 8 > d:        # stay in the paper's d >> k regime
                continue
            n = n_for(d)
            for dtype in DTYPES:
                plan = make_plan(d, k, kappa=kappa, s=s, seed=seed, dtype=dtype)
                for variant in VARIANTS:
                    use_tn = v1_tn = tn
                    if use_tn is None:
                        # the tiles the engine resolves (chip-legal)
                        use_tn = engine.lower(plan, engine.LaunchSpec(
                            op=variant, n=n, impl="pallas")).tn
                        v1_tn = tune.v1_default_tn(plan, n)
                    if autotune_first:
                        # each generation gets its own best tile — timing v1
                        # at v2's winner would bias the speedup toward v2
                        use_tn = tune.autotune(plan, n, variant, iters=1).tn
                        v1_tn = tune.v1_default_tn(plan, n)
                    X = _operand(variant, plan, n, rng)
                    v2 = _apply_fn(variant, "pallas", plan, use_tn, dtype)
                    v1 = _apply_fn(variant, "pallas_v1", plan, v1_tn, dtype)
                    if check_allclose and dtype == "float32":
                        np.testing.assert_allclose(
                            np.asarray(v2(X)), np.asarray(v1(X)),
                            atol=1e-5, rtol=1e-5,
                        )
                    v2_us = 1e6 * time_fn(v2, X, iters=iters)
                    v1_us = 1e6 * time_fn(v1, X, iters=iters)
                    # modeled costs come from the SAME lowering records the
                    # timed entry points resolve — not re-derived knobs
                    lw2 = engine.lower(plan, engine.LaunchSpec(
                        op=variant, n=n, impl="pallas", tn=use_tn))
                    lw1 = engine.lower(plan, engine.LaunchSpec(
                        op=variant, n=n, impl="pallas_v1", tn=use_tn))
                    m2 = engine.cost_of(lw2)
                    m1 = engine.cost_of(lw1)
                    row = dict(
                        d=d, k=plan.k_pad, n=n, kappa=kappa, s=s,
                        variant=variant, dtype=dtype, tn=use_tn, v1_tn=v1_tn,
                        M=plan.M, Br=plan.Br, Bc=plan.Bc,
                        v1_us=v1_us, v2_us=v2_us,
                        speedup=v1_us / v2_us,
                        modeled_v1_us=m1.modeled_us, modeled_v2_us=m2.modeled_us,
                        modeled_speedup=m1.modeled_us / m2.modeled_us,
                        modeled_bottleneck_v2=m2.bottleneck,
                        lowering_v2=lw2.describe(),
                        lowering_v1=lw1.describe(),
                    )
                    rows.append(row)
                    print(f"{d:>7} {plan.k_pad:>5} {variant:>9} {dtype:>8} "
                          f"tn={use_tn:<4} v1={v1_us:9.1f}us v2={v2_us:9.1f}us "
                          f"x{row['speedup']:.2f}  modeled x{row['modeled_speedup']:.2f}")
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale (d, k) grid")
    ap.add_argument("--out", default="BENCH_kernel.json")
    ap.add_argument("--tn", type=int, default=None,
                    help="column tile (default: the engine's choice)")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--autotune", action="store_true",
                    help="sweep tn per shape before timing")
    ap.add_argument("--tune-cache", default=None,
                    help="path to persist the autotuner cache")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.full:
        d_values = (16_384, 65_536, 131_072)
        k_values = (256, 1024, 4096)
        n_for = lambda d: 1024 if d <= 65_536 else 512
    else:
        d_values = (4096, 16_384)
        k_values = (256, 1024)
        n_for = lambda d: 256

    rows = bench_grid(d_values, k_values, n_for, tn=args.tn, iters=args.iters,
                      autotune_first=args.autotune)

    measured = geomean([r["speedup"] for r in rows])
    modeled = geomean([r["modeled_speedup"] for r in rows])
    modeled_bf16 = geomean(
        [r["modeled_speedup"] for r in rows if r["dtype"] == "bfloat16"])
    payload = {
        "meta": {
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "interpret": jax.default_backend() != "tpu",
            "grid": {"d": list(d_values), "k": list(k_values)},
            "note": ("measured_* is interpret-mode wall-clock off-TPU; "
                     "modeled_* is the roofline sketch_model on TPU v5e"),
        },
        "rows": rows,
        "geomean_measured_speedup": measured,
        "geomean_modeled_speedup": modeled,
        "geomean_modeled_speedup_bf16": modeled_bf16,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    if args.tune_cache:
        tune.save_cache(args.tune_cache)
    print(f"\nwrote {args.out}: geomean measured x{measured:.2f}, "
          f"modeled x{modeled:.2f} (bf16 rows x{modeled_bf16:.2f})")


if __name__ == "__main__":
    main()
