"""Quickstart: sketch a matrix with BLOCKPERM-SJLT / FlashSketch.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core.blockperm import make_plan
from repro.core import coherence
from repro.core.variants import make_sketch
from repro.kernels import ops


def main():
    d, n, k = 8192, 256, 1024
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.normal(size=(d, n)), jnp.float32)

    # --- low-level API: plan + kernel apply -------------------------------
    plan = make_plan(d, k, kappa=4, s=2, seed=0)
    print("plan:", plan.describe())
    Y = ops.sketch_apply(plan, A)           # Pallas on TPU, XLA elsewhere
    print("Y = SA:", Y.shape)
    print("Gram rel-error:", coherence.gram_rel_error(np.asarray(A), np.asarray(Y)))

    # --- transpose apply (the VJP / decompression operator) ---------------
    X = ops.sketch_apply_t(plan, Y)
    print("SᵀY:", X.shape)

    # --- high-level API: sketch families for benchmarking -----------------
    for fam in ("blockperm", "dense_gaussian", "srht", "blockrow"):
        sk = make_sketch(fam, d, k, seed=1)
        err = coherence.gram_rel_error(np.asarray(A), np.asarray(sk.apply(A)))
        print(f"{fam:16s} gram_rel={err:.4f}")


if __name__ == "__main__":
    main()
