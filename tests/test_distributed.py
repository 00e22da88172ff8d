"""Multi-device sketching tests (PR-4 acceptance set).

Two layers:
  * in-process — the per-ℓ partial kernel/oracle building blocks on the
    single test device (interpret-mode Pallas), including the
    exact-reconstruction property a psum relies on;
  * subprocess — the real shard_map paths on 8 forced host devices (the
    ``test_sharding_multidevice`` pattern: the main test process must keep
    1 device): row/col/batch-sharded applies must be ``array_equal`` to
    single-device across κ ∈ {1, 2} and both streaming dtypes, and the
    distributed sketch-and-precondition solver must converge.
"""
import json
import math
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.blockperm import make_plan
from repro.distributed import (check_row_partition, local_partial_apply,
                               partial_tables, plan_for_mesh)
from repro.kernels import ops, ref as kref


# ---------------------------------------------------------------------------
# in-process: partial kernel / oracle building blocks
# ---------------------------------------------------------------------------

def _shard_and_reassemble(plan, A, num_shards, *, impl, rows_pattern=False,
                          tn=8):
    """Emulate the sharded protocol serially: per-shard partials, summed
    (the psum), ℓ-ordered fold, scale, truncate."""
    M_loc = check_row_partition(plan, num_shards)
    Ap = kref.pad_input(plan, A)
    acc = None
    for p in range(num_shards):
        slab = Ap[p * M_loc * plan.Bc:(p + 1) * M_loc * plan.Bc]
        parts = local_partial_apply(plan, slab, p * M_loc, impl=impl, tn=tn,
                                    rows_pattern=rows_pattern)
        acc = parts if acc is None else acc + parts
    Y = acc[0]
    for ell in range(1, plan.kappa):
        Y = Y + acc[ell]
    scale = plan.scale
    if rows_pattern:
        scale *= math.sqrt(plan.d_pad / plan.k_pad)
    return (Y * scale)[: plan.k]


@pytest.mark.parametrize("kappa,dtype", [(1, "float32"), (2, "float32"),
                                         (2, "bfloat16")])
def test_partial_oracle_reassembles_bit_exact(kappa, dtype, rng):
    """Serial shard emulation of the xla partials == single-device xla
    apply, BITWISE — the property that makes the psum'd path exact."""
    plan = make_plan(500, 128, kappa=kappa, s=2, block_rows=16, seed=5,
                     dtype=dtype)
    A = jnp.asarray(rng.normal(size=(500, 9)), jnp.float32)
    Y = _shard_and_reassemble(plan, A, 4, impl="xla")
    ref = ops.sketch_apply(plan, A, "xla")
    assert np.array_equal(np.asarray(Y), np.asarray(ref))


@pytest.mark.parametrize("rows_pattern", [False, True])
def test_partial_pallas_kernel_matches_oracle(rows_pattern, rng):
    """The fused partial Pallas kernel == the jnp partial oracle on each
    shard's slab (interpret mode)."""
    plan = make_plan(500, 128, kappa=2, s=2, block_rows=16, seed=5)
    A = jnp.asarray(rng.normal(size=(500, 8)), jnp.float32)
    Yk = _shard_and_reassemble(plan, A, 2, impl="pallas",
                               rows_pattern=rows_pattern)
    ref_fn = ops.blockrow_apply if rows_pattern else ops.sketch_apply
    ref = ref_fn(plan, A, "pallas", 8)
    np.testing.assert_allclose(np.asarray(Yk), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_partial_tables_partition_covers_every_pair():
    """Ownership across shards is a PARTITION of the κ·M (g, ℓ) pairs —
    exactly one shard owns each — which is what makes psum exact.  The
    compact tables list each shard's owned pairs explicitly; their union
    must tile the full grid with no overlap."""
    plan = make_plan(500, 128, kappa=2, s=2, block_rows=16, seed=5)
    num = 4
    M_loc = check_row_partition(plan, num)
    for ell in range(plan.kappa):
        gs = np.concatenate([
            np.asarray(partial_tables(plan, p * M_loc, M_loc))[0, ell]
            for p in range(num)])
        assert np.array_equal(np.sort(gs), np.arange(plan.M))
    # blockrow's masked tables carry an explicit owned flag instead
    owned_sum = sum(
        np.asarray(partial_tables(plan, p * M_loc, M_loc,
                                  rows_pattern=True))[2]
        for p in range(num))
    assert np.array_equal(owned_sum, np.ones((plan.kappa, plan.M), np.int64))


def test_partial_apply_nonowned_slices_are_exact_zero(rng):
    """local_partial_apply returns the GLOBAL layout with exact zeros at
    every (ℓ, g) pair another shard owns."""
    plan = make_plan(500, 128, kappa=2, s=2, block_rows=16, seed=5)
    M_loc = plan.M // 4
    Ap = kref.pad_input(plan, jnp.asarray(rng.normal(size=(500, 8)),
                                          jnp.float32))
    slab = Ap[: M_loc * plan.Bc]
    parts = local_partial_apply(plan, slab, 0, impl="pallas", tn=8)
    tabs = np.asarray(partial_tables(plan, 0, M_loc))    # (2, kappa, M_loc)
    parts_np = np.asarray(parts).reshape(plan.kappa, plan.M, plan.Br, -1)
    for ell in range(plan.kappa):
        owned_g = set(tabs[0, ell].tolist())
        for g in range(plan.M):
            if g not in owned_g:
                assert np.all(parts_np[ell, g] == 0.0)
            else:
                assert np.any(parts_np[ell, g] != 0.0)


def test_partial_pallas_vmem_overflow_falls_back(rng):
    """A plan whose (Br, Bc) Φ tile busts VMEM at any tile width must not
    launch the partial kernel — impl='pallas' silently degrades to the jnp
    oracle (there is no v1 partial), mirroring ops' fused→v1 fallback."""
    from repro.distributed import partial_fits_vmem
    plan = make_plan(262_144, 1024, kappa=2, block_rows=128)   # M=8
    assert not partial_fits_vmem(plan, 8)
    A = jnp.asarray(rng.normal(size=(262_144, 4)), jnp.float32)
    Ap = kref.pad_input(plan, A)
    M_loc = plan.M // 8
    slab = Ap[: M_loc * plan.Bc]
    got = local_partial_apply(plan, slab, 0, impl="pallas", tn=None)
    want = local_partial_apply(plan, slab, 0, impl="xla")
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_dist_cost_model_rejects_unsharded_variants():
    from repro.roofline import sketch_model
    plan = plan_for_mesh(4096, 256, 4, kappa=2)
    with pytest.raises(ValueError, match="fwd"):
        sketch_model.dist_sketch_cost(plan, 16, 4, variant="blockrow")


def test_check_row_partition_rejects_bad_split():
    plan = make_plan(500, 128, kappa=2, s=2, block_rows=16, seed=5)  # M=8
    assert check_row_partition(plan, 4) == 2
    with pytest.raises(ValueError, match="divide"):
        check_row_partition(plan, 3)


def test_plan_for_mesh_divisible_grid():
    for num in (2, 4, 8):
        plan = plan_for_mesh(10_000, 200, num, kappa=2)
        assert plan.M % num == 0
        assert plan.k_pad >= 200


def test_lsqr_operator_matches_dense_lsqr(rng):
    """The injected-ops LSQR is the dense solver when fed A's products
    (the refactor contract dist_solvers relies on)."""
    from repro.kernels import ops as kops
    from repro.solvers import lsqr, lsqr_operator

    A = jnp.asarray(rng.normal(size=(400, 12)), jnp.float32)
    b = A @ jnp.asarray(rng.normal(size=(12,)), jnp.float32)
    plan = make_plan(400, 48, kappa=2, s=2, seed=1)
    _, R = kops.sketch_qr(plan, A, "xla")
    dense = lsqr(A, b, R=R, tol=1e-5)
    viaops = lsqr_operator(lambda v: A @ v, lambda u: A.T @ u, b,
                           nvars=12, R=R, tol=1e-5)
    assert viaops.converged and dense.converged
    assert viaops.iterations == dense.iterations
    # same recurrence, but separately-compiled programs: fp32 rounding may
    # differ per iteration — identical to solver precision, not bitwise
    np.testing.assert_allclose(np.asarray(viaops.x), np.asarray(dense.x),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# subprocess: the real shard_map paths on 8 forced host devices
# ---------------------------------------------------------------------------

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.core.blockperm import make_plan
    from repro.distributed import (dist_sketch_precondition_lstsq,
                                   sketch_apply_batched_sharded,
                                   sketch_apply_colsharded,
                                   sketch_apply_sharded)
    from repro.kernels import ops
    from repro.launch import mesh as mesh_lib

    rng = np.random.default_rng(0)
    mesh = mesh_lib.make_mesh((8,), ("shard",))
    out = {"exact": {}, "solver": {}}

    A = jnp.asarray(rng.normal(size=(3000, 16)), jnp.float32)
    for kappa in (1, 2):
        for dtype in ("float32", "bfloat16"):
            plan = make_plan(3000, 256, kappa=kappa, s=2, seed=3,
                             block_rows=32, dtype=dtype)
            ref = ops.sketch_apply(plan, A)
            key = f"kappa{kappa}_{dtype}"
            out["exact"]["row_" + key] = bool(np.array_equal(
                np.asarray(sketch_apply_sharded(plan, A, mesh, "shard")),
                np.asarray(ref)))
            out["exact"]["col_" + key] = bool(np.array_equal(
                np.asarray(sketch_apply_colsharded(plan, A, mesh, "shard")),
                np.asarray(ref)))
            G = jnp.asarray(rng.normal(size=(8, 3000, 4)), jnp.float32)
            out["exact"]["batch_" + key] = bool(np.array_equal(
                np.asarray(sketch_apply_batched_sharded(
                    plan, G, mesh, "shard")),
                np.asarray(ops.sketch_apply_batched(plan, G))))

    # blockrow row-sharded (the appendix variant shares the partial path)
    plan = make_plan(3000, 256, kappa=2, s=2, seed=3, block_rows=32)
    out["exact"]["row_blockrow"] = bool(np.array_equal(
        np.asarray(sketch_apply_sharded(plan, A, mesh, "shard",
                                        rows_pattern=True)),
        np.asarray(ops.blockrow_apply(plan, A))))

    # batch-sharded gather-fused (the distributed GraSS layout)
    plan_g = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    idx = jnp.asarray(np.sort(rng.choice(3000, 256, replace=False)),
                      jnp.int32)
    out["exact"]["batch_gather"] = bool(np.array_equal(
        np.asarray(sketch_apply_batched_sharded(
            plan_g, G, mesh, "shard", row_index=idx)),
        np.asarray(ops.sketch_apply_batched(plan_g, G, row_index=idx))))

    # batch-sharded GraSS featurize == single-device features
    from repro.attribution import mlp as mlp_lib
    from repro.attribution.grass import GrassPipeline, GrassPipelineConfig
    mcfg = mlp_lib.MLPConfig(d_in=32, hidden=(16,), steps=5)
    xg, yg = mlp_lib.make_synthetic_mnist(32, 32, mcfg.n_classes, seed=0)
    params = mlp_lib.train_mlp(mcfg, xg, yg)
    gcfg = GrassPipelineConfig(sparse_dim=128, sketch_dim=32, chunk=4)
    f_single = GrassPipeline(gcfg, params).featurize(xg, yg)
    f_shard = GrassPipeline(gcfg, params, mesh=mesh, shard_axis="shard")
    f_sharded = f_shard.featurize(xg, yg)
    out["exact"]["grass_featurize"] = bool(np.allclose(
        np.asarray(f_single), np.asarray(f_sharded), atol=1e-5))
    out["exact"]["grass_no_quarantine"] = f_shard.quarantined == 0

    # distributed sketch-and-precondition: converges, matches single-device
    d, n = 4096, 24
    Am = jnp.asarray(rng.normal(size=(d, n)), jnp.float32)
    b = Am @ jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    res = dist_sketch_precondition_lstsq(Am, b, mesh, "shard", tol=1e-5)
    x_np, *_ = np.linalg.lstsq(np.asarray(Am), np.asarray(b), rcond=None)
    out["solver"] = {
        "converged": bool(res.converged),
        "iterations": int(res.iterations),
        "relres": float(res.relres),
        "x_err": float(np.max(np.abs(np.asarray(res.x) - x_np))),
    }

    # guarded distributed solve: the replica-consistency guard must see the
    # psum'd SA bit-identical on all 8 devices and accept draw #1
    resg = dist_sketch_precondition_lstsq(Am, b, mesh, "shard", tol=1e-5,
                                          guard=True)
    out["solver"]["guarded_converged"] = bool(resg.converged)
    out["solver"]["guarded_status"] = resg.health.status
    out["solver"]["guarded_attempts"] = int(resg.health.attempts)
    out["solver"]["guarded_replica_ok"] = any(
        f.guard == "replica_consistency" and f.status == "healthy"
        for f in resg.health.findings)
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_sharded_apply_matches_single_device(tmp_path):
    script = tmp_path / "dist_run.py"
    script.write_text(_SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath("src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert all(res["exact"].values()), res["exact"]
    assert res["solver"]["converged"], res["solver"]
    assert res["solver"]["iterations"] <= 40
    assert res["solver"]["x_err"] < 1e-3
    assert res["solver"]["guarded_converged"], res["solver"]
    assert res["solver"]["guarded_status"] in ("healthy", "degraded")
    assert res["solver"]["guarded_attempts"] == 1, res["solver"]
    assert res["solver"]["guarded_replica_ok"], res["solver"]
