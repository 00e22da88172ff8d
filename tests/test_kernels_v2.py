"""FlashSketch v2 (fused-κ single-write) kernel tests.

Covers the PR-1 acceptance set: bit-exactness of the fused Φ construction
against the ``dense_block`` oracle, v2-vs-v1 allclose on all three kernel
variants, differentiation through the bf16 streaming path, and autotuner
cache determinism.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import blockperm, wiring
from repro.core.blockperm import make_plan
from repro.kernels import flashsketch as fsk
from repro.kernels import ops, ref as kref, tune

SWEEP = [
    # (d, k, kappa, s, block_rows, n)
    (256, 64, 1, 1, 8, 16),
    (256, 64, 2, 2, 8, 33),
    (300, 96, 3, 2, 16, 37),
    (512, 128, 4, 4, 32, 64),
    (1000, 256, 4, 2, 32, 128),
]


# ---------------------------------------------------------------------------
# Fused Φ construction: bit-exact vs the dense_block / ref.py oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,k,kappa,s,br,n", SWEEP[:4])
def test_stacked_phi_bit_exact(d, k, kappa, s, br, n):
    plan = make_plan(d=d, k=k, kappa=kappa, s=s, block_rows=br, seed=d + n)
    pi = np.asarray(wiring.wiring_table(plan.seed, plan.M, plan.kappa))
    for g in range(min(plan.M, 4)):
        neighbors = pi[:, g]
        stacked = np.asarray(fsk.stacked_phi(plan, g, neighbors))
        assert stacked.shape == (plan.Br, plan.kappa * plan.Bc)
        for ell, h in enumerate(neighbors):
            want = np.asarray(blockperm.dense_block(plan, g, int(h)))
            got = stacked[:, ell * plan.Bc:(ell + 1) * plan.Bc]
            # entries are ±1/0 — must match *bitwise*, not just to tolerance
            assert np.array_equal(got, want), (g, ell, h)


def test_stacked_phi_bf16_lossless():
    """Casting Φ to bf16 (the mixed-precision scratch dtype) is exact."""
    plan = make_plan(512, 128, kappa=4, s=2, block_rows=32, seed=3)
    pi = np.asarray(wiring.wiring_table(plan.seed, plan.M, plan.kappa))
    stacked = fsk.stacked_phi(plan, 0, pi[:, 0])
    assert np.array_equal(
        np.asarray(stacked.astype(jnp.bfloat16).astype(jnp.float32)),
        np.asarray(stacked),
    )


# ---------------------------------------------------------------------------
# v2 vs v1 equivalence on all three kernel variants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,k,kappa,s,br,n", SWEEP)
def test_v2_matches_v1_fwd(d, k, kappa, s, br, n, rng):
    plan = make_plan(d=d, k=k, kappa=kappa, s=s, block_rows=br, seed=d + n)
    A = jnp.asarray(rng.normal(size=(d, n)), jnp.float32)
    Y1 = ops.sketch_apply(plan, A, impl="pallas_v1", tn=16)
    Y2 = ops.sketch_apply(plan, A, impl="pallas", tn=16)
    np.testing.assert_allclose(np.asarray(Y2), np.asarray(Y1),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("d,k,kappa,s,br,n", SWEEP)
def test_v2_matches_v1_transpose(d, k, kappa, s, br, n, rng):
    plan = make_plan(d=d, k=k, kappa=kappa, s=s, block_rows=br, seed=d + n)
    Y = jnp.asarray(rng.normal(size=(plan.k, n)), jnp.float32)
    X1 = ops.sketch_apply_t(plan, Y, impl="pallas_v1", tn=16)
    X2 = ops.sketch_apply_t(plan, Y, impl="pallas", tn=16)
    np.testing.assert_allclose(np.asarray(X2), np.asarray(X1),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("d,k,kappa,s,br,n", SWEEP)
def test_v2_matches_v1_blockrow(d, k, kappa, s, br, n, rng):
    plan = make_plan(d=d, k=k, kappa=kappa, s=s, block_rows=br, seed=d + n)
    A = jnp.asarray(rng.normal(size=(d, n)), jnp.float32)
    Y1 = ops.blockrow_apply(plan, A, impl="pallas_v1", tn=16)
    Y2 = ops.blockrow_apply(plan, A, impl="pallas", tn=16)
    np.testing.assert_allclose(np.asarray(Y2), np.asarray(Y1),
                               atol=1e-5, rtol=1e-5)


def test_v2_matches_ref_fwd(rng):
    plan = make_plan(1000, 256, kappa=4, s=2, block_rows=32, seed=9)
    A = jnp.asarray(rng.normal(size=(1000, 40)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.sketch_apply(plan, A, impl="pallas", tn=8)),
        np.asarray(kref.flashsketch_ref(plan, A)),
        atol=1e-5, rtol=1e-5,
    )


# ---------------------------------------------------------------------------
# Mixed-precision streaming path
# ---------------------------------------------------------------------------

def test_bf16_stream_matches_bf16_oracle(rng):
    """Pallas bf16 path == XLA oracle fed bf16-rounded input (fp32 accum)."""
    plan = make_plan(512, 128, kappa=4, s=2, block_rows=32, seed=7,
                     dtype="bfloat16")
    A = jnp.asarray(rng.normal(size=(512, 48)), jnp.float32)
    Yp = ops.sketch_apply(plan, A, impl="pallas", tn=16)
    Yx = ops.sketch_apply(plan, A, impl="xla")
    np.testing.assert_allclose(np.asarray(Yp), np.asarray(Yx),
                               atol=1e-5, rtol=1e-5)


def test_bf16_stream_close_to_fp32(rng):
    plan = make_plan(512, 128, kappa=4, s=2, block_rows=32, seed=7)
    A = jnp.asarray(rng.normal(size=(512, 48)), jnp.float32)
    Y32 = ops.sketch_apply(plan, A, impl="pallas", tn=16)
    Yb = ops.sketch_apply(plan, A, impl="pallas", tn=16, dtype="bfloat16")
    # bf16 has ~8 mantissa bits: inputs are O(1), κs=8 terms per output
    np.testing.assert_allclose(np.asarray(Yb), np.asarray(Y32),
                               atol=5e-2, rtol=5e-2)


def test_vjp_roundtrip_bf16(rng):
    """jax.grad through sketch_apply on the bf16 path ≈ the fp32 VJP = Sᵀ dY."""
    plan = make_plan(300, 96, kappa=3, s=2, block_rows=16, seed=5)
    A = jnp.asarray(rng.normal(size=(300, 24)), jnp.float32)
    W = jnp.asarray(rng.normal(size=(plan.k, 24)), jnp.float32)

    def loss(A_, impl, dtype):
        return jnp.sum(W * ops.sketch_apply(plan, A_, impl, 8, dtype))

    g_ref = jax.grad(lambda A_: loss(A_, "xla", None))(A)
    g_bf = jax.grad(lambda A_: loss(A_, "pallas", "bfloat16"))(A)
    # dL/dA = Sᵀ W exactly, so the bf16 kernel path must track it closely
    np.testing.assert_allclose(np.asarray(g_bf), np.asarray(g_ref),
                               atol=5e-2, rtol=5e-2)
    g_f32 = jax.grad(lambda A_: loss(A_, "pallas", None))(A)
    np.testing.assert_allclose(np.asarray(g_f32), np.asarray(g_ref),
                               atol=1e-5, rtol=1e-5)


def test_plan_dtype_knob():
    plan = make_plan(256, 64, kappa=2, s=2, dtype="bfloat16")
    assert plan.stream_dtype == jnp.bfloat16
    assert plan.stream_itemsize == 2
    back = plan.with_dtype("float32")
    assert back.stream_itemsize == 4
    # dtype does not perturb the sketch draw
    assert back == make_plan(256, 64, kappa=2, s=2)
    with pytest.raises(ValueError):
        make_plan(256, 64, dtype="float16")


# ---------------------------------------------------------------------------
# Autotuner
# ---------------------------------------------------------------------------

def test_tune_heuristic_deterministic():
    plan = make_plan(512, 128, kappa=4, s=2, block_rows=32, seed=1)
    t1 = tune.resolve_tn(plan, 200, "fwd")
    t2 = tune.resolve_tn(plan, 200, "fwd")
    assert t1 == t2
    assert t1 & (t1 - 1) == 0            # power of two
    # small-n problems must not be padded past their bucket
    assert tune.resolve_tn(plan, 4, "fwd") <= 8


def test_tune_cache_roundtrip(tmp_path):
    tune.clear_cache()
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=2)
    res = tune.autotune(plan, 32, "fwd", iters=1, warmup=0)
    assert res.source == "tuned"
    assert tune.resolve_tn(plan, 32, "fwd") == res.tn
    # re-tuning the same shape class is a cache hit (same object back)
    assert tune.autotune(plan, 32, "fwd", iters=1, warmup=0) == res

    path = tmp_path / "tune.json"
    n_saved = tune.save_cache(str(path))
    assert n_saved == tune.cache_size() >= 1
    tune.clear_cache()
    assert tune.resolve_tn(plan, 32, "fwd") == tune.heuristic_tn(plan, 32, "fwd")
    n_loaded = tune.load_cache(str(path))
    assert n_loaded == n_saved
    assert tune.resolve_tn(plan, 32, "fwd") == res.tn
    # loaded entries are authoritative: autotune won't re-time them
    assert tune.autotune(plan, 32, "fwd", iters=1, warmup=0).source == "loaded"
    tune.clear_cache()


def test_autotune_raises_on_a_failed_candidate(monkeypatch):
    """A candidate that fails to run is a kernel or VMEM-model bug: the
    sweep raises instead of quietly dropping the tile."""
    tune.clear_cache()
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=2)

    def broken(plan, X, *, tn, interpret=False):
        raise RuntimeError(f"compile refused tn={tn}")

    monkeypatch.setitem(tune._KERNELS, "fwd", broken)
    with pytest.raises(RuntimeError, match="compile refused"):
        tune.autotune(plan, 256, "fwd", iters=1, warmup=0)
    assert tune.lookup(plan, 256, "fwd") is None
    tune.clear_cache()


def test_tune_key_separates_dtype_and_variant():
    plan = make_plan(512, 128, kappa=4, s=2, block_rows=32, seed=1)
    k_f32 = tune.cache_key(plan, 100, "fwd")
    k_b16 = tune.cache_key(plan.with_dtype("bfloat16"), 100, "fwd")
    k_tr = tune.cache_key(plan, 100, "transpose")
    assert len({k_f32, k_b16, k_tr}) == 3
    # n buckets to the next power of two
    assert tune.cache_key(plan, 100, "fwd") == tune.cache_key(plan, 128, "fwd")
    assert tune.cache_key(plan, 100, "fwd") != tune.cache_key(plan, 129, "fwd")


def test_default_plans_fit_fused_vmem():
    """make_plan trades Br for M so the v2 working set stays VMEM-resident
    across the paper's (d, k) grid."""
    for d in (16_384, 65_536, 131_072, 262_144):
        for k in (64, 1024, 4096):
            if k * 8 > d:
                continue
            plan = make_plan(d, k, kappa=4, s=2)
            assert plan.k_pad >= k          # padding contract unchanged
            for variant in ("fwd", "transpose", "blockrow"):
                assert tune.fused_fits_vmem(plan, 512, variant), \
                    (d, k, variant, plan.describe())


def test_oversized_pinned_plan_falls_back_to_v1(rng):
    """An explicit block_rows choice that blows the fused VMEM budget must
    dispatch to the v1 revisiting kernel — silently correct, not OOM."""
    plan = make_plan(65_536, 1024, kappa=4, s=2, block_rows=256)
    assert not tune.fused_fits_vmem(plan, 8, "fwd")
    A = jnp.zeros((plan.d_pad, 8), jnp.float32)
    A = A.at[:512].set(jnp.asarray(rng.normal(size=(512, 8)), jnp.float32))
    Yp = ops.sketch_apply(plan, A[: plan.d], impl="pallas", tn=8)
    Yr = kref.flashsketch_ref(plan, A[: plan.d])
    np.testing.assert_allclose(np.asarray(Yp), np.asarray(Yr),
                               atol=1e-5, rtol=1e-5)


def test_tune_key_includes_backend():
    plan = make_plan(512, 128, kappa=4, s=2, block_rows=32, seed=1)
    k_here = tune.cache_key(plan, 64, "fwd")
    k_interp = tune.cache_key(plan, 64, "fwd", interpret=True)
    k_compiled = tune.cache_key(plan, 64, "fwd", interpret=False)
    assert k_interp != k_compiled          # interpreter winners never leak
    assert k_here in (k_interp, k_compiled)


def test_variants_plan_with_dtype_override():
    from repro.core import variants
    base = make_plan(512, 128, kappa=2, s=2)
    sk = variants.BlockPermSketch(512, 128, plan=base, dtype="bfloat16")
    assert sk.plan.dtype == "bfloat16"
    # and the cost model reflects the halved input stream
    c16 = sk.cost_model(256).hbm_bytes
    c32 = variants.BlockPermSketch(512, 128, plan=base).cost_model(256).hbm_bytes
    assert c16 < c32


def test_autotune_plan_sweeps_block_rows():
    tune.clear_cache()
    plan, res = tune.autotune_plan(512, 128, 32, kappa=2, s=2, seed=4,
                                   iters=1, warmup=0, tns=(16, 32))
    assert res.block_rows == plan.Br
    assert res.tn in (16, 32)
    # the winning plan keeps the requested sketch semantics
    assert plan.k >= 128 and plan.kappa == 2 and plan.s == 2
    tune.clear_cache()
