"""Regression tests for the PR-4 planner/dispatch bugfix sweep.

Covers the three fixes:
  1. ``make_plan(block_rows=)`` honors the pin (or raises) — no silent
     clamp — and ``autotune_plan`` dedupes its sweep by effective (M, Br).
  2. The gather-fused path never pads the (d_src, n) HBM operand at
     ragged ``n`` (the ragged last tile is handled in-kernel).
  3. ``sketch_vectors`` threads tn/dtype and resolves its tile via the
     SAME batched tuner shape class as ``sketch_apply_batched``.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.blockperm import make_plan
from repro.kernels import lowering, ops, tune


# ---------------------------------------------------------------------------
# Fix 1: block_rows pin
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block_rows,k,want_br", [
    (2048, 1024, 2048),   # the ISSUE's verified silent-clamp case (was 256)
    (256, 64, 256),       # (was 16)
    (8, 64, 8),           # small pin, honored as before
    (100, 1024, 128),     # non-pow2 pin rounds UP, then honored
])
def test_make_plan_honors_block_rows_pin(block_rows, k, want_br):
    plan = make_plan(4096, k, kappa=4, s=2, block_rows=block_rows)
    assert plan.Br == want_br
    assert plan.k_pad == plan.M * plan.Br >= k
    assert plan.M >= 4          # kappa <= M stays realizable


def test_make_plan_pin_roundtrip_distinct_grids():
    """Doubling the pin must produce a DIFFERENT effective grid — the
    property autotune_plan's Br sweep relies on."""
    base = make_plan(4096, 1024, kappa=4, s=2, block_rows=1024)
    doubled = make_plan(4096, 1024, kappa=4, s=2, block_rows=2048)
    assert (base.M, base.Br) != (doubled.M, doubled.Br)
    assert doubled.Br == 2 * base.Br


def test_make_plan_unrealizable_pin_raises():
    with pytest.raises(ValueError, match="not realizable"):
        make_plan(256, 64, kappa=2, s=3, block_rows=8)   # 3 does not divide 8


def test_make_plan_auto_path_unchanged():
    """The auto (unpinned) planner still picks the PR-1 grids."""
    plan = make_plan(4096, 1024, kappa=4, s=2)
    assert plan.Br <= 256 and plan.k_pad >= 1024
    assert plan.M >= plan.kappa


def test_autotune_plan_dedupes_by_effective_grid(monkeypatch):
    timed = []

    def fake_autotune(plan, n, variant="fwd", **kw):
        timed.append((plan.M, plan.Br))
        return tune.TuneResult(tn=8, time_us=float(len(timed)),
                               source="tuned")

    monkeypatch.setattr(tune, "autotune", fake_autotune)
    # 24 and 32 both round to Br=32 -> one timing; 64 is distinct
    tune.autotune_plan(512, 128, 16, kappa=2, s=2,
                       block_rows_candidates=[24, 32, 64])
    assert len(timed) == len(set(timed)) == 2


def test_autotune_plan_default_sweep_has_no_duplicates(monkeypatch):
    timed = []

    def fake_autotune(plan, n, variant="fwd", **kw):
        timed.append((plan.M, plan.Br))
        return tune.TuneResult(tn=8, time_us=1.0, source="tuned")

    monkeypatch.setattr(tune, "autotune", fake_autotune)
    plan, res = tune.autotune_plan(4096, 1024, 16, kappa=1, s=2)
    assert len(timed) == len(set(timed)) == 3   # Br/2, Br, Br*2 all distinct
    assert res.block_rows == plan.Br


def test_autotune_plan_skips_kpad_inflating_candidates(monkeypatch):
    """With the pin honored, a Br*2 candidate can inflate k_pad when M is
    at the kappa floor — such plans sketch a DIFFERENT object and must not
    compete on raw launch time."""
    timed = []

    def fake_autotune(plan, n, variant="fwd", **kw):
        timed.append(plan.k_pad)
        return tune.TuneResult(tn=8, time_us=1.0, source="tuned")

    monkeypatch.setattr(tune, "autotune", fake_autotune)
    # kappa=4: base is (M=4, Br=256, k_pad=1024); br=512 would give
    # (M=4, Br=512, k_pad=2048) -> skipped
    plan, _ = tune.autotune_plan(4096, 1024, 16, kappa=4, s=2)
    assert timed and all(kp == 1024 for kp in timed)
    assert plan.k_pad == 1024


# ---------------------------------------------------------------------------
# Fix 2: ragged-n gather path never pads the source operand
# ---------------------------------------------------------------------------

def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                yield from _all_eqns(v.jaxpr)


@pytest.mark.parametrize("dtype", [None, "bfloat16"])
@pytest.mark.parametrize("n", [33, 17, 7])
def test_gather_ragged_n_bit_exact(n, dtype, rng):
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    A = jnp.asarray(rng.normal(size=(700, n)), jnp.float32)
    idx = jnp.asarray(np.sort(rng.choice(700, 256, replace=False)), jnp.int32)
    fused = ops.sketch_apply(plan, A, "pallas", 16, dtype, row_index=idx)
    ref = ops.sketch_apply(plan, A[idx], "pallas", 16, dtype)
    assert fused.shape == (plan.k, n)
    assert np.array_equal(np.asarray(fused), np.asarray(ref))
    fb = ops.blockrow_apply(plan, A, "pallas", 16, dtype, row_index=idx)
    rb = ops.blockrow_apply(plan, A[idx], "pallas", 16, dtype)
    assert np.array_equal(np.asarray(fb), np.asarray(rb))


def test_gather_ragged_n_jaxpr_has_no_full_A_pad(rng):
    """The no-A-copy contract, checked structurally: at ragged n the jaxpr
    of the fused gather contains NO pad of the (d_src, n) operand."""
    d_src, n = 700, 33
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    A = jnp.asarray(rng.normal(size=(d_src, n)), jnp.float32)
    idx = jnp.asarray(np.sort(rng.choice(d_src, 256, replace=False)),
                      jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda X: ops.sketch_apply(plan, X, "pallas", 16, row_index=idx))(A)
    offending = [
        e for e in _all_eqns(jaxpr.jaxpr)
        if e.primitive.name == "pad"
        and any(getattr(v.aval, "shape", None) == (d_src, n)
                for v in e.invars)
    ]
    assert not offending, offending


def _column_pads(jaxpr, n):
    """pad eqns that widen a width-``n`` operand's column axis — the
    padded-copy pattern the ragged-n fix removed."""
    return [
        e for e in _all_eqns(jaxpr)
        if e.primitive.name == "pad"
        and any(getattr(v.aval, "shape", (0, 0))[-1:] == (n,)
                for v in e.invars)
        and e.outvars[0].aval.shape[-1] > n
    ]


@pytest.mark.parametrize("impl", ["pallas", "pallas_v1"])
def test_apply_ragged_n_jaxpr_has_no_column_pad(impl, rng):
    """sketch_apply / sketch_apply_t / blockrow_apply at ragged n must not
    materialize a column-padded copy of the operand (the remainder tile is
    handled in-kernel, like the gather path).  d == d_pad here, so the
    pallas fwd/blockrow traces contain no pad of the operand AT ALL."""
    n = 33
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    assert plan.d == plan.d_pad                      # no row pad either
    A = jnp.asarray(rng.normal(size=(256, n)), jnp.float32)
    Y = jnp.asarray(rng.normal(size=(plan.k, n)), jnp.float32)
    for fn, op in [
        (lambda X: ops.sketch_apply(plan, X, impl, 16), A),
        (lambda X: ops.sketch_apply_t(plan, X, impl, 16), Y),
        (lambda X: ops.blockrow_apply(plan, X, impl, 16), A),
    ]:
        jaxpr = jax.make_jaxpr(fn)(op)
        offending = _column_pads(jaxpr.jaxpr, n)
        assert not offending, offending


@pytest.mark.parametrize("dtype", [None, "bfloat16"])
@pytest.mark.parametrize("n", [33, 17, 7])
def test_apply_ragged_n_matches_oracle(n, dtype, rng):
    """Ragged-n v2/v1 launches agree with the xla oracle on every variant
    (the in-kernel edge tile must be value-identical to the old padded
    launch, whose outputs were sliced back to n)."""
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    A = jnp.asarray(rng.normal(size=(256, n)), jnp.float32)
    Y = jnp.asarray(rng.normal(size=(plan.k, n)), jnp.float32)
    for fwd, op in [(ops.sketch_apply, A), (ops.sketch_apply_t, Y),
                    (ops.blockrow_apply, A)]:
        ref = fwd(plan, op, "xla", None, dtype)
        for impl in ("pallas", "pallas_v1"):
            got = fwd(plan, op, impl, 16, dtype)
            assert got.shape == ref.shape
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       atol=1e-4, rtol=1e-4)


def test_gather_ragged_n_vjp(rng):
    """The scatter VJP survives the ragged-tile path."""
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    A = jnp.asarray(rng.normal(size=(700, 13)), jnp.float32)
    idx = jnp.asarray(np.sort(rng.choice(700, 256, replace=False)), jnp.int32)
    W = jnp.asarray(rng.normal(size=(plan.k, 13)), jnp.float32)
    g_fused = jax.grad(lambda A_: jnp.sum(
        W * ops.sketch_apply(plan, A_, "pallas", 16, row_index=idx)))(A)
    g_ref = jax.grad(lambda A_: jnp.sum(
        W * ops.sketch_apply(plan, A_[idx], "xla")))(A)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# Fix 3: sketch_vectors == sketch_apply_batched tile resolution
# ---------------------------------------------------------------------------

def _record_lowerings(monkeypatch):
    """Spy on the engine: every LaunchSpec resolved through lower()."""
    calls = []
    orig = lowering.lower

    def spy(plan, spec):
        calls.append(spec)
        return orig(plan, spec)

    monkeypatch.setattr(lowering, "lower", spy)
    return calls


@pytest.mark.parametrize("use_gather", [False, True])
def test_sketch_vectors_resolves_like_batched(use_gather, monkeypatch, rng):
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    B = 6
    if use_gather:
        x = jnp.asarray(rng.normal(size=(B, 700)), jnp.float32)
        idx = jnp.asarray(np.sort(rng.choice(700, 256, replace=False)),
                          jnp.int32)
    else:
        x = jnp.asarray(rng.normal(size=(B, 256)), jnp.float32)
        idx = None
    calls = _record_lowerings(monkeypatch)
    ops.sketch_vectors(plan, x, "pallas", row_index=idx)
    v_specs = [s for s in calls if s.batch > 1]
    calls.clear()
    ops.sketch_apply_batched(plan, x[:, :, None], "pallas", row_index=idx)
    b_specs = [s for s in calls if s.batch > 1]
    # identical batched LaunchSpec: per-matrix width 1, batch folded over
    # B, same gather flag — the two entry points CANNOT resolve different
    # launches because they lower the same spec through the same engine
    assert len(v_specs) == len(b_specs) == 1
    assert v_specs[0] == b_specs[0] == lowering.LaunchSpec(
        op="fwd", n=1, impl="pallas", gather=use_gather, batch=B)


def test_sketch_vectors_threads_tn_and_dtype(rng):
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    x = jnp.asarray(rng.normal(size=(5, 256)), jnp.float32)
    y = ops.sketch_vectors(plan, x, "pallas", 8, "bfloat16")
    want = ops.sketch_apply(plan, x.T, "pallas", 8, "bfloat16").T
    assert np.array_equal(np.asarray(y), np.asarray(want))
    # and the bf16 stream actually changes the result vs fp32
    y32 = ops.sketch_vectors(plan, x, "pallas", 8)
    assert not np.array_equal(np.asarray(y), np.asarray(y32))


def test_sketch_vectors_uses_batched_cache_winner(monkeypatch, rng):
    """A tuned winner cached under the batched shape class must be served
    to BOTH batch entry points."""
    tune.clear_cache()
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    B = 300
    # the shape class of the forward kernel these calls run
    variant = lowering.lower(plan, lowering.LaunchSpec(
        op="fwd", n=1, impl="pallas", batch=B)).variant
    key = tune.cache_key(plan, 1, variant, batch=B)
    tune._CACHE[key] = tune.TuneResult(tn=128, time_us=1.0, source="tuned")
    tune._bump_generation()
    try:
        seen = []
        orig = lowering.execute

        def spy(lw, operand, row_index=None):
            seen.append(lw.tn)
            return orig(lw, operand, row_index=row_index)

        monkeypatch.setattr(lowering, "execute", spy)
        x = jnp.asarray(rng.normal(size=(B, 256)), jnp.float32)
        ops.sketch_vectors(plan, x, "pallas")
        ops.sketch_apply_batched(plan, x[:, :, None], "pallas")
        assert seen == [128, 128]
    finally:
        tune.clear_cache()
