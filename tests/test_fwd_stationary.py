"""The input-stationary forward kernel and the rung that selects it.

``flashsketch_pallas_stationary`` reads each block of A once and contracts
it against its κ Φ tiles stacked; the per-level results land in
write-once slots that are folded in ℓ order, so the output equals the
output-stationary kernel's bit for bit.  The output-stationary kernel is
forced through the rung's own predicate (``lowering.stationary_fits``).

On the CPU the interpreter's dot rounds a 64-row product of a 512-deep
chunk differently from four 16-row ones (d=4096, k=128, B_r=16, B_c=512:
5.7e-6 apart), so the bitwise sweep keeps ``B_c`` below 512; the
reference comparison covers that shape.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.blockperm import (STATIONARY_VARIANT, VMEM_BUDGET_BYTES,
                                  fused_variant_bytes, make_plan)
from repro.health import report as health_report
from repro.kernels import lowering, ops, ref as kref, tune

SWEEP = [
    # (d, k, kappa, s, block_rows, n, tn, dtype)
    (256, 64, 1, 1, 8, 16, 16, None),
    (256, 64, 2, 2, 8, 33, 16, None),
    (300, 96, 2, 1, 16, 37, 16, None),
    (512, 128, 4, 2, 16, 40, 16, None),
    (1000, 256, 4, 2, 8, 128, 128, None),
    (512, 128, 4, 2, 16, 40, 16, "bfloat16"),
    (300, 96, 2, 2, 16, 37, 16, "bfloat16"),
    (512, 128, 4, 1, 16, 40, 16, "fp8_e4m3_sr"),
]


@pytest.fixture
def fresh_memo():
    lowering.clear_lowering_cache()
    tune.clear_cache()
    yield
    lowering.clear_lowering_cache()
    tune.clear_cache()


def _output_stationary(monkeypatch):
    monkeypatch.setattr(lowering, "stationary_fits", lambda *a, **k: False)
    lowering.clear_lowering_cache()


@pytest.mark.parametrize("d,k,kappa,s,br,n,tn,dtype", SWEEP)
def test_stationary_bit_exact_to_output_stationary(d, k, kappa, s, br, n,
                                                   tn, dtype, rng,
                                                   fresh_memo, monkeypatch):
    plan = make_plan(d, k, kappa=kappa, s=s, block_rows=br, seed=d + n)
    A = jnp.asarray(rng.normal(size=(d, n)), jnp.float32)
    spec = lowering.LaunchSpec(op="fwd", n=n, impl="pallas", tn=tn,
                               dtype=dtype)
    assert lowering.lower(plan, spec).stationary
    new = ops.sketch_apply(plan, A, "pallas", tn, dtype)
    _output_stationary(monkeypatch)
    assert not lowering.lower(plan, spec).stationary
    old = ops.sketch_apply(plan, A, "pallas", tn, dtype)
    assert np.array_equal(np.asarray(new), np.asarray(old))


@pytest.mark.parametrize("d,k,br,n,tn", [
    (1000, 256, 32, 40, 8),
    (4096, 128, 16, 300, 128),     # B_c = 512: two column tiles, ragged
])
def test_stationary_matches_reference(d, k, br, n, tn, rng, fresh_memo):
    plan = make_plan(d, k, kappa=4, s=2, block_rows=br, seed=9)
    A = jnp.asarray(rng.normal(size=(d, n)), jnp.float32)
    assert lowering.lower(plan, lowering.LaunchSpec(
        op="fwd", n=n, impl="pallas", tn=tn)).stationary
    np.testing.assert_allclose(
        np.asarray(ops.sketch_apply(plan, A, "pallas", tn)),
        np.asarray(kref.flashsketch_ref(plan, A)), atol=1e-5, rtol=1e-5)


def test_stationary_batched_and_vjp(rng, fresh_memo):
    """The batched fold and the transpose's VJP (a forward) take the rung
    and agree with the reference."""
    plan = make_plan(512, 128, kappa=4, s=2, block_rows=16, seed=3)
    As = jnp.asarray(rng.normal(size=(3, 512, 24)), jnp.float32)
    lw = lowering.lower(plan, lowering.LaunchSpec(op="fwd", n=24,
                                                  impl="pallas", batch=3))
    assert lw.stationary and lw.n_eff == 72
    Ys = ops.sketch_apply_batched(plan, As, "pallas")
    for b in range(3):
        np.testing.assert_allclose(
            np.asarray(Ys[b]), np.asarray(kref.flashsketch_ref(plan, As[b])),
            atol=1e-5, rtol=1e-5)
    import jax
    Y = jnp.asarray(rng.normal(size=(plan.k, 24)), jnp.float32)
    W = jnp.asarray(rng.normal(size=(512, 24)), jnp.float32)
    g = jax.grad(lambda y: jnp.sum(ops.sketch_apply_t(plan, y, "pallas")
                                   * W))(Y)
    np.testing.assert_allclose(np.asarray(g),
                               np.asarray(kref.flashsketch_ref(plan, W)),
                               atol=1e-5, rtol=1e-5)


def test_rung_picks_the_kernel_by_what_it_observes(fresh_memo):
    def lw(plan, op="fwd", n=64, **kw):
        return lowering.lower(plan, lowering.LaunchSpec(
            op=op, n=n, impl="pallas", **kw))

    perm = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    taken = lw(perm)
    assert taken.stationary and taken.variant == STATIONARY_VARIANT
    assert "input-stationary" in taken.describe()
    assert taken.vmem_bytes == tune._vmem_footprint(
        perm, taken.tn, STATIONARY_VARIANT)
    # the blockrow op (iid wiring), a global family, the transpose and the
    # fused gather keep their kernels
    assert not lw(perm, op="blockrow").stationary
    assert not lw(perm, op="transpose").stationary
    assert not lw(perm, gather=True).stationary
    count = make_plan(256, 64, s=1, block_rows=8, seed=4,
                      family="countsketch")
    assert not lw(count).stationary
    # B_r below Φ's sublane tile: bf16 packs 16 rows a tile
    assert not lw(perm, dtype="bfloat16").stationary
    # the working set does not grow with n ...
    assert lw(perm, n=1 << 20).stationary
    # ... but the (κ, M, B_r, tn) slots grow with k: at k = 16384 they
    # cannot fit at any tile
    big_k = _big_k_plan()
    assert not lowering.stationary_fits(big_k, 64)
    wide = lw(big_k)
    assert not wide.stationary and wide.variant == "fwd"
    assert wide.downgrade is None


def _big_k_plan():
    return make_plan(65_536, 16_384, kappa=4, s=2, seed=4)


def test_counter_counts_records_that_took_the_rung(fresh_memo):
    perm = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    fwd = [lowering.LaunchSpec(op="fwd", n=n, impl="pallas")
           for n in (8, 64, 33)]
    launches = [(perm, s) for s in fwd]
    launches += [(perm, lowering.LaunchSpec(op=op, n=64, impl="pallas"))
                 for op in ("transpose", "blockrow")]
    launches.append((_big_k_plan(), fwd[1]))
    before = health_report.counters().get("lowering.fwd_input_stationary", 0)
    records = [lowering.lower(p, s) for p, s in launches]
    for p, s in launches:                 # memo hits: no new records
        lowering.lower(p, s)
    after = health_report.counters().get("lowering.fwd_input_stationary", 0)
    assert after - before == sum(r.stationary for r in records) == 3
    assert "fwd: input-stationary kernel" in lowering.explain(perm, fwd[1])


def test_output_stationary_tile_is_not_served_to_the_stationary_kernel(
        fresh_memo):
    perm = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    spec = lowering.LaunchSpec(op="fwd", n=300, impl="pallas")
    tune._CACHE[tune.cache_key(perm, 300, "fwd")] = tune.TuneResult(
        tn=128, time_us=1.0, source="tuned")
    tune._bump_generation()
    lw = lowering.lower(perm, spec)
    assert lw.stationary and lw.tn_source == "heuristic" and lw.tn != 128


def test_stationary_working_set_needs_the_block_count():
    """Its slots and output block span every output block: sized without
    M, the figure would be far too low, so it is refused."""
    with pytest.raises(ValueError):
        fused_variant_bytes(4, 16, 4096, 128, variant=STATIONARY_VARIANT)
    assert (fused_variant_bytes(4, 16, 4096, 128, variant=STATIONARY_VARIANT,
                                M=64)
            > fused_variant_bytes(4, 16, 4096, 128, variant=STATIONARY_VARIANT,
                                  M=1))


@pytest.mark.parametrize("n", [600, 1024, 4096])
def test_cells_plan_fits_at_every_n(n, fresh_memo):
    """At the apply cells' plan the input-stationary working set does not
    depend on n: every n of the paper's grid takes the rung at the same
    tile, inside the budget."""
    plan = make_plan(262_144, 1024, kappa=4, s=2, seed=0)
    lw = lowering.lower(plan, lowering.LaunchSpec(op="fwd", n=n,
                                                  impl="pallas"))
    assert lw.stationary and lw.tn == 512, lw.describe()
    assert lw.vmem_bytes <= VMEM_BUDGET_BYTES


def test_cached_winner_over_the_budget_is_not_served(fresh_memo):
    """A loaded or tuned tile whose working set is over the budget (a
    cache saved under another budget, or damaged) falls back to the
    heuristic, and ``explain`` says why."""
    plan = make_plan(262_144, 1024, kappa=4, s=2, seed=0)
    assert (tune._vmem_footprint(plan, 1024, STATIONARY_VARIANT)
            > VMEM_BUDGET_BYTES)
    tune._CACHE[tune.cache_key(plan, 1024, STATIONARY_VARIANT)] = \
        tune.TuneResult(tn=1024, time_us=1.0, source="loaded")
    tune._bump_generation()
    spec = lowering.LaunchSpec(op="fwd", n=1024, impl="pallas")
    lw = lowering.lower(plan, spec)
    assert lw.stationary and lw.tn_source == "heuristic" and lw.tn == 512
    assert lw.vmem_bytes <= VMEM_BUDGET_BYTES
    assert "loaded winner 1024 needs" in lowering.explain(plan, spec)
