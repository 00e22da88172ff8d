"""Guarded sketching tests (PR-6 acceptance set).

Four layers:
  * report/policy units — verdict ordering, HealthReport supersede
    semantics, the deterministic escalation-ladder attempt sequence;
  * guards on manufactured artifacts — every injector class from
    ``repro.health.inject`` must be DETECTED by its guard (NaN operand,
    bad-draw input, corrupt tuner cache, psum corruption, VMEM overflow);
  * recovery — the redraw ladder converges on the adversarially coherent
    input within the escalation budget, deterministically across runs; the
    Cholesky→QR factor downgrade rescues a rank-deficient Gram; corrupted
    caches fall back to the heuristic; non-finite gradient rows are
    quarantined out of the GraSS feature cache;
  * integration — ``sketch_precondition_lstsq(guard=True)`` still
    converges on well-posed problems with attempts == 1, ``HealthReport``
    counters appear on ``SolveResult`` and in ``explain()`` output, and
    the whole injector suite passes end to end.
"""
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.blockperm import make_plan
from repro.health import guards, inject, report
from repro.health.policy import Attempt, RedrawPolicy
from repro.kernels import lowering, ops, tune
from repro.solvers.sketch_precondition import sketch_precondition_lstsq


# ---------------------------------------------------------------------------
# report / policy units
# ---------------------------------------------------------------------------

def test_worst_status_ordering():
    assert report.worst_status() == report.HEALTHY
    assert report.worst_status("healthy", "degraded") == report.DEGRADED
    assert report.worst_status("degraded", "failed", "healthy") == report.FAILED
    with pytest.raises(ValueError):
        report.worst_status("fine")


def test_health_report_supersede_semantics():
    """A recovered artifact's later finding supersedes the bad draw: the
    report's status reflects the ACCEPTED state, the history stays."""
    rpt = report.HealthReport(op="t")
    rpt.add(report.GuardFinding("isometry", "SA", report.FAILED))
    assert rpt.status == report.FAILED
    rpt.act("redraw(seed=1)")
    rpt.add(report.GuardFinding("isometry", "SA", report.HEALTHY))
    assert rpt.status == report.HEALTHY
    assert len(rpt.findings) == 2 and rpt.actions == ["redraw(seed=1)"]
    j = rpt.to_json()
    assert j["counters"]["isometry.failed"] == 1
    assert j["counters"]["isometry.healthy"] == 1


def test_global_counters_roundtrip():
    report.reset_counters()
    report.record("guard.test.failed", detail="x")
    report.record("guard.test.failed")
    assert report.counters() == {"guard.test.failed": 2}
    assert ("guard.test.failed", "x") in report.recent_events()
    assert json.loads(report.counters_json()) == {"guard.test.failed": 2}
    assert "guard.test.failed=2" in report.summarize_counters()
    report.reset_counters()
    assert report.summarize_counters() == "no events recorded"


def test_policy_attempt_sequence_deterministic():
    pol = RedrawPolicy(max_redraws=2, max_kappa_bumps=1, max_sampling_bumps=1)
    seq1 = list(pol.attempts(seed=7, kappa=2, sampling_factor=4.0))
    seq2 = list(pol.attempts(seed=7, kappa=2, sampling_factor=4.0))
    assert seq1 == seq2                       # pure function of the knobs
    assert len(seq1) == pol.budget == 5
    assert [a.action for a in seq1] == [
        "initial", "redraw", "redraw", "kappa_bump", "sampling_bump"]
    assert seq1[0] == Attempt(0, "initial", 7, 2, 4.0)
    # every non-initial attempt uses a FRESH derived seed
    seeds = [a.seed for a in seq1]
    assert len(set(seeds)) == len(seeds)
    assert seq1[3].kappa == 4 and seq1[4].sampling_factor == 8.0


def test_policy_kappa_cap_and_plan_sizing():
    pol = RedrawPolicy(max_redraws=0, max_kappa_bumps=3, kappa_cap=8,
                       max_sampling_bumps=0)
    seq = list(pol.attempts(seed=0, kappa=4, sampling_factor=4.0))
    # 4 -> 8, then capped: only one bump possible
    assert [a.kappa for a in seq] == [4, 8]
    # sampling_bump attempts ignore an explicit k and grow the sketch
    pol2 = RedrawPolicy(max_redraws=0, max_kappa_bumps=0,
                        max_sampling_bumps=1)
    init, bump = pol2.attempts(seed=0, kappa=2, sampling_factor=4.0)
    p0 = pol2.plan_for(init, 512, 16, s=2, k=80)
    p1 = pol2.plan_for(bump, 512, 16, s=2, k=80)
    assert p0.k_req == 80 and p1.k_req == 128      # 8.0 * 16
    assert p1.seed != p0.seed


# ---------------------------------------------------------------------------
# guards: every injector class is DETECTED
# ---------------------------------------------------------------------------

def test_finite_guard_detects_injected_nan_and_inf(rng):
    clean = rng.normal(size=(16, 8)).astype(np.float32)
    assert guards.finite_guard(clean).status == report.HEALTHY
    bad = inject.inject_nan(clean, count=5, seed=3)
    f = guards.finite_guard(bad, "operand")
    assert f.status == report.FAILED and f.value == 5.0
    # deterministic: same (array, seed) poisons the same entries
    assert np.array_equal(np.isnan(bad),
                          np.isnan(inject.inject_nan(clean, count=5, seed=3)))
    f2 = guards.finite_guard(
        inject.inject_nan(clean, count=1, seed=0, value=float("inf")))
    assert f2.status == report.FAILED


def test_guards_skip_under_tracer():
    """Guards return None (check skipped) inside jit instead of crashing —
    guarded entry points stay jit-safe, they just lose coverage there."""
    seen = []

    @jax.jit
    def f(x):
        seen.append(guards.finite_guard(x))
        seen.append(guards.isometry_guard(x, x))
        seen.append(guards.r_condition_guard(x))
        return x

    f(jnp.eye(4))
    assert seen == [None, None, None]


def test_annihilated_direction_is_exact(rng):
    plan = make_plan(512, 64, kappa=1, s=1, seed=0)
    x = inject.annihilated_direction(plan)
    assert np.linalg.norm(x) == pytest.approx(1.0)
    Sx = np.asarray(ops.sketch_apply(plan, jnp.asarray(x[:, None]), "xla"))
    assert np.all(Sx == 0.0)                   # exactly, not approximately
    # a fresh draw breaks the collision: the redraw rung works by design
    plan2 = make_plan(512, 64, kappa=1, s=1, seed=1)
    Sx2 = np.asarray(ops.sketch_apply(plan2, jnp.asarray(x[:, None]), "xla"))
    assert np.linalg.norm(Sx2) > 0.5
    # and a kappa bump defeats it too (collision must repeat at every level)
    plan4 = make_plan(512, 64, kappa=2, s=1, seed=0)
    Sx4 = np.asarray(ops.sketch_apply(plan4, jnp.asarray(x[:, None]), "xla"))
    assert np.linalg.norm(Sx4) > 0.5


def test_bad_draw_detected_by_isometry_and_ose(rng):
    plan = make_plan(512, 64, kappa=1, s=1, seed=0)
    A = inject.adversarial_input(plan, 8, seed=0)
    SA = np.asarray(ops.sketch_apply(plan, jnp.asarray(A), "xla"))
    assert guards.isometry_guard(A, SA).status == report.FAILED
    assert guards.ose_probe(plan, A, impl="xla").status == report.FAILED
    R = ops.triangular_factor(jnp.asarray(SA))
    assert guards.r_condition_guard(R).status == report.FAILED
    # a healthy draw on the same input classifies healthy
    plan2 = make_plan(512, 64, kappa=2, s=2, seed=1)
    SA2 = np.asarray(ops.sketch_apply(plan2, jnp.asarray(A), "xla"))
    assert guards.isometry_guard(A, SA2).status == report.HEALTHY
    pr = guards.ose_probe(plan2, A, impl="xla")
    assert pr.status in (report.HEALTHY, report.DEGRADED)


def test_r_condition_guard_bands():
    R = jnp.diag(jnp.asarray([1.0, 1e-3]))
    assert guards.r_condition_guard(R).status == report.HEALTHY
    R = jnp.diag(jnp.asarray([1.0, 1e-8]))
    assert guards.r_condition_guard(R).status == report.DEGRADED
    R = jnp.diag(jnp.asarray([1.0, 0.0]))
    assert guards.r_condition_guard(R).status == report.FAILED
    R = jnp.asarray([[1.0, jnp.nan], [0.0, 1.0]])
    assert guards.r_condition_guard(R).status == report.FAILED


def test_replica_consistency_detects_all_corruption_modes(rng):
    base = rng.normal(size=(6, 4)).astype(np.float32)
    good = [base.copy() for _ in range(4)]
    assert guards.replica_consistency_guard(good).status == report.HEALTHY
    for mode in ("zero", "permute", "scale"):
        bad = inject.corrupt_replica(good, slot=2, mode=mode, seed=1)
        f = guards.replica_consistency_guard(bad)
        assert f.status == report.FAILED, mode
        # the originals were not modified
        assert np.array_equal(good[2], base)
    # single replica is trivially consistent
    assert guards.replica_consistency_guard([base]).status == report.HEALTHY


def test_vmem_overflow_forces_downgrade_and_counts():
    report.reset_counters()
    lowering.clear_lowering_cache()
    plan, spec = inject.vmem_overflow_request()
    lw = lowering.lower(plan, spec)
    assert lw.downgrade and "vmem" in lw.downgrade
    assert report.counters().get("lowering.downgrade", 0) >= 1
    # the downgrade shows up in explain() on its counters line
    txt = lowering.explain(plan, spec)
    assert "lowering.downgrade" in txt and "counters:" in txt


# ---------------------------------------------------------------------------
# recovery: the ladder, the factor downgrade, the cache fallback
# ---------------------------------------------------------------------------

def test_redraw_ladder_recovers_adversarial_input():
    """Draw #1 fails the OSE probe; the policy converges within the
    escalation budget, deterministically across runs (satellite c)."""
    plan = make_plan(512, 64, kappa=1, s=1, seed=0)
    A = jnp.asarray(inject.adversarial_input(plan, 8, seed=0))
    b = A @ jnp.ones(8, jnp.float32)
    pol = RedrawPolicy(max_redraws=2, max_kappa_bumps=1, max_sampling_bumps=1)

    def run():
        return sketch_precondition_lstsq(
            A, b, k=plan.k_req, kappa=1, s=1, seed=0, impl="xla",
            guard=True, policy=pol, probe=True, tol=1e-5)

    res = run()
    rpt = res.health
    assert rpt is not None and rpt.op == "sketch_precondition_lstsq"
    # draw #1 failed the ground-truth OSE probe...
    first_probe = next(f for f in rpt.findings if f.guard == "ose_probe")
    assert first_probe.status == report.FAILED
    # ...the ladder recovered within budget, and the solve converged
    assert 1 < rpt.attempts <= pol.budget
    assert rpt.status in (report.HEALTHY, report.DEGRADED)
    assert res.converged and res.relres <= 1e-5
    assert any(a.startswith("redraw") for a in rpt.actions)
    # counters surface on the result
    assert rpt.counters()["attempts"] == rpt.attempts
    # deterministic: identical escalation path and verdicts on a re-run
    res2 = run()
    assert res2.health.actions == rpt.actions
    assert [f.status for f in res2.health.findings] == \
        [f.status for f in rpt.findings]
    assert np.allclose(np.asarray(res2.x), np.asarray(res.x))


def test_guarded_solve_accepts_healthy_draw_first_try(rng):
    """On a well-posed problem the guards cost a verdict, not a redraw —
    and the answer matches the unguarded path exactly (same plan)."""
    A = jnp.asarray(rng.normal(size=(1024, 16)), jnp.float32)
    b = A @ jnp.asarray(rng.normal(size=(16,)), jnp.float32)
    res_g = sketch_precondition_lstsq(A, b, seed=3, impl="xla", guard=True)
    res_u = sketch_precondition_lstsq(A, b, seed=3, impl="xla")
    assert res_g.health.attempts == 1 and not res_g.health.actions
    assert res_g.health.status in (report.HEALTHY, report.DEGRADED)
    assert res_g.converged and res_u.converged
    assert np.array_equal(np.asarray(res_g.x), np.asarray(res_u.x))
    assert res_u.health is None


def test_guarded_solve_cg_and_chol_paths(rng):
    A = jnp.asarray(rng.normal(size=(1024, 16)), jnp.float32)
    b = A @ jnp.asarray(rng.normal(size=(16,)), jnp.float32)
    res = sketch_precondition_lstsq(A, b, seed=1, impl="xla", guard=True,
                                    method="cg", factorization="chol")
    assert res.converged and res.health.attempts == 1


def test_chol_fallback_on_rank_deficient_gram():
    """factorization='chol' on a rank-deficient sketch silently yields NaN
    factors; the eager path must detect and downgrade to QR (satellite b)."""
    report.reset_counters()
    # duplicated columns -> exactly singular Gram -> NaN Cholesky
    col = np.arange(1.0, 65.0, dtype=np.float32)
    SA = jnp.asarray(np.stack([col, col, 2 * col], axis=1))
    assert not np.all(np.isfinite(
        np.asarray(jnp.linalg.cholesky(SA.T @ SA))))   # the failure is real
    with pytest.warns(RuntimeWarning, match="non-finite"):
        R = ops.triangular_factor(SA, "chol")
    assert np.all(np.isfinite(np.asarray(R)))          # rescued via QR
    assert report.counters().get("factor.chol_downgrade") == 1
    # the QR fallback is the same factor the qr path produces
    assert np.allclose(np.asarray(R),
                       np.asarray(ops.triangular_factor(SA, "qr")))
    # under jit the values are unreadable: no crash, caller keeps chol
    jitted = jax.jit(lambda m: ops.triangular_factor(m, "chol"))
    _ = jitted(SA)                                     # must not raise


def test_load_cache_survives_corruption(tmp_path):
    """Corrupted/truncated cache JSON warns and falls back instead of
    raising (satellite a)."""
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    for mode in ("truncate", "garbage", "bad_entry"):
        path = str(tmp_path / f"cache_{mode}.json")
        tune.clear_cache()
        tune._CACHE[tune.cache_key(plan, 64, "fwd")] = tune.TuneResult(
            tn=32, time_us=1.0, source="tuned")
        tune.save_cache(path)
        inject.corrupt_cache_file(path, mode)
        tune.clear_cache()
        report.reset_counters()
        with pytest.warns(RuntimeWarning):
            n = tune.load_cache(path)
        assert n == 0, mode
        assert report.counters().get("tune.cache_corrupt", 0) >= 1, mode
        # the tuner still resolves tiles (heuristic fallback)
        assert tune.resolve_tn(plan, 64, "fwd") >= 1
    # a missing file is the same non-event
    with pytest.warns(RuntimeWarning):
        assert tune.load_cache(str(tmp_path / "nope.json")) == 0
    tune.clear_cache()


def test_load_cache_keeps_good_rows_alongside_bad(tmp_path):
    """Row-level corruption skips the bad rows and keeps the good ones."""
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    path = str(tmp_path / "cache.json")
    tune.clear_cache()
    tune._CACHE[tune.cache_key(plan, 64, "fwd")] = tune.TuneResult(
        tn=32, time_us=1.0, source="tuned")
    tune.save_cache(path)
    with open(path) as f:
        payload = json.load(f)
    payload["[broken"] = {"tn": "not an int"}
    payload['["x"]'] = {"no_tn": True}
    with open(path, "w") as f:
        json.dump(payload, f)
    tune.clear_cache()
    report.reset_counters()
    with pytest.warns(RuntimeWarning, match="malformed"):
        assert tune.load_cache(path) == 1          # the good row survived
    hit = tune.lookup(plan, 64, "fwd")
    assert hit is not None and hit.tn == 32
    assert report.counters()["tune.cache_corrupt"] == 2
    tune.clear_cache()


def test_save_cache_is_atomic(tmp_path):
    """save_cache never leaves a partial file: the payload appears via
    rename, and no tmp droppings survive."""
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    path = str(tmp_path / "cache.json")
    tune.clear_cache()
    tune._CACHE[tune.cache_key(plan, 64, "fwd")] = tune.TuneResult(
        tn=32, time_us=1.0, source="tuned")
    assert tune.save_cache(path) == 1
    assert [p.name for p in tmp_path.iterdir()] == ["cache.json"]
    with open(path) as f:
        json.load(f)                               # complete, valid JSON
    tune.clear_cache()
    assert tune.load_cache(path) == 1
    tune.clear_cache()


def test_grass_quarantines_nonfinite_gradient_rows():
    """A NaN-poisoned example is zeroed out of the feature cache and
    counted — it cannot poison its chunk's feature block."""
    from repro.attribution import mlp as mlp_lib
    from repro.attribution.grass import GrassPipeline, GrassPipelineConfig

    mcfg = mlp_lib.MLPConfig(d_in=16, hidden=(8,), steps=3)
    xs, ys = mlp_lib.make_synthetic_mnist(12, 16, mcfg.n_classes, seed=0)
    params = mlp_lib.train_mlp(mcfg, xs, ys)
    cfg = GrassPipelineConfig(sparse_dim=64, sketch_dim=16, chunk=4)
    pipe = GrassPipeline(cfg, params)
    clean = np.asarray(pipe.featurize(xs, ys))
    assert pipe.quarantined == 0

    report.reset_counters()
    x_bad = np.array(xs)
    x_bad[5] = np.nan                              # poison one example
    feats = np.asarray(pipe.featurize(jnp.asarray(x_bad), ys))
    assert pipe.quarantined == 1
    assert report.counters()["grass.quarantined"] == 1
    assert np.all(feats[5] == 0.0)                 # quarantined row
    assert np.all(np.isfinite(feats))              # nothing leaked
    mask = np.ones(12, bool)
    mask[5] = False
    assert np.allclose(feats[mask], clean[mask], atol=1e-6)
    rpt = pipe.health()
    assert rpt.quarantined == 1 and rpt.status == report.DEGRADED
    # build_cache counts through the same path
    pipe2 = GrassPipeline(cfg, params)
    cache, _ = pipe2.build_cache(jnp.asarray(x_bad), ys, batch=8)
    assert pipe2.quarantined == 1 and cache.shape == (12, 16)


# ---------------------------------------------------------------------------
# integration: explain() surface + the whole injector suite
# ---------------------------------------------------------------------------

def test_explain_includes_health_counters():
    report.reset_counters()
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    txt = lowering.explain(plan, op="fwd", n=8, impl="pallas")
    assert "counters: no events recorded" in txt
    report.record("guard.finite.failed")
    txt = lowering.explain(plan, op="fwd", n=8, impl="pallas")
    assert "guard.finite.failed=1" in txt
    report.reset_counters()


def test_injector_suite_end_to_end(tmp_path):
    """The CI fault-injection gate: every injector detected + recovered,
    counters JSON written."""
    out = str(tmp_path / "HEALTH_counters.json")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        rc = inject.run_injector_suite(out=out, verbose=False)
    assert rc == 0
    with open(out) as f:
        payload = json.load(f)
    assert payload["ok"] is True
    assert set(payload["injectors"]) == {
        "nan_operand_detected", "inf_output_detected", "bad_draw_detected",
        "bad_draw_recovered", "corrupt_cache_recovered",
        "psum_corruption_detected", "vmem_overflow_downgraded"}
    assert all(v == "detected" for v in payload["injectors"].values())
    assert payload["counters"].get("policy.redraw", 0) >= 1
