"""Tests for the sketch lowering engine (``repro.kernels.lowering``).

Three layers:

  1. Golden snapshot — ``lower()`` over the full decision grid
     (op × impl × dtype × gather × batch × shard × ragged-n) serialized
     against ``tests/data/lowering_snapshot.json``.  ANY dispatch-behavior
     change shows up as an explicit diff of that file; regenerate with
     ``REGEN_LOWERING_SNAPSHOT=1 pytest tests/test_lowering.py`` after
     reviewing the diff.
  2. Cost consistency — ``sketch_model.cost_of(lowering)`` must agree with
     the legacy ``kernel_cost``/``dist_sketch_cost`` entry points on every
     grid point, so the modeled cost is provably computed from the record
     that launches.
  3. Engine unit tests — downgrade ladder, explain() traces, the memoized
     record cache and its tuner-generation invalidation, spec validation,
     and the unified tuner cache key (autotune_plan ↔ resolve_tn round
     trip, incl. batched shapes and JSON persistence).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.blockperm import make_plan
from repro.distributed import plan_for_mesh
from repro.kernels import lowering, ops, tune
from repro.roofline import sketch_model

SNAPSHOT = os.path.join(os.path.dirname(__file__), "data",
                        "lowering_snapshot.json")

# Decisions depend on the backend only through impl="auto" (and the tuner
# backend tag); the golden file is generated off-TPU, where CI runs.
pytestmark = pytest.mark.skipif(
    jax.default_backend() == "tpu",
    reason="golden lowering snapshot is generated for the off-TPU backends")


def _plans():
    return {
        # d == d_pad, everything fits: the no-downgrade grid
        "pinned": make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4),
        # stacked Φ busts VMEM at any tile: v2→v1 / gather-materialize rows
        "big": make_plan(65_536, 1024, kappa=4, s=2, block_rows=256),
        # P | M: the sharded rows
        "mesh": plan_for_mesh(4096, 1024, 4, kappa=2),
        # partial fits only below the requested tile: the shrink rung
        "mesh_shrink": plan_for_mesh(65_536, 1024, 8, kappa=4),
        # partial (Br, Bc) Φ tile busts VMEM: row-sharded oracle fallback
        "mesh_big": plan_for_mesh(262_144, 1024, 8, kappa=2),
        # global families (κ = M plans) through the SAME engine: the
        # competitor-family grid (no blockrow, no row-shard — both raise)
        "count": make_plan(256, 64, s=1, block_rows=8, seed=4,
                           family="countsketch"),
        "graph": make_plan(256, 64, s=4, block_rows=8, seed=4,
                           family="graph"),
    }


def _grid():
    """The full decision grid: (plan-name, LaunchSpec kwargs) cases."""
    cases = []
    # single-device product — op × impl × dtype × gather × batch × ragged-n.
    # The dtype axis walks the precision-policy registry: fp32 (None),
    # bf16, and the two fp8 stream formats (stochastic-rounding e4m3 plus
    # nearest e5m2), so every fp8 dispatch decision is snapshot-pinned.
    for op in lowering.OPS:
        for impl in lowering.IMPLS:
            for dtype in (None, "bfloat16", "fp8_e4m3_sr", "fp8_e5m2"):
                for gather in (False, True):
                    if gather and op not in lowering.GATHER_OPS:
                        continue
                    for batch in (1, 8):
                        for n in (64, 33):
                            cases.append(("pinned", dict(
                                op=op, n=n, impl=impl, dtype=dtype,
                                gather=gather, batch=batch)))
    # global families ride the single-device grid untouched: op × impl ×
    # dtype × gather × batch (+ one ragged-n point).  blockrow and
    # shard="row" are validation errors for them, not grid points.
    for plan_name in ("count", "graph"):
        for op in ("fwd", "transpose"):
            for impl in ("pallas", "xla"):
                for dtype in (None, "bfloat16", "fp8_e4m3_sr"):
                    for gather in (False, True):
                        if gather and op not in lowering.GATHER_OPS:
                            continue
                        for batch in (1, 8):
                            cases.append((plan_name, dict(
                                op=op, n=64, impl=impl, dtype=dtype,
                                gather=gather, batch=batch)))
        cases.append((plan_name, dict(op="fwd", n=33, impl="pallas")))
    # the downgrade ladder on the oversized plan
    for spec in (
        dict(op="fwd", n=8, impl="pallas"),               # v2 -> v1
        dict(op="fwd", n=8, impl="pallas", gather=True),  # materialize + v1
        dict(op="fwd", n=8, impl="pallas_v1", gather=True),
        dict(op="transpose", n=8, impl="pallas"),
        dict(op="blockrow", n=8, impl="pallas"),
    ):
        cases.append(("big", spec))
    # sharded rows
    for spec in (
        dict(op="fwd", n=64, impl="pallas", shard="row", devices=4),
        dict(op="fwd", n=33, impl="pallas", shard="row", devices=4),
        dict(op="fwd", n=64, impl="xla", shard="row", devices=4),
        dict(op="fwd", n=64, impl="auto", shard="row", devices=4),
        dict(op="blockrow", n=64, impl="pallas", shard="row", devices=4),
        dict(op="fwd", n=64, impl="pallas", shard="col", devices=4),
        dict(op="fwd", n=64, impl="pallas", shard="batch", devices=4,
             batch=8),
        dict(op="fwd", n=64, impl="pallas", shard="batch", devices=4,
             batch=8, gather=True),
    ):
        cases.append(("mesh", spec))
    # row-sharded explicit tile shrunk by the partial VMEM budget
    cases.append(("mesh_shrink", dict(op="fwd", n=64, impl="pallas",
                                      tn=512, shard="row", devices=8)))
    # row-sharded VMEM fallback to the oracle partial
    cases.append(("mesh_big", dict(op="fwd", n=8, impl="pallas",
                                   shard="row", devices=8)))
    return cases


def _lower_grid():
    tune.clear_cache()
    lowering.clear_lowering_cache()
    plans = _plans()
    out = []
    for plan_name, spec_kwargs in _grid():
        lw = lowering.lower(plans[plan_name],
                            lowering.LaunchSpec(**spec_kwargs))
        out.append({"plan": plan_name, "spec": spec_kwargs,
                    "lowering": lw.to_json()})
    return out


# ---------------------------------------------------------------------------
# 1. Golden snapshot
# ---------------------------------------------------------------------------

def test_lowering_snapshot_matches_golden():
    got = _lower_grid()
    if os.environ.get("REGEN_LOWERING_SNAPSHOT"):
        os.makedirs(os.path.dirname(SNAPSHOT), exist_ok=True)
        with open(SNAPSHOT, "w") as f:
            json.dump(got, f, indent=1, sort_keys=True)
        pytest.skip("snapshot regenerated — review the diff and commit")
    with open(SNAPSHOT) as f:
        want = json.load(f)
    # roundtrip got through JSON so tuple/list and int/float normalize
    got = json.loads(json.dumps(got, sort_keys=True))
    want_by_key = {(w["plan"], json.dumps(w["spec"], sort_keys=True)): w
                   for w in want}
    assert len(want) == len(got), (
        f"grid size changed: snapshot has {len(want)} cases, lower() "
        f"produced {len(got)} — regenerate with REGEN_LOWERING_SNAPSHOT=1")
    for g in got:
        key = (g["plan"], json.dumps(g["spec"], sort_keys=True))
        assert key in want_by_key, f"new grid case {key} not in snapshot"
        assert g["lowering"] == want_by_key[key]["lowering"], (
            f"dispatch behavior changed for {key}:\n"
            f"  was: {want_by_key[key]['lowering']}\n"
            f"  now: {g['lowering']}\n"
            f"If intended, regenerate with REGEN_LOWERING_SNAPSHOT=1.")


def test_engine_tiles_obey_the_chip_tile_rule():
    """Every tile the engine picks itself — heuristic, tuned, v1 default,
    the row-shard shrink — is a multiple of the 128-lane tiling or the
    launch's full column count (the chip's block rule), on every backend;
    gather tiles are always lane multiples (whole-tile row DMAs)."""
    plans = _plans()
    checked = 0
    for plan_name, spec_kwargs in _grid():
        lw = lowering.lower(plans[plan_name],
                            lowering.LaunchSpec(**spec_kwargs))
        if lw.tn is None or lw.tn_source.startswith("explicit"):
            continue
        assert lw.tn in tune.legal_tns(lw.n_eff, lw.variant), lw.describe()
        assert lw.tn % 128 == 0 or lw.tn == lw.n_eff, lw.describe()
        if lw.gather_fused:
            assert lw.tn % 128 == 0, lw.describe()
        checked += 1
    assert checked > 100


# ---------------------------------------------------------------------------
# 2. cost_of(lowering) == legacy cost entry points, every grid point
# ---------------------------------------------------------------------------

def test_cost_of_agrees_with_legacy_kernel_cost():
    plans = _plans()
    checked = 0
    for plan_name, spec_kwargs in _grid():
        lw = lowering.lower(plans[plan_name],
                            lowering.LaunchSpec(**spec_kwargs))
        if lw.shard == "row":
            if lw.op != "fwd":
                # dist_sketch_cost models the compact fwd partial only;
                # cost_of must keep refusing rather than invent 1/P terms
                with pytest.raises(ValueError):
                    sketch_model.cost_of(lw)
                continue
            want = sketch_model.dist_sketch_cost(
                lw.plan, lw.n_eff, lw.devices, variant=lw.op,
                tn=lw.tn if lw.tn is not None else 128)
        else:
            want = sketch_model.kernel_cost(
                lw.plan, lw.n_loc,
                version="v1" if lw.impl == "pallas_v1" else "v2",
                variant=lw.op,
                tn=lw.tn if lw.tn is not None else 128,
                gather=lw.gather_fused, batch=lw.batch_loc)
        got = sketch_model.cost_of(lw)
        assert got == want, (plan_name, spec_kwargs)
        checked += 1
    assert checked > 100          # the grid really was traversed


def test_cost_of_matches_family_cost_model_on_global_grid():
    """The registered family's ``cost_model`` and the engine's
    ``cost_of`` must price the SAME launch for the new global families,
    and the κ = M realization must charge the known closed forms:
    dense-like MXU work (2·k_pad·d_pad·n — every input block feeds every
    output block) and A streamed M times."""
    from repro.core.variants import SKETCH_FAMILIES
    for name in ("countsketch", "graph"):
        sk = SKETCH_FAMILIES[name](256, 64, seed=4, block_rows=8)
        p = sk.plan
        assert p.family == name and p.kappa == p.M
        for n in (8, 64, 33):
            lw = sk.lowering_for(n)
            kc = sketch_model.cost_of(lw)
            cm = sk.cost_model(n)
            assert cm.flops == kc.mxu_flops
            assert cm.hbm_bytes == kc.hbm_bytes
            assert not cm.materializes_S
            assert kc.mxu_flops == 2.0 * p.k_pad * p.d_pad * n
            assert kc.hbm_bytes >= p.stream_itemsize * p.M * p.d_pad * n


# ---------------------------------------------------------------------------
# 3. Engine behavior
# ---------------------------------------------------------------------------

def test_downgrade_v2_to_v1_recorded():
    plan = make_plan(65_536, 1024, kappa=4, s=2, block_rows=256)
    lw = lowering.lower(plan, lowering.LaunchSpec(op="fwd", n=8,
                                                  impl="pallas"))
    assert lw.impl == "pallas_v1" and lw.impl_requested == "pallas"
    assert lw.downgrade and "vmem" in lw.downgrade
    assert lw.tn_source == "v1_default"
    assert lw.version == "v1"


def test_downgrade_gather_materialized_recorded():
    plan = make_plan(65_536, 1024, kappa=4, s=2, block_rows=256)
    lw = lowering.lower(plan, lowering.LaunchSpec(
        op="fwd", n=8, impl="pallas", gather=True))
    assert lw.gather and not lw.gather_fused
    assert "materialized" in lw.downgrade
    # the materialized launch then rides the regular ladder down to v1
    assert lw.impl == "pallas_v1"
    assert lw.variant == "fwd"                 # the kernel that runs


def test_row_shard_vmem_fallback_to_oracle():
    plan = make_plan(262_144, 1024, kappa=2, block_rows=128)   # M=8
    assert not lowering.partial_fits_vmem(plan, 8)
    lw = lowering.lower(plan, lowering.LaunchSpec(
        op="fwd", n=8, impl="pallas", shard="row", devices=8))
    assert lw.impl == "xla" and lw.downgrade and "Φ tile" in lw.downgrade


def test_row_shard_tile_shrink_is_recorded():
    """An explicit tile shrunk by the partial VMEM budget must not be
    reported as 'the request ran as asked' (review finding)."""
    plan = make_plan(65_536, 1024, kappa=4, block_rows=128)    # M=8
    lw = lowering.lower(plan, lowering.LaunchSpec(
        op="fwd", n=64, impl="pallas", tn=512, shard="row", devices=8))
    if lw.tn == 512:
        pytest.skip("plan fits at the requested tile — nothing to record")
    assert lw.tn < 512
    assert "vmem_shrunk" in lw.tn_source
    assert lw.downgrade and "shrunk" in lw.downgrade


def test_lowering_cache_does_not_grow_across_generations():
    """Tuner mutations flush the memo instead of stranding dead entries
    keyed by old generations (review finding)."""
    tune.clear_cache()
    lowering.clear_lowering_cache()
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    spec = lowering.LaunchSpec(op="fwd", n=120, impl="pallas")
    for _ in range(5):
        lowering.lower(plan, spec)
        tune._bump_generation()
    lowering.lower(plan, spec)
    assert lowering.lowering_cache_size() == 1
    tune.clear_cache()


def test_interpret_mode_is_on_the_record():
    """Off the TPU a Pallas lowering interprets and says so; the oracle
    never claims to; describe() shows it."""
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    for impl in ("pallas", "pallas_v1"):
        lw = lowering.lower(plan, lowering.LaunchSpec(op="fwd", n=64,
                                                      impl=impl))
        assert lw.interpret and "interpret" in lw.describe()
        assert lw.to_json()["interpret"] is True
    lw = lowering.lower(plan, lowering.LaunchSpec(op="fwd", n=64))
    assert lw.impl == "xla" and not lw.interpret
    assert "interpret" not in lw.describe()


def test_no_downgrade_records_none():
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    lw = lowering.lower(plan, lowering.LaunchSpec(op="fwd", n=64,
                                                  impl="pallas"))
    assert lw.downgrade is None
    assert lw.impl == lw.impl_requested == "pallas"
    assert lw.pad_cols == 0


def test_explain_mentions_decisions():
    plan = make_plan(65_536, 1024, kappa=4, s=2, block_rows=256)
    txt = lowering.explain(plan, op="fwd", n=8, impl="pallas")
    assert "pallas_v1" in txt                  # the downgrade
    assert "vmem" in txt
    assert "Lowering(" in txt                  # the final record
    # rejected tile candidates show up for a heuristic resolution
    plan2 = make_plan(4096, 256, kappa=4, s=2)
    txt2 = lowering.explain(plan2, op="fwd", n=4096, impl="pallas")
    assert "tn:" in txt2


def test_lowering_cache_hits_and_tuner_invalidation():
    lowering.clear_lowering_cache()
    tune.clear_cache()
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    spec = lowering.LaunchSpec(op="fwd", n=200, impl="pallas")
    a = lowering.lower(plan, spec)
    b = lowering.lower(plan, spec)
    assert a is b                              # memoized record
    # a tuned winner landing bumps the generation and re-resolves
    key = tune.cache_key(plan, 200, "fwd")
    tune._CACHE[key] = tune.TuneResult(tn=128, time_us=1.0, source="tuned")
    tune._bump_generation()
    c = lowering.lower(plan, spec)
    assert c is not b and c.tn == 128 and c.tn_source == "tuned"
    tune.clear_cache()


def test_spec_validation():
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    mesh_plan = plan_for_mesh(4096, 1024, 4, kappa=2)
    bad = [
        dict(op="nope"),
        dict(impl="cuda"),
        dict(shard="diag"),
        dict(n=0),
        dict(batch=0),
        dict(tn=0),
        dict(op="transpose", gather=True),
        dict(shard="row", op="transpose", devices=4),
        dict(shard="row", gather=True, devices=4),
        dict(shard="row", impl="pallas_v1", devices=4),
        dict(shard="col", n=33, devices=4),
        dict(shard="batch", batch=6, devices=4),
    ]
    for kw in bad:
        with pytest.raises(ValueError):
            lowering.lower(mesh_plan if kw.get("shard") == "row" else plan,
                           lowering.LaunchSpec(**{"n": 64, **kw}))
    # row-sharding P must divide M
    with pytest.raises(ValueError, match="divide"):
        lowering.lower(plan, lowering.LaunchSpec(
            op="fwd", n=64, shard="row", devices=3))


def test_global_family_spec_validation():
    """Global families have no blockrow formulation and no compact
    row-sharded partial — the engine must refuse, not mislower."""
    cplan = make_plan(256, 64, s=1, block_rows=8, seed=4,
                      family="countsketch")
    with pytest.raises(ValueError, match="blockrow"):
        lowering.lower(cplan, lowering.LaunchSpec(op="blockrow", n=64))
    gplan = make_plan(4096, 1024, s=4, block_rows=256, seed=4,
                      family="graph")
    assert gplan.M % 4 == 0          # the divide check is not what fires
    with pytest.raises(ValueError, match="compact partial"):
        lowering.lower(gplan, lowering.LaunchSpec(
            op="fwd", n=64, shard="row", devices=4))
    # col/batch sharding needs no partial reduction — still allowed
    lw = lowering.lower(gplan, lowering.LaunchSpec(
        op="fwd", n=64, shard="col", devices=4))
    assert lw.shard == "col"


def test_tuner_cache_key_distinguishes_families():
    """Identical geometry, different family ⇒ different tuner key: a
    blockperm winner must never be served to a countsketch launch."""
    bp = make_plan(256, 64, kappa=8, s=1, block_rows=8, seed=4)
    cs = make_plan(256, 64, s=1, block_rows=8, seed=4,
                   family="countsketch")
    geom = lambda p: (p.d_pad, p.k_pad, p.M, p.Br, p.kappa, p.s, p.dtype)
    assert geom(bp) == geom(cs)      # the families differ ONLY by family
    assert tune.cache_key(bp, 64, "fwd") != tune.cache_key(cs, 64, "fwd")


def test_execute_guards():
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    A = jnp.zeros((256, 16), jnp.float32)
    lw = lowering.lower(plan, lowering.LaunchSpec(op="fwd", n=16,
                                                  impl="xla"))
    with pytest.raises(ValueError, match="non-gather"):
        lowering.execute(lw, A, row_index=jnp.zeros((256,), jnp.int32))
    lwg = lowering.lower(plan, lowering.LaunchSpec(
        op="fwd", n=16, impl="xla", gather=True))
    with pytest.raises(ValueError, match="row_index"):
        lowering.execute(lwg, A)
    with pytest.raises(ValueError, match="plan.d"):
        lowering.execute(lwg, A, row_index=jnp.zeros((100,), jnp.int32))
    mesh_plan = plan_for_mesh(4096, 1024, 4, kappa=2)
    lwr = lowering.lower(mesh_plan, lowering.LaunchSpec(
        op="fwd", n=16, impl="xla", shard="row", devices=4))
    with pytest.raises(ValueError, match="shard"):
        lowering.execute(lwr, A)


def test_ops_entry_points_route_through_engine(monkeypatch, rng):
    """Every public apply goes through lower(): the structural guarantee
    behind 'no inline dispatch in ops'."""
    specs = []
    orig = lowering.lower

    def spy(plan, spec):
        specs.append(spec)
        return orig(plan, spec)

    monkeypatch.setattr(lowering, "lower", spy)
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    A = jnp.asarray(rng.normal(size=(256, 16)), jnp.float32)
    Y = jnp.asarray(rng.normal(size=(plan.k, 16)), jnp.float32)
    idx = jnp.arange(256, dtype=jnp.int32)
    ops.sketch_apply(plan, A, "pallas", 8)
    ops.sketch_apply_t(plan, Y, "pallas", 8)
    ops.blockrow_apply(plan, A, "pallas", 8)
    ops.sketch_apply(plan, A, "pallas", 8, row_index=idx)
    ops.sketch_apply_batched(plan, A[None], "pallas")
    ops.sketch_vectors(plan, A.T, "pallas")
    assert len(specs) >= 6
    assert {s.op for s in specs} == {"fwd", "transpose", "blockrow"}


# ---------------------------------------------------------------------------
# 4. Unified tuner cache key (satellite): autotune_plan ↔ resolve_tn,
#    batched shapes, JSON round trip.
# ---------------------------------------------------------------------------

def test_autotune_plan_winner_served_to_batched_resolve(monkeypatch):
    tune.clear_cache()

    def fake_autotune(plan, n, variant="fwd", batch=1, **kw):
        res = tune.TuneResult(tn=64, time_us=1.0, source="tuned")
        tune._CACHE[tune.cache_key(plan, n, variant, batch=batch)] = res
        tune._bump_generation()
        return res

    monkeypatch.setattr(tune, "autotune", fake_autotune)
    B = 16
    plan, res = tune.autotune_plan(512, 128, 4, kappa=2, s=2, batch=B)
    # the winner must be visible through the SAME key builder the readers
    # use — batched consult included
    assert tune.resolve_tn(plan, 4, "fwd", batch=B) == res.tn == 64
    hit = tune.lookup(plan, 4, "fwd", batch=B)
    assert hit is not None and hit.tn == 64
    tune.clear_cache()


def test_batched_cache_key_roundtrips_through_json(tmp_path):
    tune.clear_cache()
    plan = make_plan(512, 128, kappa=2, s=2, block_rows=32, seed=1)
    key = tune.cache_key(plan, 4, "fwd_gather", batch=16)
    tune._CACHE[key] = tune.TuneResult(tn=128, time_us=2.5, source="tuned")
    path = str(tmp_path / "cache.json")
    assert tune.save_cache(path) == 1
    tune.clear_cache()
    gen_before = tune.cache_generation()
    assert tune.load_cache(path) == 1
    assert tune.cache_generation() > gen_before   # loaders invalidate
    assert tune.resolve_tn(plan, 4, "fwd_gather", batch=16) == 128
    hit = tune.lookup(plan, 4, "fwd_gather", batch=16)
    assert hit.source == "loaded"
    tune.clear_cache()


def test_lowering_sees_freshly_loaded_winner(tmp_path):
    """End-to-end: a JSON-shipped winner must flow through lookup() into
    fresh Lowering records (the generation-keyed memo must not serve the
    pre-load record)."""
    tune.clear_cache()
    lowering.clear_lowering_cache()
    plan = make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)
    spec = lowering.LaunchSpec(op="fwd", n=300, impl="pallas")
    before = lowering.lower(plan, spec)
    assert before.tn_source == "heuristic" and before.tn != 128
    tune._CACHE[tune.cache_key(plan, 300, "fwd")] = tune.TuneResult(
        tn=128, time_us=1.0, source="tuned")
    path = str(tmp_path / "cache.json")
    tune.save_cache(path)
    tune.clear_cache()
    tune.load_cache(path)
    after = lowering.lower(plan, spec)
    assert after.tn == 128 and after.tn_source == "loaded"
    tune.clear_cache()
