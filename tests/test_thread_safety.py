"""Concurrency regression tests for the tuner cache and lowering memo.

The serving layer loads/saves/consults the tuner cache and resolves
lowerings from worker threads.  Without the RLock guards these hammers
reliably die with ``RuntimeError: dictionary changed size during
iteration`` (``save_cache`` iterating ``_CACHE`` while ``load_cache``
inserts) or serve stale-tile lowering records across a generation flush.
"""
import json
import threading

import pytest

from repro.core.blockperm import STATIONARY_VARIANT, make_plan
from repro.kernels import lowering, tune


def _cache_file(tmp_path, plans, n=256, tn=128,
                variants=("fwd", "transpose")):
    """A valid tuner-cache JSON with one row per (plan, variant)."""
    payload = {}
    for plan in plans:
        for variant in variants:
            key = tune.cache_key(plan, n, variant)
            payload[json.dumps(list(key))] = {
                "tn": tn, "block_rows": None, "time_us": 1.0,
                "source": "tuned"}
    path = tmp_path / "winners.json"
    path.write_text(json.dumps(payload))
    return str(path)


def _hammer(workers, iters=60):
    """Run each worker fn iters times on its own thread; re-raise the
    first exception any of them hit."""
    errors = []

    def run(fn):
        try:
            for _ in range(iters):
                fn()
        except Exception as e:        # pragma: no cover - the failure path
            errors.append(e)

    threads = [threading.Thread(target=run, args=(fn,)) for fn in workers]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    if errors:
        raise errors[0]


@pytest.fixture(autouse=True)
def _fresh_caches():
    tune.clear_cache()
    lowering.clear_lowering_cache()
    yield
    tune.clear_cache()
    lowering.clear_lowering_cache()


def test_tuner_cache_concurrent_load_save_clear(tmp_path):
    # vary the SHAPE CLASS (cache_key ignores the seed): d × k × κ
    plans = [make_plan(d, k, kappa=kp, s=2)
             for d in (128, 256, 512) for k in (32, 64)
             for kp in (1, 2, 4)]
    src = _cache_file(tmp_path, plans)
    dst = str(tmp_path / "out.json")
    gen0 = tune.cache_generation()

    _hammer([
        lambda: tune.load_cache(src),
        lambda: tune.load_cache(src, merge=False),
        lambda: tune.save_cache(dst),
        lambda: tune.clear_cache(),
        lambda: [tune.lookup(p, 256, "fwd") for p in plans],
    ])

    # the registry is still coherent: a final load serves every winner
    tune.clear_cache()
    kept = tune.load_cache(src)
    assert kept == 2 * len(plans)
    for plan in plans:
        hit = tune.lookup(plan, 256, "fwd")
        assert hit is not None and hit.tn == 128 and hit.source == "loaded"
    # every mutation bumped the generation (atomically with its flush)
    assert tune.cache_generation() > gen0


def test_lowering_memo_concurrent_with_generation_flushes(tmp_path):
    plans = [make_plan(512, 64, kappa=2, s=2, seed=sd) for sd in range(6)]
    # rows for both forwards: these plans take the input-stationary one
    src = _cache_file(tmp_path, plans, tn=128,
                      variants=("fwd", STATIONARY_VARIANT, "transpose"))
    # impl="pallas": the auto path lowers to the tile-less xla oracle on
    # CPU; the pallas (interpret-mode) path exercises tile resolution
    specs = [lowering.LaunchSpec(op="fwd", n=256, impl="pallas", batch=b)
             for b in (1, 4)]

    def lower_all():
        for plan in plans:
            for spec in specs:
                lw = lowering.lower(plan, spec)
                assert lw.tn >= 1

    _hammer([
        lower_all,
        lower_all,
        lambda: tune.load_cache(src),     # bumps the generation → flush
        lambda: tune.clear_cache(),       # bumps it again
    ])

    # post-condition: with the tuned winners loaded last, the memo serves
    # the tuned tile (no stale record survived the flush races)
    tune.clear_cache()
    lowering.clear_lowering_cache()
    tune.load_cache(src)
    for plan in plans:
        assert lowering.lower(plan, specs[0]).tn == 128


def test_save_cache_snapshot_under_concurrent_insert(tmp_path):
    """save_cache must iterate a SNAPSHOT: concurrent inserts used to
    raise 'dictionary changed size during iteration'."""
    plans = [make_plan(256, 8 * (i + 1), kappa=1, s=1) for i in range(16)]
    src = _cache_file(tmp_path, plans)
    tune.load_cache(src)
    dst = str(tmp_path / "snap.json")

    _hammer([
        lambda: tune.save_cache(dst),
        lambda: tune.load_cache(src),
        lambda: tune.load_cache(src, merge=False),
    ], iters=120)

    # the atomically-replaced file is always a complete valid cache
    assert tune.load_cache(dst) > 0
