"""The executor memo of ``lowering.execute``: a concrete (eager) call runs
one compiled program per record and operand shape, built on its first
call and reused after; traced calls keep the inline launch."""
import contextlib
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.blockperm import make_plan
from repro.health import report
from repro.kernels import flashsketch as fsk
from repro.kernels import lowering, ops, tune

BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
EXEC_COUNTERS = ("lowering.exec_memo_hit", "lowering.exec_memo_miss")


@pytest.fixture(scope="module")
def plan():
    return make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)


@pytest.fixture(autouse=True)
def _fresh():
    lowering.clear_lowering_cache()
    report.reset_counters()
    yield
    lowering.clear_lowering_cache()


def _exec_counters():
    snap = report.counters()
    return {k: snap.get(k, 0) for k in EXEC_COUNTERS}


def _operand(rows, n=128, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (rows, n)), jnp.float32)


@contextlib.contextmanager
def _jax_events():
    """Counts JAX's trace and build events while the block runs."""
    seen = {"traces": 0, "builds": 0}

    def listen(event, duration, **kw):
        if event == TRACE_EVENT:
            seen["traces"] += 1
        elif event == BUILD_EVENT:
            seen["builds"] += 1
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


@contextlib.contextmanager
def _launch_names(monkeypatch):
    """The ``pallas_call`` names traced while the block runs."""
    names = []
    orig = fsk._launch

    def spy(name, *args, **kwargs):
        names.append(name)
        return orig(name, *args, **kwargs)
    monkeypatch.setattr(fsk, "_launch", spy)
    yield names


def test_second_eager_call_is_a_hit_without_trace_or_build(plan,
                                                           monkeypatch):
    A = _operand(plan.d)
    ops.sketch_apply(plan, A, "pallas").block_until_ready()
    assert _exec_counters() == {"lowering.exec_memo_hit": 0,
                                "lowering.exec_memo_miss": 1}
    assert lowering.executor_cache_size() == 1
    with _launch_names(monkeypatch) as names, _jax_events() as seen:
        ops.sketch_apply(plan, A, "pallas").block_until_ready()
    assert _exec_counters() == {"lowering.exec_memo_hit": 1,
                                "lowering.exec_memo_miss": 1}
    assert names == []
    assert seen == {"traces": 0, "builds": 0}
    assert "lowering.exec_memo_hit=1" in lowering.explain(
        plan, n=128, impl="pallas")


def _fwd(plan, X, impl):
    return ops.sketch_apply(plan, X, impl)


def _transpose(plan, X, impl):
    return ops.sketch_apply_t(plan, X, impl)


def _blockrow(plan, X, impl):
    return ops.blockrow_apply(plan, X, impl)


def _rows(plan):
    return jnp.asarray(np.random.default_rng(1).permutation(300)[:plan.d],
                       jnp.int32)


def _gather(plan, X, impl):
    return ops.sketch_apply_indexed(plan, X, _rows(plan), impl)


def _blockrow_gather(plan, X, impl):
    return ops.blockrow_apply(plan, X, impl, row_index=_rows(plan))


CASES = {
    # entry, operand rows (None: plan.d), kernel name
    "fwd": (_fwd, None, "flashsketch_fwd"),
    "transpose": (_transpose, "k", "flashsketch_transpose"),
    "gather": (_gather, 300, "flashsketch_fwd_gather"),
    "blockrow": (_blockrow, None, "flashsketch_blockrow"),
    "blockrow_gather": (_blockrow_gather, 300, "flashsketch_blockrow_gather"),
}


def _case_operand(plan, rows):
    return _operand({None: plan.d, "k": plan.k}.get(rows, rows))


@pytest.mark.parametrize("case", sorted(CASES))
def test_eager_equals_jitted_bit_for_bit(plan, case):
    fn, rows, _ = CASES[case]
    X = _case_operand(plan, rows)
    want = np.asarray(jax.jit(lambda x: fn(plan, x, "pallas"))(X))
    assert _exec_counters() == {"lowering.exec_memo_hit": 0,
                                "lowering.exec_memo_miss": 0}
    miss = np.asarray(fn(plan, X, "pallas"))
    hit = np.asarray(fn(plan, X, "pallas"))
    assert _exec_counters() == {"lowering.exec_memo_hit": 1,
                                "lowering.exec_memo_miss": 1}
    np.testing.assert_array_equal(miss, want)
    np.testing.assert_array_equal(hit, want)


@pytest.mark.parametrize("case,impl", [(c, "pallas") for c in sorted(CASES)]
                         + [("fwd", "pallas_v1"), ("transpose", "pallas_v1"),
                            ("blockrow", "pallas_v1")])
def test_hit_span_names_the_kernel_the_miss_traced(plan, case, impl,
                                                   monkeypatch):
    """The span a hit opens is the one its kernel's launch opens."""
    fn, rows, kernel = CASES[case]
    X = _case_operand(plan, rows)
    with _launch_names(monkeypatch) as names:
        fn(plan, X, impl)
    want = kernel + ("_v1" if impl == "pallas_v1" else "")
    assert names == [want]
    (key,) = lowering._EXEC_MEMO
    assert lowering._kernel_name(key[0]) == want


def test_a_new_shape_or_dtype_is_a_miss(plan):
    A = _operand(plan.d)
    ops.sketch_apply(plan, A, "pallas")
    ops.sketch_apply(plan, A[:, :96], "pallas")
    ops.sketch_apply(plan, A.astype(jnp.bfloat16), "pallas")
    assert _exec_counters() == {"lowering.exec_memo_hit": 0,
                                "lowering.exec_memo_miss": 3}
    assert lowering.executor_cache_size() == 3
    ops.sketch_apply(plan, A + 1.0, "pallas")
    assert _exec_counters()["lowering.exec_memo_hit"] == 1


def test_a_new_value_is_computed_anew(plan):
    A, B = _operand(plan.d, seed=0), _operand(plan.d, seed=1)
    ya = np.asarray(ops.sketch_apply(plan, A, "pallas"))
    yb = np.asarray(ops.sketch_apply(plan, B, "pallas"))
    np.testing.assert_allclose(yb, np.asarray(ops.sketch_apply(
        plan, B, "xla")), rtol=1e-5, atol=1e-5)
    assert not np.array_equal(ya, yb)


def test_traced_calls_bypass_the_memo(plan):
    A = _operand(plan.d)
    f = jax.jit(lambda a: ops.sketch_apply(plan, a, "pallas"))
    f(A).block_until_ready()
    text = f.lower(A).as_text(debug_info=True)
    assert _exec_counters() == {"lowering.exec_memo_hit": 0,
                                "lowering.exec_memo_miss": 0}
    assert lowering.executor_cache_size() == 0
    for name in ("repro.ops.sketch_apply", "repro.lowering.execute",
                 "repro.kernel.flashsketch_fwd"):
        assert name in text, name


def test_the_xla_oracle_runs_inline(plan):
    ops.sketch_apply(plan, _operand(plan.d), "xla")
    assert _exec_counters() == {"lowering.exec_memo_hit": 0,
                                "lowering.exec_memo_miss": 0}
    assert lowering.executor_cache_size() == 0


def test_clear_lowering_cache_empties_the_memo(plan):
    ops.sketch_apply(plan, _operand(plan.d), "pallas")
    assert lowering.executor_cache_size() == 1
    lowering.clear_lowering_cache()
    assert lowering.executor_cache_size() == 0


def test_a_tuner_generation_bump_empties_the_memo(plan):
    A = _operand(plan.d)
    ops.sketch_apply(plan, A, "pallas")
    assert lowering.executor_cache_size() == 1
    tune._bump_generation()
    ops.sketch_apply(plan, A, "pallas")      # lower() flushes, then misses
    assert _exec_counters() == {"lowering.exec_memo_hit": 0,
                                "lowering.exec_memo_miss": 2}
    assert lowering.executor_cache_size() == 1


def test_threads_launching_one_record(plan):
    """Concurrent launches of one record each get the right answer, and
    every call is counted once (a lost update would break the sum)."""
    A = _operand(plan.d)
    want = np.asarray(jax.jit(lambda a: ops.sketch_apply(plan, a, "pallas"))(
        A))
    workers, calls = 8, 3
    start = threading.Barrier(workers)
    out, errors = [], []

    def run():
        try:
            start.wait()
            for _ in range(calls):
                out.append(np.asarray(ops.sketch_apply(plan, A, "pallas")))
        except Exception as e:        # pragma: no cover - the failure path
            errors.append(e)

    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=run) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        sys.setswitchinterval(prev)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert len(out) == workers * calls
    for got in out:
        np.testing.assert_array_equal(got, want)
    assert sum(_exec_counters().values()) == workers * calls
    assert lowering.executor_cache_size() == 1


def test_sharded_records_are_not_executed(plan):
    lw = lowering.lower(plan, lowering.LaunchSpec(
        op="fwd", n=128, impl="pallas", shard="col", devices=2))
    with pytest.raises(ValueError, match="single-device"):
        lowering.execute(lw, _operand(plan.d))
    assert lowering.executor_cache_size() == 0
