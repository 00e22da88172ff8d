"""Deterministic tests for the resilient sketch server.

Every scenario runs on a ``ManualClock`` — arrivals, deadlines, backoff
and breaker cool-downs are all virtual time, so overload/fault replays
are exact and instant.  The one threaded test at the bottom exercises
the real async driver.
"""
import numpy as np
import pytest

from repro.health import report as health_report
from repro.health.inject import adversarial_input, inject_nan
from repro.kernels import ops
from repro.serving import (DEADLINE, DEGRADED, FAILED, OK, SHED,
                           CircuitBreaker, DegradeLadder, ManualClock,
                           SketchRequest, SketchServer, ThreadedServer)
from repro.serving import degrade

D, N, K = 128, 16, 32
PARAMS = dict(d=D, k=K, kappa=2, s=2, seed=11)
ADV_PARAMS = dict(d=D, k=K, kappa=1, s=1, seed=11)   # injectable plans


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def _operand(rng, n=N):
    return rng.standard_normal((D, n)).astype(np.float32)


def _server(**kw):
    kw.setdefault("clock", ManualClock())
    kw.setdefault("max_batch", 4)
    kw.setdefault("batch_wait_s", 0.01)
    return SketchServer(**kw)


def _req(rng, *, params=PARAMS, operand=None, **kw):
    return SketchRequest(tenant=kw.pop("tenant", "t"), kind="sketch",
                         operand=_operand(rng) if operand is None
                         else operand,
                         plan_params=dict(params), **kw)


def _serve_one(srv, req):
    ticket = srv.submit(req)
    if not isinstance(ticket, int):
        return ticket
    # 2× the window: exactly 1× can fall a float-ulp short after the
    # clock has accumulated many advances
    srv.clock.advance(2 * srv.batcher.batch_wait_s)
    srv.run_pending()
    resp = srv.poll(ticket)
    assert resp is not None
    return resp


# -- healthy path ----------------------------------------------------------

def test_healthy_response_bitwise_equals_direct_apply(rng):
    srv = _server()
    A = _operand(rng)
    resp = _serve_one(srv, _req(rng, operand=A))
    assert resp.status == OK and not resp.flagged and resp.attempts == 1
    plan = srv.plans.resolve("t", PARAMS)
    direct = np.asarray(ops.sketch_apply(plan, A))
    assert np.array_equal(resp.result, direct)


def test_coalescing_one_launch_per_plan_shape(rng):
    srv = _server(max_batch=8)
    same = [_req(rng) for _ in range(3)]
    other = _req(rng, params=dict(PARAMS, seed=99))
    tickets = [srv.submit(r) for r in same + [other]]
    srv.clock.advance(0.02)
    srv.run_pending()
    resps = [srv.poll(t) for t in tickets]
    assert [r.batch_size for r in resps] == [3, 3, 3, 1]
    # coalesced results match the per-request direct launch bit-for-bit
    plan = srv.plans.resolve("t", PARAMS)
    for r, req in zip(resps[:3], same):
        assert np.array_equal(r.result,
                              np.asarray(ops.sketch_apply(
                                  plan, req.operand)))


def test_sketch_response_carries_the_served_lowering(rng):
    """Each coalesced response names the launch that produced it: the
    group's batched lowering, one record for the whole group."""
    srv = _server(max_batch=4)
    tickets = [srv.submit(_req(rng)) for _ in range(3)]
    srv.clock.advance(0.02)
    srv.run_pending()
    lws = [srv.poll(t).lowering for t in tickets]
    assert all(lw is lws[0] for lw in lws)
    assert lws[0].batch == 3 and lws[0].op == "fwd"
    assert lws[0].plan == srv.plans.resolve("t", PARAMS)


def test_solve_request_served_healthy(rng):
    srv = _server()
    A = _operand(rng, n=8)
    x_true = rng.standard_normal(8).astype(np.float32)
    req = SketchRequest(tenant="t", kind="solve", operand=A, rhs=A @ x_true,
                        plan_params=dict(d=D, k=K, kappa=2, s=2, seed=3))
    resp = _serve_one(srv, req)
    assert resp.status == OK
    assert resp.result.converged
    np.testing.assert_allclose(np.asarray(resp.result.x), x_true,
                               rtol=1e-3, atol=1e-3)


# -- admission / overload --------------------------------------------------

def test_overload_sheds_with_recorded_findings(rng):
    srv = _server(max_queue=4)
    tickets = [srv.submit(_req(rng)) for _ in range(7)]
    shed = [t for t in tickets if not isinstance(t, int)]
    assert len(shed) == 3
    for resp in shed:
        assert resp.status == SHED
        assert resp.health is not None
        assert any(f.guard == "admission" for f in resp.health.findings)
        assert resp.flagged
    assert srv.stats()["shed"] == 3
    assert health_report.counters().get("serve.reject.shed") == 3


def test_hopeless_deadline_rejected_at_admission(rng):
    srv = _server(service_estimate_s=0.05)
    resp = srv.submit(_req(rng, deadline_s=0.01))
    assert not isinstance(resp, int) and resp.status == DEADLINE
    assert health_report.counters().get("serve.reject.deadline") == 1


def test_deadline_expired_in_queue(rng):
    srv = _server(batch_wait_s=0.01)
    ticket = srv.submit(_req(rng, deadline_s=0.02))
    assert isinstance(ticket, int)
    srv.clock.advance(0.05)            # past the deadline before dispatch
    srv.run_pending()
    resp = srv.poll(ticket)
    assert resp.status == DEADLINE and resp.result is None


def test_backpressure_and_degrade_ladder_recorded(rng):
    srv = _server(max_queue=8, max_batch=8)
    tickets = [srv.submit(_req(rng)) for _ in range(8)]
    assert srv.stats()["backpressure"] == 1.0
    assert srv.ladder.level == len(degrade.RUNGS)      # every rung engaged
    srv.run_pending()                  # rung 1 collapses the window: due now
    resps = [srv.poll(t) for t in tickets]
    assert all(r is not None for r in resps)
    for r in resps:
        assert r.status == DEGRADED    # precision rung is a real downgrade
        # the dtype rungs collapse to the deepest engaged one: exactly ONE
        # dtype finding per response, and at full backpressure it is fp8
        dtype_findings = [f for f in r.health.findings
                         if f.guard == "degrade" and f.target == "dtype"]
        assert len(dtype_findings) == 1
        assert "fp8" in dtype_findings[0].detail
        assert r.flagged
    counts = health_report.counters()
    assert counts.get("serve.degrade.dtype") == 1      # once per dispatch
    assert counts.get("serve.ladder.up", 0) >= 1       # one per level step


# -- fault paths -----------------------------------------------------------

def test_nan_operand_fails_fast_without_retries(rng):
    srv = _server()
    A = np.asarray(inject_nan(_operand(rng), count=3, seed=0))
    resp = _serve_one(srv, _req(rng, operand=A))
    assert resp.status == FAILED and resp.flagged
    assert resp.attempts == 1          # unrecoverable: ladder not spent
    assert "unrecoverable_operand" in resp.health.actions
    assert any(f.guard == "finite" and f.target == "operand"
               and f.status == "failed" for f in resp.health.findings)


def test_adversarial_input_recovers_via_redraw(rng):
    srv = _server()
    plan = srv.plans.resolve("t", ADV_PARAMS)
    A = np.asarray(adversarial_input(plan, N, seed=1))
    resp = _serve_one(srv, _req(rng, params=ADV_PARAMS, operand=A))
    assert resp.status == DEGRADED and resp.flagged
    assert resp.attempts >= 2
    assert any(a.startswith("redraw") for a in resp.health.actions)
    # the recovered draw is actually usable
    assert np.all(np.isfinite(resp.result))
    ratio = np.linalg.norm(resp.result) / np.linalg.norm(A)
    assert abs(ratio - 1.0) < 0.9


def test_deadline_exhausted_redraw_returns_least_bad(rng):
    # backoff (0.1s) cannot fit the 50ms deadline budget: the ladder must
    # stop before its first rung and serve the least-bad (initial) draw
    srv = _server(backoff_base_s=0.1)
    plan = srv.plans.resolve("t", ADV_PARAMS)
    A = np.asarray(adversarial_input(plan, N, seed=2))
    resp = _serve_one(srv, _req(rng, params=ADV_PARAMS, operand=A,
                                deadline_s=0.05))
    assert resp.status == FAILED and resp.flagged
    assert resp.attempts == 1
    assert "escalation_budget_exhausted" in resp.health.actions
    assert resp.result is not None     # least-bad draw, explicitly flagged
    assert health_report.counters().get(
        "serve.escalation_budget_exhausted") == 1


def test_breaker_trips_suppresses_retries_then_recovers(rng):
    clock = ManualClock()
    srv = _server(clock=clock,
                  breaker=CircuitBreaker(fail_threshold=2, cooldown_s=1.0))
    plan = srv.plans.resolve("t", ADV_PARAMS)

    def adversarial_resp(seed):
        A = np.asarray(adversarial_input(plan, N, seed=seed))
        return _serve_one(srv, _req(rng, params=ADV_PARAMS, operand=A))

    # the breaker counts INITIAL guard verdicts: two consecutive failed
    # first draws trip it even though redraws recover each request
    adversarial_resp(3)
    adversarial_resp(4)
    assert health_report.counters().get("serve.breaker.trip") == 1
    assert "open" in {s["state"] for s in srv.breaker.snapshot().values()}

    # while open: generous deadline, but retries are suppressed
    A = np.asarray(adversarial_input(plan, N, seed=5))
    resp = _serve_one(srv, _req(rng, params=ADV_PARAMS, operand=A,
                                deadline_s=100.0))
    assert resp.attempts == 1 and resp.flagged
    assert any(f.guard == "breaker" for f in resp.health.findings)

    # after the cool-down a healthy request closes it again
    clock.advance(2.0)
    resp = _serve_one(srv, _req(rng, params=ADV_PARAMS))
    assert resp.status == OK
    assert all(s["state"] == "closed"
               for s in srv.breaker.snapshot().values())
    counts = health_report.counters()
    assert counts.get("serve.breaker.half_open") == 1
    assert counts.get("serve.breaker.close") == 1


def test_no_silent_failures_under_mixed_faults(rng):
    """The acceptance gate in miniature: every fault-touched response is
    flagged or explicitly rejected; clean requests still serve ok."""
    srv = _server(max_batch=4)
    plan = srv.plans.resolve("t", ADV_PARAMS)
    faulty, clean = [], []
    for i in range(12):
        if i % 4 == 1:
            A = np.asarray(inject_nan(_operand(rng), count=2, seed=i))
            faulty.append(srv.submit(_req(rng, operand=A)))
        elif i % 4 == 3:
            A = np.asarray(adversarial_input(plan, N, seed=i))
            faulty.append(srv.submit(
                _req(rng, params=ADV_PARAMS, operand=A)))
        else:
            clean.append(srv.submit(_req(rng)))
    srv.clock.advance(0.02)
    srv.run_pending(force=True)        # drain every group, in batch chunks
    for t in faulty:
        resp = srv.poll(t) if isinstance(t, int) else t
        assert resp.flagged or resp.rejected
    for t in clean:
        resp = srv.poll(t) if isinstance(t, int) else t
        assert resp.served and np.all(np.isfinite(resp.result))


# -- the threaded driver ---------------------------------------------------

def test_threaded_server_round_trip(rng):
    with ThreadedServer(max_batch=4, batch_wait_s=0.001) as srv:
        tickets = [srv.submit(_req(rng)) for _ in range(6)]
        resps = [srv.result(t, timeout=60.0) for t in tickets]
    assert all(r.status == OK for r in resps)
    assert srv.stats()["served"] == 6
