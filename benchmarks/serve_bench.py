"""Serving bench: latency/throughput under Poisson load + faults-under-load.

    PYTHONPATH=src python -m benchmarks.serve_bench                # full
    PYTHONPATH=src python -m benchmarks.serve_bench --tiny         # CI smoke
    PYTHONPATH=src python -m benchmarks.serve_bench --tiny --inject

Writes ``BENCH_serve.json`` and exits non-zero if a gate fails.

The harness is EVENT-DRIVEN VIRTUAL TIME: the server runs on a
``ManualClock``; the Poisson arrival schedule is pre-drawn and replayed
by advancing the clock to each arrival, while every real kernel launch
and guard pass feeds its MEASURED wall duration back into the clock
(``SketchServer._timed`` / ``_guard_slice``).  Queueing dynamics are
therefore exactly reproducible — the guarded and unguarded runs see the
IDENTICAL arrival schedule — while service times stay real.

Two sections, two gates:

  * ``healthy`` — the same Poisson workload served with ``guard=True``
    and ``guard=False``.  GATE: guarded p99 latency overhead ≤ 25%
    (``--max-p99-overhead``) — detection must be cheap enough to leave
    on in production.
  * ``inject`` (``--inject``) — the same load with faults woven in:
    NaN-poisoned operands, adversarial annihilating inputs (κ=1/s=1
    plan class), and a corrupted tuner cache loaded mid-run.  GATE:
    ZERO SILENT FAILURES — every fault-touched request either serves a
    flagged (non-healthy-report) response or is rejected with an
    explicit shed/deadline status, and every ``ok`` response in the
    whole run holds a finite result.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional

import numpy as np

from repro.health import report as health_report
from repro.health.inject import (adversarial_input, corrupt_cache_file,
                                 inject_nan)
from repro.kernels import tune
from repro.serving import ManualClock, SketchRequest, SketchServer
from repro.compile_cache import enable_compile_cache

PARAMS = dict(kappa=2, s=2, seed=7)
ADV_PARAMS = dict(kappa=1, s=1, seed=7)     # injectable plan class


def _arrivals(rps: float, count: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rps, size=count))


def _percentile(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if xs else float("nan")


def warmup(*, d: int, n: int, k: int, max_batch: int = 8) -> None:
    """Compile every shape the timed runs can hit: each coalesced batch
    size is a distinct jit specialization, and a first-call compile in a
    timed run would dominate the tail."""
    clock = ManualClock()
    srv = SketchServer(clock=clock, guard=True, max_batch=max_batch,
                       batch_wait_s=0.001, max_queue=4 * max_batch)
    rng = np.random.default_rng(0)
    for b in range(1, max_batch + 1):
        for _ in range(b):
            srv.submit(SketchRequest(
                tenant="warm", kind="sketch",
                operand=rng.standard_normal((d, n)).astype(np.float32),
                plan_params=dict(PARAMS, d=d, k=k)))
        srv.run_pending(force=True)
    srv.drain()


def run_load(*, d: int, n: int, k: int, rps: float, count: int,
             guard: bool, seed: int, deadline_s: float,
             inject: bool = False, corrupt_path: Optional[str] = None,
             max_batch: int = 8, batch_wait_s: float = 0.002) -> Dict:
    """Replay one Poisson schedule through a fresh virtual-time server."""
    clock = ManualClock()
    srv = SketchServer(clock=clock, guard=guard, max_batch=max_batch,
                       batch_wait_s=batch_wait_s, max_queue=4 * max_batch)
    rng = np.random.default_rng(seed + 1)
    params = dict(PARAMS, d=d, k=k)
    adv_params = dict(ADV_PARAMS, d=d, k=k)
    adv_plan = srv.plans.resolve("bench", adv_params)
    arrivals = _arrivals(rps, count, seed)

    faulty: Dict[int, str] = {}
    tickets = []
    for i, t_arr in enumerate(arrivals):
        clock.advance(max(0.0, float(t_arr) - clock.now()))
        A = rng.standard_normal((d, n)).astype(np.float32)
        p = params
        if inject and i % 11 == 4:
            A = np.asarray(inject_nan(A, count=2, seed=i))
            faulty[i] = "nan"
        elif inject and i % 11 == 8:
            A = np.asarray(adversarial_input(adv_plan, n, seed=i))
            p = adv_params
            faulty[i] = "adversarial"
        if inject and corrupt_path is not None and i == count // 2:
            # corrupted tuner cache lands MID-RUN: load must warn + fall
            # back to the heuristic, and the generation bump must flush
            # the lowering memo without breaking in-flight groups
            corrupt_cache_file(corrupt_path, mode="garbage")
            tune.load_cache(corrupt_path)
        tickets.append(srv.submit(SketchRequest(
            tenant=f"t{i % 2}", kind="sketch", operand=A,
            plan_params=dict(p), deadline_s=deadline_s)))
        srv.run_pending()

    guard_steps = 0
    while srv.batcher.depth() and guard_steps < 10_000:
        clock.advance(2 * batch_wait_s)
        srv.run_pending()
        guard_steps += 1
    srv.run_pending(force=True)

    responses = [t if not isinstance(t, int) else srv.poll(t)
                 for t in tickets]
    assert all(r is not None for r in responses), "lost responses"

    lat = [r.latency_s for r in responses if r.served]
    statuses: Dict[str, int] = {}
    for r in responses:
        statuses[r.status] = statuses.get(r.status, 0) + 1
    silent = [i for i, r in enumerate(responses)
              if r.status == "ok" and (
                  r.result is None or not np.all(np.isfinite(r.result)))]
    unflagged_faults = [i for i in faulty
                        if responses[i].served and not responses[i].flagged]
    return {
        "guard": guard,
        "requests": count,
        "statuses": statuses,
        "served": sum(1 for r in responses if r.served),
        "p50_ms": _percentile(lat, 50) * 1e3,
        "p99_ms": _percentile(lat, 99) * 1e3,
        "throughput_rps": (sum(1 for r in responses if r.served)
                           / max(clock.now(), 1e-9)),
        "virtual_makespan_s": clock.now(),
        "injected": {kind: sum(1 for v in faulty.values() if v == kind)
                     for kind in set(faulty.values())},
        "silent_ok_nonfinite": silent,
        "unflagged_fault_responses": unflagged_faults,
        "stats": srv.stats(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true", help="CI smoke sizes")
    ap.add_argument("--inject", action="store_true",
                    help="run the fault-injection-under-load section")
    ap.add_argument("--d", type=int, default=None)
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--k", type=int, default=None)
    ap.add_argument("--rps", type=float, default=None)
    ap.add_argument("--count", type=int, default=None)
    ap.add_argument("--deadline-s", type=float, default=1.0)
    ap.add_argument("--max-p99-overhead", type=float, default=0.25,
                    help="healthy-workload gate: guarded p99 may exceed "
                         "unguarded p99 by at most this fraction")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.tiny:
        d, n, k, rps, count = 256, 16, 64, 400.0, 80
    else:
        d, n, k, rps, count = 2048, 64, 256, 200.0, 400
    d = args.d or d
    n = args.n or n
    k = args.k or k
    rps = args.rps or rps
    count = args.count or count

    cfg = dict(d=d, n=n, k=k, rps=rps, count=count,
               deadline_s=args.deadline_s, tiny=args.tiny, seed=args.seed)
    print(f"[serve_bench] config: {cfg}")

    # warm the jit caches so neither timed run pays first-call compiles
    warmup(d=d, n=n, k=k)
    health_report.reset_counters()

    out: Dict = {"config": cfg}
    ok = True

    # -- healthy workload: guarded vs unguarded, identical schedule -------
    healthy = {}
    for guard in (False, True):
        r = run_load(d=d, n=n, k=k, rps=rps, count=count, guard=guard,
                     seed=args.seed, deadline_s=args.deadline_s)
        healthy["guarded" if guard else "unguarded"] = r
        print(f"[serve_bench] guard={guard}: p50={r['p50_ms']:.3f}ms "
              f"p99={r['p99_ms']:.3f}ms served={r['served']}/{count} "
              f"thru={r['throughput_rps']:.0f} rps {r['statuses']}")
    p99_u = healthy["unguarded"]["p99_ms"]
    p99_g = healthy["guarded"]["p99_ms"]
    overhead = (p99_g - p99_u) / p99_u if p99_u > 0 else float("inf")
    gate_latency = bool(overhead <= args.max_p99_overhead)
    healthy["p99_overhead_frac"] = overhead
    healthy["gate_p99_overhead_ok"] = gate_latency
    print(f"[serve_bench] guarded p99 overhead: {overhead * 100:+.1f}% "
          f"(gate ≤ {args.max_p99_overhead * 100:.0f}%) "
          f"{'ok' if gate_latency else 'FAIL'}")
    if not gate_latency:
        ok = False
    out["healthy"] = healthy

    # -- faults under load ------------------------------------------------
    if args.inject:
        import tempfile
        health_report.reset_counters()
        with tempfile.NamedTemporaryFile(suffix=".json",
                                         delete=False) as f:
            corrupt_path = f.name
        r = run_load(d=d, n=n, k=k, rps=rps, count=count, guard=True,
                     seed=args.seed + 1, deadline_s=args.deadline_s,
                     inject=True, corrupt_path=corrupt_path)
        counters = health_report.counters()
        silent = (len(r["silent_ok_nonfinite"])
                  + len(r["unflagged_fault_responses"]))
        gate_silent = silent == 0
        cache_seen = counters.get("tune.cache_corrupt", 0) > 0
        r["counters"] = counters
        r["gate_no_silent_failures"] = gate_silent
        r["cache_corruption_detected"] = cache_seen
        print(f"[serve_bench] inject: {r['injected']} faults over "
              f"{count} requests; statuses {r['statuses']}; "
              f"silent failures: {silent} "
              f"{'ok' if gate_silent else 'FAIL'}")
        print(f"[serve_bench] counters: "
              f"{health_report.summarize_counters(12)}")
        if not (gate_silent and cache_seen):
            ok = False
        out["inject"] = r

    with open(args.out, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    print(f"[serve_bench] wrote {args.out}; "
          f"{'all gates ok' if ok else 'GATE FAILURE'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
