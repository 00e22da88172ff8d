"""Sketch-server CLI: run the resilient serving layer against live load.

    # one batch of guarded sketch + solve requests, print statuses:
    PYTHONPATH=src python -m repro.launch.serve --smoke

    # sustained Poisson load at 200 req/s for 2 seconds (real threads):
    PYTHONPATH=src python -m repro.launch.serve --poisson-rps 200 --duration 2

    # same, with fault injection (NaN-poisoned + adversarial operands)
    # and the no-silent-failures check:
    PYTHONPATH=src python -m repro.launch.serve --poisson-rps 200 \
        --duration 2 --inject

This CLI drives the REAL threaded server (``serving.ThreadedServer``)
under wall-clock arrivals; the deterministic virtual-time harness with
JSON output and gates lives in ``benchmarks/serve_bench.py``.  (The LLM
decode-loop launcher that used to live here is ``repro.launch.generate``.)
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.health import report as health_report
from repro.health.inject import adversarial_input, inject_nan
from repro.serving import SketchRequest, ThreadedServer
from repro.compile_cache import enable_compile_cache


def _print_stats(label, responses, srv):
    lat = sorted(r.latency_s for r in responses if r.served)
    by_status = {}
    for r in responses:
        by_status[r.status] = by_status.get(r.status, 0) + 1
    print(f"[serve] {label}: {len(responses)} responses {by_status}")
    if lat:
        p50 = lat[len(lat) // 2]
        p99 = lat[min(len(lat) - 1, int(len(lat) * 0.99))]
        print(f"[serve]   latency p50={p50 * 1e3:.2f}ms "
              f"p99={p99 * 1e3:.2f}ms")
    print(f"[serve]   stats: {srv.stats()}")


def run_smoke() -> int:
    rng = np.random.default_rng(0)
    params = dict(d=256, k=64, kappa=2, s=2, seed=3)
    with ThreadedServer(max_batch=4, batch_wait_s=0.002) as srv:
        tickets = []
        for _ in range(8):
            A = rng.standard_normal((256, 16)).astype(np.float32)
            tickets.append(srv.submit(SketchRequest(
                tenant="smoke", kind="sketch", operand=A,
                plan_params=dict(params))))
        A = rng.standard_normal((256, 8)).astype(np.float32)
        b = rng.standard_normal(256).astype(np.float32)
        tickets.append(srv.submit(SketchRequest(
            tenant="smoke", kind="solve", operand=A, rhs=b,
            plan_params=dict(d=256, k=64, kappa=2, s=2, seed=3))))
        responses = [t if not isinstance(t, int) else srv.result(t)
                     for t in tickets]
        _print_stats("smoke", responses, srv)
    bad = [r for r in responses if not r.served]
    print(f"[serve] smoke {'FAILED' if bad else 'ok'}")
    return 1 if bad else 0


def run_poisson(rps: float, duration_s: float, inject: bool,
                seed: int = 0) -> int:
    rng = np.random.default_rng(seed)
    params = dict(d=256, k=64, kappa=2, s=2, seed=5)
    adv_params = dict(d=256, k=64, kappa=1, s=1, seed=5)
    n_req = max(1, int(rps * duration_s))
    gaps = rng.exponential(1.0 / rps, size=n_req)
    faulty = set()
    with ThreadedServer(max_batch=8, batch_wait_s=0.002,
                        max_queue=128) as srv:
        tickets = []
        for i, gap in enumerate(gaps):
            time.sleep(float(gap))
            A = rng.standard_normal((256, 16)).astype(np.float32)
            p = params
            if inject and i % 7 == 3:
                A = np.asarray(inject_nan(A, count=2, seed=i))
                faulty.add(i)
            elif inject and i % 7 == 5:
                plan_probe = srv.server.plans.resolve(
                    "load", dict(adv_params))
                A = np.asarray(adversarial_input(plan_probe, 16, seed=i))
                p = adv_params
                faulty.add(i)
            tickets.append(srv.submit(SketchRequest(
                tenant="load", kind="sketch", operand=A,
                plan_params=dict(p), deadline_s=2.0)))
        responses = [t if not isinstance(t, int) else srv.result(t)
                     for t in tickets]
        _print_stats(f"poisson rps={rps:g}", responses, srv)
    if inject:
        silent = [i for i in faulty
                  if responses[i].served and not responses[i].flagged]
        print(f"[serve] injected {len(faulty)} faults; "
              f"silent failures: {len(silent)}")
        print(f"[serve] counters: {health_report.summarize_counters()}")
        if silent:
            print("[serve] FAILED: silent failures detected")
            return 1
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="one mixed batch of requests, print statuses")
    ap.add_argument("--poisson-rps", type=float, default=None,
                    help="sustained Poisson load at this request rate")
    ap.add_argument("--duration", type=float, default=2.0,
                    help="seconds of Poisson load")
    ap.add_argument("--inject", action="store_true",
                    help="poison a fraction of requests (NaN/adversarial) "
                         "and check the no-silent-failures contract")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.smoke:
        return run_smoke()
    if args.poisson_rps is not None:
        return run_poisson(args.poisson_rps, args.duration, args.inject,
                           args.seed)
    ap.error("pick a mode: --smoke or --poisson-rps")


if __name__ == "__main__":
    raise SystemExit(main())
