#!/usr/bin/env python3
"""Smoke run of the sketch library's main path on a TPU.

    python chip_smoke.py               # one chip: fwd, transpose/VJP,
                                       # solve, grass, serve
    python chip_smoke.py --four-chips  # four chips: row-sharded apply and
                                       # the distributed solver only

Every phase runs through the public entry points (``repro.kernels.ops``,
``repro.solvers``, ``repro.attribution``, ``repro.serving``,
``repro.distributed``) at the sizes users run, in this one process, and
prints one JSON line: the phase, the ``Lowering`` records on its path,
compile and warm wall seconds (a smoke timing, not a benchmark), and the
largest error against a plain float32 ``jax.numpy`` reference.  A phase
fails when the backend is not a TPU, when a lowering on its path is not
``impl=pallas`` or carries a downgrade, when its compiled program holds
no ``tpu_custom_call``, or when its error misses the policy's tolerance
(``Precision.exactness_atol``) or a bit-exactness claim of the repo.

The last line of standard output is ``{"ok": true, "device": {...}}``;
any failure exits non-zero.  Without a TPU the script exits at once,
before any phase runs.  The compile cache is ``JAX_COMPILATION_CACHE_DIR``
when set, else ``.jax_cache/`` next to this file.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

D, K, N = 65_536, 1024, 512            # the paper's forward shape
SOLVE_D, SOLVE_N, SOLVE_COND = 2 ** 20, 512, 1e4
GRASS_B, GRASS_SPARSE, GRASS_TOTAL, GRASS_KAPPA = 256, 16_384, 65_536, 2
SERVE_N, SERVE_REQUESTS = 64, 8
SOLVE_TOL = 1e-6


class PhaseError(AssertionError):
    """A check of a phase failed."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


def _check_lowering(lw) -> str:
    _check(lw.impl == "pallas", f"impl is {lw.impl!r}, not 'pallas': "
                                f"{lw.describe()}")
    _check(lw.downgrade is None, f"downgrade on the path: {lw.describe()}")
    _check(not lw.interpret, f"interpret mode on the chip: {lw.describe()}")
    return lw.describe()


def _compile(fn, *args):
    """(compiled, seconds); the program must hold a Pallas kernel."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    secs = time.perf_counter() - t0
    _check("tpu_custom_call" in compiled.as_text(),
           "compiled program holds no tpu_custom_call")
    return compiled, secs


def _warm(compiled, *args, reps: int = 1):
    """(output, warm wall seconds) of a compiled program: the median of
    ``reps`` calls after one unmeasured call."""
    import jax
    import numpy as np
    jax.block_until_ready(compiled(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        times.append(time.perf_counter() - t0)
    return out, float(np.median(times))


def _max_err(got, want):
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    _check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    _check(bool(np.all(np.isfinite(got))), "non-finite output")
    return float(np.max(np.abs(got - want))), float(np.max(np.abs(want)))


def _within(err: float, scale: float, atol: float) -> bool:
    """The allclose rule of the repo's tests (``atol == rtol``)."""
    return err <= atol * (1.0 + scale)


def _emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def phase_fwd(key):
    import jax
    import jax.numpy as jnp
    from repro.core import precision
    from repro.core.blockperm import make_plan
    from repro.kernels import lowering, ops, ref

    A = jax.random.normal(key, (D, N), jnp.float32)
    for dtype in ("float32", "bfloat16", "fp8_e4m3_sr"):
        for kappa in (1, 4):
            plan = make_plan(D, K, kappa=kappa, s=2, seed=0, dtype=dtype)
            lw = lowering.lower(plan, lowering.LaunchSpec(
                op="fwd", n=N, impl="pallas"))
            desc = _check_lowering(lw)
            compiled, c_s = _compile(
                lambda X: ops.sketch_apply(plan, X, "pallas"), A)
            Y, w_s = _warm(compiled, A)
            want = ref.flashsketch_ref(plan, precision.emulate_stream(
                A, plan.precision, seed=plan.seed))
            err, scale = _max_err(Y, want)
            atol = plan.precision.exactness_atol
            _emit({"phase": "fwd", "dtype": dtype, "kappa": kappa,
                   "lowering": [desc], "compile_s": c_s,
                   "smoke_warm_s": w_s, "max_err": err, "ref_max": scale,
                   "atol": atol})
            _check(_within(err, scale, atol),
                   f"fwd {dtype} kappa={kappa}: error {err} > {atol}")


def phase_transpose_vjp(key):
    import jax
    import jax.numpy as jnp
    from repro.core import precision
    from repro.core.blockperm import make_plan
    from repro.kernels import lowering, ops, ref

    kA, kY = jax.random.split(key)
    A = jax.random.normal(kA, (D, N), jnp.float32)
    for dtype in ("float32", "bfloat16"):
        plan = make_plan(D, K, kappa=4, s=2, seed=0, dtype=dtype)
        W = jax.random.normal(kY, (plan.k, N), jnp.float32)
        lw = lowering.lower(plan, lowering.LaunchSpec(
            op="transpose", n=N, impl="pallas"))
        descs = [_check_lowering(lw), _check_lowering(lowering.lower(
            plan, lowering.LaunchSpec(op="fwd", n=N, impl="pallas")))]
        want = ref.flashsketch_transpose_ref(plan, precision.emulate_stream(
            W, plan.precision, seed=plan.seed))
        atol = plan.precision.exactness_atol

        compiled, c_s = _compile(
            lambda Y: ops.sketch_apply_t(plan, Y, "pallas"), W)
        X, w_s = _warm(compiled, W)
        err, scale = _max_err(X, want)
        _emit({"phase": "transpose", "dtype": dtype, "kappa": 4,
               "lowering": descs[:1], "compile_s": c_s, "smoke_warm_s": w_s,
               "max_err": err, "ref_max": scale, "atol": atol})
        _check(_within(err, scale, atol),
               f"transpose {dtype}: error {err} > {atol}")

        # d/dA Σ W ⊙ (S A) = Sᵀ W: the custom VJP runs the transpose kernel
        grad = jax.grad(lambda X_: jnp.sum(
            W * ops.sketch_apply(plan, X_, "pallas")))
        compiled, c_s = _compile(grad, A)
        G, w_s = _warm(compiled, A)
        err, scale = _max_err(G, want)
        _emit({"phase": "vjp", "dtype": dtype, "kappa": 4,
               "lowering": descs, "compile_s": c_s, "smoke_warm_s": w_s,
               "max_err": err, "ref_max": scale, "atol": atol})
        _check(_within(err, scale, atol),
               f"vjp {dtype}: error {err} > {atol}")


def make_solve_problem(key):
    """Seeded tall ``A = G · diag(σ) · Vᵀ`` (Gaussian G, orthogonal V, σ
    log-spaced from 1 to 1/cond) and a consistent ``b = A x``, made on the
    device."""
    import jax
    import jax.numpy as jnp
    from repro.core.precision import fp32_matmul

    d, n, cond = SOLVE_D, SOLVE_N, SOLVE_COND

    kG, kV, kx = jax.random.split(key, 3)

    @jax.jit
    def build(kG, kV, kx):
        V, _ = jnp.linalg.qr(jax.random.normal(kV, (n, n), jnp.float32))
        sigma = jnp.logspace(0.0, -jnp.log10(cond), n, dtype=jnp.float32)
        G = jax.random.normal(kG, (d, n), jnp.float32) / jnp.sqrt(d)
        A = fp32_matmul(G * sigma[None, :], V.T)
        x = jax.random.normal(kx, (n,), jnp.float32)
        return A, fp32_matmul(A, x)

    return build(kG, kV, kx)


def _relres(A, x, b) -> float:
    import jax.numpy as jnp
    from repro.core.precision import fp32_matmul
    return float(jnp.linalg.norm(fp32_matmul(A, x) - b) / jnp.linalg.norm(b))


def phase_solve(A, b):
    import jax
    from repro.configs.flashsketch_paper import SOLVER_PRESETS
    from repro.kernels import ops
    from repro.solvers import solve_preset

    t0 = time.perf_counter()
    res = solve_preset(A, b, "default", impl="pallas")
    jax.block_until_ready(res.x)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = solve_preset(A, b, "default", impl="pallas")
    jax.block_until_ready(res.x)
    warm_s = time.perf_counter() - t0
    desc = _check_lowering(res.lowering)
    plan = res.lowering.plan
    # the solver runs its sketch-and-factor step eagerly, with no one
    # program to inspect: compile that step as the solver calls it
    factorization = SOLVER_PRESETS["default"].factorization
    _compile(lambda X: ops.sketch_qr(plan, X, "pallas",
                                     factorization=factorization), A)
    relres = _relres(A, res.x, b)
    _emit({"phase": "solve", "preset": "default", "d": SOLVE_D, "n": SOLVE_N,
           "cond": SOLVE_COND, "lowering": [desc],
           "compile_s": max(0.0, cold_s - warm_s), "smoke_warm_s": warm_s,
           "iterations": res.iterations, "max_err": relres,
           "relres_solver": res.relres, "atol": SOLVE_TOL})
    _check(res.converged and relres <= SOLVE_TOL,
           f"solve: relres {relres} > {SOLVE_TOL} "
           f"(converged={res.converged}, iterations={res.iterations})")


def phase_grass(key):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.attribution import mlp as mlp_lib
    from repro.attribution.grass import (GrassPipeline, GrassPipelineConfig,
                                         sparsify_mask)
    from repro.core.blockperm import make_plan
    from repro.kernels import lowering, ops, ref

    # the GraSS grid's largest row: B per-example gradients of d_total
    # coordinates, the sparsify mask keeps GRASS_SPARSE of them
    kG, kP = jax.random.split(key)
    G = jax.random.normal(kG, (GRASS_B, GRASS_TOTAL, 1), jnp.float32)
    mask = sparsify_mask(GRASS_TOTAL, GRASS_SPARSE, 0)
    plan = make_plan(GRASS_SPARSE, K, kappa=GRASS_KAPPA, s=2, seed=0)
    lw = lowering.lower(plan, lowering.LaunchSpec(
        op="fwd", n=1, impl="pallas", gather=True, batch=GRASS_B))
    descs = [_check_lowering(lw)]
    _check(lw.gather_fused, f"gather not fused: {lw.describe()}")
    compiled, c_s = _compile(
        lambda X, m: ops.sketch_apply_batched(plan, X, "pallas",
                                              row_index=m), G, mask)
    Y, w_s = _warm(compiled, G, mask, reps=5)
    # the materialized gather, then the same kernel at the same tile
    mat_compiled, mat_c_s = _compile(
        lambda X, m: ops.sketch_apply_batched(plan, X[:, m, :], "pallas",
                                              lw.tn), G, mask)
    mat, mat_w_s = _warm(mat_compiled, G, mask, reps=5)
    descs.append(_check_lowering(lowering.lower(plan, lowering.LaunchSpec(
        op="fwd", n=1, impl="pallas", tn=lw.tn, batch=GRASS_B))))
    exact = bool(np.array_equal(np.asarray(Y), np.asarray(mat)))
    Gm = G[:, mask, :]
    want = ref.flashsketch_ref(plan, Gm[:, :, 0].T).T[:, :, None]
    err, scale = _max_err(Y, want)
    atol = plan.precision.exactness_atol
    _emit({"phase": "grass_batched_gather", "B": GRASS_B,
           "sparse_dim": GRASS_SPARSE, "d_total": GRASS_TOTAL,
           "kappa": GRASS_KAPPA, "lowering": descs, "compile_s": c_s,
           "smoke_warm_s": w_s, "compile_s_materialized": mat_c_s,
           "smoke_warm_s_materialized": mat_w_s, "max_err": err,
           "ref_max": scale, "atol": atol,
           "array_equal_materialized": exact})
    _check(exact, "fused gather differs from the materialized gather")
    _check(_within(err, scale, atol), f"grass: error {err} > {atol}")

    # the shipped pipeline: per-example MLP gradients -> fused sketch
    mcfg = mlp_lib.MLPConfig()
    params = mlp_lib.init_mlp(mcfg, kP)
    xs, ys = mlp_lib.make_synthetic_mnist(GRASS_B, mcfg.d_in,
                                          mcfg.n_classes, seed=0)
    cfg = GrassPipelineConfig()
    with jax.default_matmul_precision("highest"):
        pipe = GrassPipeline(cfg, params)
        lw = pipe.sketch.lowering_for(cfg.chunk, gather=True)
        descs = [_check_lowering(lw)]
        t0 = time.perf_counter()
        feats = jax.block_until_ready(pipe.featurize(xs, ys))
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        feats = jax.block_until_ready(pipe.featurize(xs, ys))
        warm_s = time.perf_counter() - t0
        unfused = GrassPipeline(dataclasses.replace(cfg, fused=False),
                                params).featurize(xs, ys)
        src = pipe.source
        grads = jax.jit(jax.vmap(lambda x, y: src.grad_fn(params, x, y)))(
            jnp.asarray(xs), jnp.asarray(ys))
        want = ref.flashsketch_ref(pipe.sketch.plan, grads[:, src.mask].T).T
    exact = bool(np.array_equal(np.asarray(feats), np.asarray(unfused)))
    err, scale = _max_err(feats, want)
    atol = pipe.sketch.plan.precision.exactness_atol
    _emit({"phase": "grass_featurize", "examples": GRASS_B,
           "sparse_dim": cfg.sparse_dim, "sketch_dim": cfg.sketch_dim,
           "chunk": cfg.chunk, "lowering": descs,
           "compile_s": max(0.0, cold_s - warm_s), "smoke_warm_s": warm_s,
           "max_err": err, "ref_max": scale, "atol": atol,
           "array_equal_unfused": exact})
    _check(exact, "fused featurize differs from the materializing path")
    _check(_within(err, scale, atol), f"featurize: error {err} > {atol}")


def phase_serve(key, A_s, b_s):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.blockperm import make_plan
    from repro.kernels import lowering, ops
    from repro.serving import SketchRequest, ThreadedServer

    params = dict(d=D, k=K, kappa=4, s=2, seed=0)
    stack = jax.random.normal(key, (SERVE_REQUESTS, D, SERVE_N), jnp.float32)
    operands = [np.asarray(a) for a in stack]
    plan = make_plan(D, K, kappa=4, s=2, seed=0)
    direct_lw = lowering.lower(plan, lowering.LaunchSpec(
        op="fwd", n=SERVE_N, impl="pallas"))
    direct_fn, _ = _compile(lambda X: ops.sketch_apply(plan, X, "pallas"),
                            stack[0])
    direct = [np.asarray(direct_fn(jnp.asarray(a))) for a in operands]

    t0 = time.perf_counter()
    with ThreadedServer(max_batch=SERVE_REQUESTS, batch_wait_s=0.5,
                        impl="pallas") as srv:
        tickets = [srv.submit(SketchRequest(
            tenant="smoke", kind="sketch", operand=a, plan_params=params))
            for a in operands]
        resps = [srv.result(t, timeout=600.0) for t in tickets]
        solve = srv.result(srv.submit(SketchRequest(
            tenant="smoke", kind="solve", operand=A_s, rhs=b_s,
            plan_params=dict(d=SOLVE_D, k=4 * SOLVE_N, kappa=4, s=2,
                             seed=0))), timeout=600.0)
    wall_s = time.perf_counter() - t0
    statuses = [r.status for r in resps]
    _check(all(s == "ok" for s in statuses), f"sketch statuses {statuses}")
    _check(solve.served, f"solve request: {solve.status}")
    # the records the server launched with, as its responses carry them
    _check(all(r.lowering is not None for r in resps),
           "a sketch response carries no lowering")
    served_lw = list({id(r.lowering): r.lowering for r in resps}.values())
    descs = ([_check_lowering(lw) for lw in served_lw]
             + [_check_lowering(solve.result.lowering),
                _check_lowering(direct_lw)])
    # the server's coalesced launch, compiled as the server calls it
    lw = served_lw[0]
    group_fn, _ = _compile(lambda S: ops.sketch_apply_batched(
        lw.plan, S, "pallas", lw.tn, None), stack)
    group_out = np.asarray(group_fn(stack))
    batch_sizes = [r.batch_size for r in resps]
    bitwise = [bool(np.array_equal(np.asarray(r.result), want))
               for r, want in zip(resps, direct)]
    same_program = all(np.array_equal(np.asarray(r.result), group_out[j])
                       for j, r in enumerate(resps))
    err = max(float(np.max(np.abs(np.asarray(r.result) - want)))
              for r, want in zip(resps, direct))
    relres = _relres(A_s, solve.result.x, b_s)
    _emit({"phase": "serve", "requests": SERVE_REQUESTS, "n": SERVE_N,
           "lowering": descs, "smoke_wall_s": wall_s,
           "statuses": statuses, "batch_sizes": batch_sizes,
           "bitwise_equal_direct": bitwise,
           "bitwise_equal_group_program": same_program, "max_err": err,
           "solve_status": solve.status, "solve_relres": relres})
    _check(all(b == SERVE_REQUESTS for b in batch_sizes),
           f"requests did not coalesce into one launch: {batch_sizes}")
    _check(len(served_lw) == 1, f"{len(served_lw)} launches served the group")
    _check(same_program, "served results differ from the checked program")
    _check(all(bitwise), "coalesced results differ from direct sketch_apply")
    _check(solve.status == "ok" and relres <= SOLVE_TOL,
           f"solve request: {solve.status}, relres {relres}")


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------

def phase_sharded_apply(key, mesh):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed import plan_for_mesh, sketch_apply_sharded
    from repro.kernels import lowering, ops

    devices = mesh.shape["shard"]
    plan = plan_for_mesh(D, K, devices, kappa=4, s=2, seed=0)
    A_one = jax.random.normal(key, (D, N), jnp.float32)    # one chip
    A = jax.device_put(A_one, NamedSharding(mesh, P("shard", None)))
    slabs = {str(s.device): list(s.data.shape) for s in A.addressable_shards}
    _check(len(slabs) == devices and all(
        shape[0] == D // devices for shape in slabs.values()),
        f"A is not row-sharded over every device: {slabs}")
    lw = lowering.lower(plan, lowering.LaunchSpec(
        op="fwd", n=N, impl="pallas", shard="row", devices=devices))
    descs = [_check_lowering(lw)]
    compiled, c_s = _compile(
        lambda X: sketch_apply_sharded(plan, X, mesh, "shard", "pallas"), A)
    Y, w_s = _warm(compiled, A)
    single = jax.jit(lambda X: ops.sketch_apply(plan, X, "pallas"))(A_one)
    descs.append(_check_lowering(lowering.lower(plan, lowering.LaunchSpec(
        op="fwd", n=N, impl="pallas"))))
    exact = bool(np.array_equal(np.asarray(Y), np.asarray(single)))
    err = float(np.max(np.abs(np.asarray(Y) - np.asarray(single))))
    _emit({"phase": "sharded_apply", "devices": devices, "plan":
           plan.describe(), "slab_shapes": slabs, "lowering": descs,
           "compile_s": c_s, "smoke_warm_s": w_s, "max_err": err,
           "array_equal_one_chip": exact})
    _check(exact, "row-sharded apply differs from the one-chip apply")


def phase_dist_solve(key, mesh):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed import dist_sketch_precondition_lstsq
    from repro.distributed.sharded_apply import plan_for_mesh
    from repro.configs.flashsketch_paper import solver_sketch_rows
    from repro.kernels import lowering
    from repro.solvers import solve_preset

    devices = mesh.shape["shard"]
    A_one, b_one = make_solve_problem(key)                 # one chip
    A = jax.device_put(A_one, NamedSharding(mesh, P("shard", None)))
    b = jax.device_put(b_one, NamedSharding(mesh, P("shard")))
    plan = plan_for_mesh(SOLVE_D, solver_sketch_rows(SOLVE_N), devices,
                         kappa=4, s=2, seed=0)
    descs = [_check_lowering(lowering.lower(plan, lowering.LaunchSpec(
        op="fwd", n=SOLVE_N, impl="pallas", shard="row", devices=devices)))]
    t0 = time.perf_counter()
    res = dist_sketch_precondition_lstsq(A, b, mesh, "shard", plan,
                                         tol=SOLVE_TOL, impl="pallas")
    jax.block_until_ready(res.x)
    wall_s = time.perf_counter() - t0
    relres = _relres(A, res.x, b)
    single = solve_preset(A_one, b_one, "default", impl="pallas")
    x_d, x_s = np.asarray(res.x), np.asarray(single.x)
    agree = float(np.linalg.norm(x_d - x_s) / np.linalg.norm(x_s))
    # two solutions with relative residuals r₁, r₂ of one consistent
    # system differ by at most cond(A)·(r₁ + r₂), relatively
    bound = SOLVE_COND * (relres + _relres(A_one, single.x, b_one))
    _emit({"phase": "dist_solve", "devices": devices, "d": SOLVE_D,
           "n": SOLVE_N, "cond": SOLVE_COND, "lowering": descs,
           "smoke_wall_s": wall_s, "iterations": res.iterations,
           "max_err": relres, "atol": SOLVE_TOL,
           "one_chip_iterations": single.iterations,
           "x_rel_diff_one_chip": agree, "x_rel_diff_bound": bound})
    _check(res.converged and relres <= SOLVE_TOL,
           f"distributed solve: relres {relres} > {SOLVE_TOL}")
    _check(single.converged and agree <= bound,
           f"distributed solution differs from one chip by {agree} "
           f"(bound {bound})")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the row-sharded apply and distributed solve "
                         "on a 4-chip mesh, and nothing else")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devs[0].platform!r} devices); "
              f"nothing was run", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        print(f"chip_smoke: needs {want} TPU chips, JAX sees {len(devs)}",
              file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(json.dumps({"compile_cache": cache_dir}), file=sys.stderr)

    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    if args.four_chips:
        from repro.launch import mesh as mesh_lib
        mesh = mesh_lib.make_mesh((4,), ("shard",))
        phases = [("sharded_apply", lambda: phase_sharded_apply(keys[0], mesh)),
                  ("dist_solve", lambda: phase_dist_solve(keys[1], mesh))]
    else:
        made = {}

        def problem():
            if "Ab" not in made:
                made["Ab"] = jax.block_until_ready(make_solve_problem(keys[2]))
            return made["Ab"]

        phases = [
            ("fwd", lambda: phase_fwd(keys[0])),
            ("transpose_vjp", lambda: phase_transpose_vjp(keys[1])),
            ("solve", lambda: phase_solve(*problem())),
            ("grass", lambda: phase_grass(keys[3])),
            ("serve", lambda: phase_serve(keys[4], *problem())),
        ]
    failed = []
    for name, run in phases:
        try:
            run()
        except Exception as e:             # report, then run the rest
            traceback.print_exc()
            _emit({"phase": name, "ok": False, "error": repr(e)[:2000]})
            failed.append(name)
    if failed:
        print(f"chip_smoke: FAILED phases {failed}", file=sys.stderr)
        return 1
    # the devices the phases ran on, not the devices the host has
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": want}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
