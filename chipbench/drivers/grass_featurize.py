"""Closed-loop GraSS feature-cache build.  Each call is
``GrassPipeline.featurize`` over every example of the training set, which
stays on the device, and waits for the (examples, k) features.

The configuration gives everything: the MLP, the gradient layout, the
sparsify rule, the sketch, the examples per fused launch (``chunk``), the
training set's size and the matmul precision the deployment states.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import harness
from drivers.common import program_plan
from harness import WindowResult, closed_loop, span
from reference import grass as ref_grass
from reference import sketch as ref_sketch

REF_BLOCK = 2048     # examples per block of the reference


def make_data(seed: int, cfg: dict, examples: int):
    """A seeded MLP init (W ~ N(0, 1/fan_in), b = 0) and MNIST-shaped
    examples (class centres plus Gaussian noise), in one jitted call."""
    dims = cfg["mlp"]["dims"]
    noise = cfg["data"]["noise"]

    @jax.jit
    def build(key):
        kp, kc, ky, kx = jax.random.split(key, 4)
        params = {}
        for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
            params[f"w{i}"] = (jax.random.normal(jax.random.fold_in(kp, i),
                                                 (a, b), jnp.float32)
                               / np.sqrt(a))
            params[f"b{i}"] = jnp.zeros((b,), jnp.float32)
        centres = jax.random.normal(kc, (dims[-1], dims[0]), jnp.float32)
        ys = jax.random.randint(ky, (examples,), 0, dims[-1], jnp.int32)
        xs = centres[ys] + noise * jax.random.normal(
            kx, (examples, dims[0]), jnp.float32)
        return params, xs, ys

    return jax.block_until_ready(build(harness.seed_key(seed)))


class Cell:
    def __init__(self, config: dict, workload: dict, seed: int):
        from repro.attribution.grass import GrassPipeline, GrassPipelineConfig
        self.cfg = config
        self.seed = seed
        self.precision = config["matmul_precision"]
        self.params, self.xs, self.ys = make_data(
            seed, config, config["data"]["examples"])
        sk, sp = config["sketch"], config["sparsify"]
        program_plan(sk, "float32")
        with jax.default_matmul_precision(self.precision):
            self.pipe = GrassPipeline(GrassPipelineConfig(
                sparse_dim=sp["keep"], sketch_dim=sk["k"],
                seed=sk["seed"], chunk=config["chunk"]), self.params)
            jax.block_until_ready(self.pipe.featurize(self.xs, self.ys))
        self.kept = []

    def window(self, seconds: float) -> WindowResult:
        rows = self.xs.shape[0]
        last = {}

        def call(i):
            with jax.default_matmul_precision(self.precision):
                with span("featurize"):
                    F = self.pipe.featurize(self.xs, self.ys)
                with span("wait_result"):
                    F.block_until_ready()
            if i == 0:
                self.kept.append(F)
            last["F"] = F

        n, elapsed = closed_loop(call, seconds)
        if n > 1:
            self.kept.append(last["F"])
        return WindowResult(
            end_to_end={"featurize_rows_per_s": n * rows / elapsed},
            attempted=n, failed=0,
            counts={"calls": n, "rows": n * rows, "window_s": elapsed},
            info={"calls": n, "window_s": elapsed})

    def release(self) -> None:
        self.pipe = None

    def checks(self) -> dict:
        """Every row of the first and the last output of the window against
        the reference, computed in blocks of ``REF_BLOCK`` examples."""
        err = 0.0
        total = self.xs.shape[0]
        for i in range(0, total, REF_BLOCK):
            rows = slice(i, min(total, i + REF_BLOCK))
            want = ref_grass.features(self.params, self.xs[rows],
                                      self.ys[rows], self.cfg)
            err = max([err] + [ref_sketch.rel_err(F[rows], want)
                               for F in self.kept])
        return {"rel_err": err}

    def control(self) -> dict:
        """The checks' numbers with the reference at bfloat16 put in the
        program's place."""
        rows = np.arange(min(REF_BLOCK, self.xs.shape[0]))
        xs, ys = self.xs[rows], self.ys[rows]
        return {"rel_err": ref_sketch.rel_err(
            ref_grass.features(self.params, xs, ys, self.cfg, "bfloat16"),
            ref_grass.features(self.params, xs, ys, self.cfg))}
