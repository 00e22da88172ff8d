"""Closed-loop sketch apply.  One caller calls ``ops.sketch_apply(plan, A,
"pallas")`` and waits for each result; the operand alternates between two
seeded arrays on the device.

Traffic parameters: ``n`` (operand columns), ``policy`` (streaming
precision) and ``jit``: false calls the public entry point as users call
it, eagerly, so each call pays its host path (the ``custom_vjp`` shell,
lowering, the launch); true calls it inside ``jax.jit``, as it runs inside
users' jitted code, so each call is one launch of a compiled program.
"""
from __future__ import annotations

import jax

from drivers.common import normal_operands, program_plan, sampled_calls
from harness import WindowResult, closed_loop, span
from reference import sketch as ref_sketch

POOL = 2             # operands the caller alternates between
CHECKED_CALLS = 4    # window calls checked besides the last


class Cell:
    def __init__(self, config: dict, workload: dict, seed: int):
        from repro.kernels import ops
        t = workload["traffic"]
        self.sk = config["sketch"]
        self.plan = program_plan(self.sk, t["policy"])

        def apply(A):
            return ops.sketch_apply(self.plan, A, "pallas")
        self.apply = jax.jit(apply) if t["jit"] else apply
        self.operands = normal_operands(seed, POOL, (self.sk["d"], t["n"]))
        self.keep = sampled_calls(seed, 64, CHECKED_CALLS)
        self.kept = {}
        for A in self.operands:                   # warm every shape
            jax.block_until_ready(self.apply(A))

    def window(self, seconds: float) -> WindowResult:
        last = {}

        def call(i):
            with span("operand_prep"):
                A = self.operands[i % POOL]
            with span("dispatch"):
                Y = self.apply(A)
            with span("wait_result"):
                Y.block_until_ready()
            if i in self.keep:
                self.kept[i] = Y
            last["i"], last["Y"] = i, Y

        n, elapsed = closed_loop(call, seconds)
        self.kept[last["i"]] = last["Y"]
        return WindowResult(
            end_to_end={"apply_ms": 1e3 * elapsed / n}, attempted=n,
            failed=0, counts={"calls": n, "window_s": elapsed},
            info={"calls": n, "window_s": elapsed})

    def release(self) -> None:
        self.apply = self.plan = None

    def checks(self) -> dict:
        want = {}
        err = 0.0
        for i, Y in sorted(self.kept.items()):
            j = i % POOL
            if j not in want:
                want[j] = ref_sketch.apply(self.sk, self.operands[j])
            err = max(err, ref_sketch.rel_err(Y, want[j]))
        return {"rel_err": err}

    def control(self) -> dict:
        """The checks' numbers with the reference at bfloat16 put in the
        program's place."""
        err = 0.0
        for j in sorted({i % POOL for i in self.kept}):
            A = self.operands[j]
            err = max(err, ref_sketch.rel_err(
                ref_sketch.apply(self.sk, A, "bfloat16"),
                ref_sketch.apply(self.sk, A)))
        return {"rel_err": err}
