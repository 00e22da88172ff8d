"""Helpers the drivers share: the program's plan for a configuration's
sketch, seeded operands made on the device, and the sample of window calls
whose outputs the check compares."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import harness


def program_plan(sk: dict, policy: str):
    """The program's plan for the configuration's sketch; refuses a plan
    whose block grid is not the one the configuration states."""
    from repro.core.blockperm import make_plan
    plan = make_plan(sk["d"], sk["k"], kappa=sk["kappa"], s=sk["s"],
                     seed=sk["seed"], dtype=policy, family=sk["family"])
    grid = {"M": plan.M, "Br": plan.Br, "Bc": plan.Bc}
    if grid != sk["grid"] or plan.k != sk["k"]:
        raise RuntimeError(f"the program plans {plan.describe()}; the "
                           f"configuration states grid {sk['grid']}")
    return plan


def normal_operands(seed: int, count: int, shape) -> list:
    """``count`` standard-normal fp32 arrays, made on the device in one
    jitted call from ``seed``."""
    keys = jax.random.split(harness.seed_key(seed), count)
    make = jax.jit(lambda ks: [jax.random.normal(ks[i], shape, jnp.float32)
                               for i in range(count)])
    return jax.block_until_ready(make(keys))


def sampled_calls(seed: int, first: int, count: int) -> set:
    """Indices of window calls whose outputs are checked, drawn from the
    seed among the first ``first`` calls (the last call is always added)."""
    rng = np.random.default_rng([seed, 0xC4EC])
    return set(int(i) for i in rng.choice(first, size=count, replace=False))
