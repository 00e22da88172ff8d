"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

The TPU compiler is installed alongside JAX and compiles for a chip that
is described, not attached, so these tests run on the CPU and catch what
interpret mode cannot: tiles the chip's tiling refuses, slices it cannot
DMA, and kernels that need more VMEM than the engine budgeted.  Each case
goes through the public entry point with the tile the engine resolves,
asserts that the program holds the Pallas kernel (``tpu_custom_call``),
and compiles with the launch's modeled ``vmem_bytes`` as Mosaic's VMEM
limit — so the model is shown to be an upper bound of what the compiler
allocates.

The topology is described inside a module fixture only: describing it
loads the TPU library, which one process at a time may hold.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.blockperm import VMEM_BUDGET_BYTES, make_plan
from repro.distributed import (plan_for_mesh, sketch_apply_colsharded,
                               sketch_apply_sharded)
from repro.kernels import flashsketch as fsk
from repro.kernels import lowering, ops

D, K, N = 65_536, 1024, 512                # the paper's forward shape


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip(monkeypatch):
    """Steer the engine onto its TPU branch (compiled Pallas, no interpret)
    while the host backend stays the CPU, with the persistent compile
    cache off: a compile for a described chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_at_modeled_vmem(monkeypatch, lw, fn, *args):
    """Compile ``fn`` with Mosaic's VMEM limit set to the record's modeled
    bytes; returns the compiled program's text."""
    assert lw.impl == "pallas" and lw.downgrade is None, lw.describe()
    assert not lw.interpret, lw.describe()
    assert lw.tn % 128 == 0 or lw.tn == lw.n_eff, lw.describe()
    assert lw.vmem_bytes <= VMEM_BUDGET_BYTES, lw.describe()
    monkeypatch.setattr(fsk, "VMEM_BUDGET_BYTES", lw.vmem_bytes)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, lw.describe()
    return text


@pytest.mark.parametrize("kappa", [1, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "fp8_e4m3",
                                   "fp8_e4m3_sr"])
@pytest.mark.parametrize("op", ["fwd", "transpose"])
def test_paper_shape_compiles(op, dtype, kappa, one_chip, chip, monkeypatch):
    plan = make_plan(D, K, kappa=kappa, s=2, seed=0, dtype=dtype)
    lw = lowering.lower(plan, lowering.LaunchSpec(op=op, n=N))
    rows = D if op == "fwd" else plan.k
    x = jax.ShapeDtypeStruct((rows, N), jnp.float32, sharding=one_chip)
    apply = ops.sketch_apply if op == "fwd" else ops.sketch_apply_t
    _compile_at_modeled_vmem(monkeypatch, lw, lambda X: apply(plan, X), x)


@pytest.mark.parametrize("sparse_dim,batch,chunk,kappa", [
    (16_384, 256, 1, 2),   # the GraSS bench grid's largest row
    (4_096, 1, 64, 4),     # GrassPipeline's shipped config, one chunk
    (4_096, 1, 32, 1),     # a narrow single-matrix gather
])
def test_grass_gather_compiles(sparse_dim, batch, chunk, kappa, one_chip,
                               chip, monkeypatch):
    d_total = 4 * sparse_dim
    plan = make_plan(sparse_dim, K, kappa=kappa, s=2, seed=0)
    lw = lowering.lower(plan, lowering.LaunchSpec(
        op="fwd", n=chunk, gather=True, batch=batch))
    assert lw.gather_fused, lw.describe()
    G = jax.ShapeDtypeStruct((batch, d_total, chunk), jnp.float32,
                             sharding=one_chip)
    mask = jax.ShapeDtypeStruct((sparse_dim,), jnp.int32, sharding=one_chip)
    _compile_at_modeled_vmem(
        monkeypatch, lw,
        lambda X, m: ops.sketch_apply_batched(plan, X, row_index=m), G, mask)


@pytest.mark.parametrize("op", ["fwd", "transpose", "blockrow"])
def test_tightest_default_plan_compiles(op, one_chip, chip, monkeypatch):
    """The default plan of the paper's (d, k) grid whose fused working set
    is nearest the budget: d=262144, k=64 (Br = s = 2, below the chip's
    8-row sublane tile, and Bc = 8192)."""
    d, k = 262_144, 64
    plan = make_plan(d, k, kappa=4, s=2, seed=0)
    lw = lowering.lower(plan, lowering.LaunchSpec(op=op, n=N))
    rows = plan.k if op == "transpose" else d
    x = jax.ShapeDtypeStruct((rows, N), jnp.float32, sharding=one_chip)
    apply = {"fwd": ops.sketch_apply, "transpose": ops.sketch_apply_t,
             "blockrow": ops.blockrow_apply}[op]
    _compile_at_modeled_vmem(monkeypatch, lw, lambda X: apply(plan, X), x)


@pytest.mark.parametrize("d,k,n", [
    (262_144, 1024, 512),          # the sketch.apply cells' plan
    (262_144, 1024, 600),          # two column tiles, the second ragged
    (552_960, 4096, 8),            # the Moonlight GraSS cell's sketch
])
def test_input_stationary_forward_compiles(d, k, n, one_chip, chip,
                                           monkeypatch):
    """The input-stationary forward at the benchmark cells' plans, at the
    tile and VMEM figure that ``explain`` reports."""
    plan = make_plan(d, k, kappa=4, s=2, seed=0)
    lw = lowering.lower(plan, lowering.LaunchSpec(op="fwd", n=n))
    assert lw.stationary, lw.describe()
    text = lowering.explain(plan, n=n)
    assert "fwd: input-stationary kernel" in text, text
    assert f"tn={lw.tn}:" in text and f"vmem={lw.vmem_bytes}B" in text, text
    A = jax.ShapeDtypeStruct((d, n), jnp.float32, sharding=one_chip)
    _compile_at_modeled_vmem(monkeypatch, lw,
                             lambda X: ops.sketch_apply(plan, X), A)


def test_input_stationary_forward_compiles_col_sharded_on_four_chips(
        topo, chip, monkeypatch):
    """Each device of a column-sharded forward runs the input-stationary
    kernel on its own columns, inside ``shard_map``."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("shard",))
    d, k, n = 262_144, 1024, 2048
    plan = make_plan(d, k, kappa=4, s=2, seed=0)
    lw = lowering.lower(plan, lowering.LaunchSpec(
        op="fwd", n=n, shard="col", devices=4))
    assert lw.stationary and lw.n_eff == n // 4, lw.describe()
    A = jax.ShapeDtypeStruct((d, n), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, "shard")))
    _compile_at_modeled_vmem(
        monkeypatch, lw,
        lambda X: sketch_apply_colsharded(plan, X, mesh, "shard"), A)


def test_solve_sketch_compiles(one_chip, chip, monkeypatch):
    """The sketch of the 2²⁰ × 512 sketch-and-precondition problem."""
    d, n = 2 ** 20, 512
    plan = make_plan(d, 4 * n, kappa=4, s=2, seed=0)
    lw = lowering.lower(plan, lowering.LaunchSpec(op="fwd", n=n))
    A = jax.ShapeDtypeStruct((d, n), jnp.float32, sharding=one_chip)
    _compile_at_modeled_vmem(monkeypatch, lw,
                             lambda X: ops.sketch_apply(plan, X), A)


def test_row_shard_partial_compiles_on_four_chips(topo, chip, monkeypatch):
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("shard",))
    plan = plan_for_mesh(D, K, 4, kappa=4, s=2, seed=0)
    lw = lowering.lower(plan, lowering.LaunchSpec(
        op="fwd", n=N, shard="row", devices=4))
    A = jax.ShapeDtypeStruct((D, N), jnp.float32,
                             sharding=NamedSharding(mesh, P("shard", None)))
    text = _compile_at_modeled_vmem(
        monkeypatch, lw,
        lambda X: sketch_apply_sharded(plan, X, mesh, "shard"), A)
    assert "all-reduce" in text          # the psum of the per-ℓ partials
