"""Additional coverage: SRHT correctness, serve loop, elastic restore,
roofline-table formatting, cost-model sanity."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.variants import SRHTSketch, make_sketch


def _hadamard(n):
    H = np.array([[1.0]])
    while H.shape[0] < n:
        H = np.block([[H, H], [H, -H]])
    return H


def test_fwht_matches_explicit_hadamard(rng):
    for n in (2, 8, 64):
        x = jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)
        got = np.asarray(SRHTSketch.fwht(x))
        want = _hadamard(n) @ np.asarray(x)
        np.testing.assert_allclose(got, want, atol=1e-4)


def test_srht_norm_preservation(rng):
    d, k = 512, 256
    x = jnp.asarray(rng.normal(size=(d, 1)), jnp.float32)
    ratios = []
    for seed in range(20):
        sk = make_sketch("srht", d, k, seed=seed)
        y = sk.apply(x)
        ratios.append(float(jnp.sum(y ** 2) / jnp.sum(x ** 2)))
    assert abs(np.mean(ratios) - 1.0) < 0.15, np.mean(ratios)


def test_cost_models_are_ordered():
    """Structural sanity of the TPU cost models at paper-regime shapes:
    blockrow and the input-stationary blockperm forward (the one this plan
    launches) read A once < the output-stationary blockperm forward (κ
    reads) < scatter-SJLT (atomics)."""
    from repro.roofline.sketch_model import kernel_cost
    d, k, n = 65_536, 2048, 512
    br = make_sketch("blockrow", d, k).cost_model(n).hbm_bytes
    bp_sketch = make_sketch("blockperm", d, k)
    lw = bp_sketch.lowering_for(n)
    assert lw.stationary, lw.describe()
    bp = bp_sketch.cost_model(n).hbm_bytes
    bp_out = kernel_cost(lw.plan, n, tn=lw.tn).hbm_bytes
    sj = make_sketch("sjlt", d, k, s=8).cost_model(n).hbm_bytes
    assert br == bp < bp_out < sj


def test_serve_generate_smoke():
    from repro.configs.base import smoke_config
    from repro.configs.registry import ARCHS
    from repro.launch.generate import generate
    from repro.models.factory import build_model, extra_inputs_concrete

    cfg = smoke_config(ARCHS["internlm2-1.8b"])
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    prompts = jax.random.randint(key, (2, 4), 0, cfg.vocab_size, jnp.int32)
    toks, tps = generate(model, params, prompts, gen=4,
                         extra=extra_inputs_concrete(cfg, 2, 4, key))
    assert toks.shape == (2, 8)
    assert tps > 0
    # greedy decoding is deterministic
    toks2, _ = generate(model, params, prompts, gen=4,
                        extra=extra_inputs_concrete(cfg, 2, 4, key))
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(toks2))


def test_elastic_restore_resharding(tmp_path):
    """Checkpoint saved once restores under a *different* sharding target
    (the elastic re-mesh path): device_put onto new shardings."""
    from repro.train import checkpoint as ckpt
    tree = {"w": jnp.arange(32, dtype=jnp.float32).reshape(4, 8)}
    d = str(tmp_path / "ck")
    ckpt.save(d, 5, tree)
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    restored, step = ckpt.restore(d, 5, tree, shardings={"w": sharding})
    assert step == 5
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))


def test_roofline_table_formats(tmp_path, monkeypatch):
    import benchmarks.roofline_table as rt
    rec = {"status": "ok", "arch": "a", "shape": "train_4k", "mesh": "pod256",
           "chips": 256, "compute_s": 1.0, "memory_s": 2.0,
           "collective_s": 0.5, "bottleneck": "memory",
           "model_flops": 256 * 197e12, "device_flops": 2 * 197e12,
           "device_hbm_bytes": 1.0, "device_coll_bytes": 1.0,
           "coll_breakdown": {}, "useful_ratio": 0.5, "step_time_s": 2.0,
           "arg_bytes_per_device": 2**30, "temp_bytes_per_device": 2**30,
           "fits_hbm": True, "note": ""}
    os.makedirs(tmp_path / "dr", exist_ok=True)
    with open(tmp_path / "dr" / "a_train_4k_pod256.json", "w") as f:
        json.dump(rec, f)
    monkeypatch.setattr(rt, "DRYRUN_DIR", str(tmp_path / "dr"))
    md = rt.table_markdown()
    assert "| a | train_4k | pod256 |" in md
    assert "memory" in md
    # skip rows render the reason
    with open(tmp_path / "dr" / "b_long_500k_pod256.json", "w") as f:
        json.dump({"status": "skip", "arch": "b", "shape": "long_500k",
                   "mesh": "pod256", "reason": "SKIP(full-attn@524k)"}, f)
    md = rt.table_markdown()
    assert "SKIP(full-attn@524k)" in md


def test_sketch_vectors_grad(rng):
    """Gradient flows through the batched vector API (GraSS featurize path)."""
    from repro.core.blockperm import make_plan
    from repro.kernels import ops
    plan = make_plan(d=128, k=32, kappa=2, s=2, block_rows=8, seed=1)
    x = jnp.asarray(rng.normal(size=(3, 128)), jnp.float32)
    g = jax.grad(lambda xx: jnp.sum(ops.sketch_vectors(plan, xx, "xla") ** 2))(x)
    assert np.all(np.isfinite(np.asarray(g)))
    assert float(jnp.linalg.norm(g)) > 0
