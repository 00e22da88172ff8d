"""Sharded execution on a small host-device mesh (subprocess: the main test
process must keep 1 device per the assignment).  Verifies:
  * the pjit train step RUNS (not just compiles) on a (2,2) mesh,
  * results match the single-device step bit-for-bit-ish,
  * sketched gradient compression works under shard_map with a pod axis.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import smoke_config
    from repro.configs.registry import ARCHS
    from repro.data import pipeline as dp
    from repro.launch import mesh as mesh_lib
    from repro.optim import adamw, grad_compress as gc
    from repro.sharding import partition as pt
    from repro.train import train_step as ts

    cfg = smoke_config(ARCHS["internlm2-1.8b"])
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)
    step_fn, model = ts.build_train_step(cfg, opt_cfg)

    data_cfg = dp.DataConfig(vocab_size=cfg.vocab_size, global_batch=4,
                             seq_len=16, seed=0)
    batch = {k: jnp.asarray(v) for k, v in dp.make_batch(data_cfg, 0).items()}

    # ---- single-device reference
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw.init_state(params, opt_cfg)
    p1, o1, _, m1 = jax.jit(step_fn)(params, opt, {}, batch)
    ref_loss = float(m1["loss"])

    # ---- (2,2) mesh pjit run
    mesh = mesh_lib.make_mesh((2, 2), ("data", "model"))
    ctx = ts.sharding_ctx_for(mesh, cfg)
    pspecs = pt.param_pspecs(params, ctx)
    ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                   is_leaf=lambda s: isinstance(s, P))
    with mesh, pt.activate(ctx):
        params_sh = jax.device_put(params, ns(pspecs))
        opt_sh = jax.device_put(opt, ns({"m": pspecs, "v": pspecs, "step": P()}))
        batch_sh = jax.device_put(batch, ns({k: P(("data",), None) for k in batch}))
        p2, o2, _, m2 = jax.jit(step_fn)(params_sh, opt_sh, {}, batch_sh)
        sharded_loss = float(m2["loss"])
        # parameters after one step agree with the single-device run
        diffs = jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                               - b.astype(jnp.float32)))),
            p1, jax.device_get(p2))
        max_diff = max(jax.tree.leaves(diffs))

    # ---- shard_map pod-axis gradient compression
    pod_mesh = mesh_lib.make_mesh((2,), ("pod",))
    ccfg = gc.CompressConfig(ratio=4, min_bucket=256)
    g_global = {"w": jnp.asarray(np.random.default_rng(0)
                                 .normal(size=(2, 2048)), jnp.float32)}
    err0 = {"w": jnp.zeros((2, 2048), jnp.float32)}

    def per_pod(g, e):
        gh, ne = gc.compress_gradients(
            ccfg, {"w": g[0]}, {"w": e[0]}, pod_axis="pod", step=0)
        return gh["w"][None], ne["w"][None]

    with pod_mesh:
        gh, ne = jax.shard_map(
            per_pod, mesh=pod_mesh,
            in_specs=(P("pod", None), P("pod", None)),
            out_specs=(P("pod", None), P("pod", None)))(
                g_global["w"], err0["w"])
        # both pods must hold the SAME compressed gradient (psum'd in
        # sketch space with a shared-seed sketch)
        gh_np = np.asarray(jax.device_get(gh))
        pod_agree = float(np.max(np.abs(gh_np[0] - gh_np[1])))

    print(json.dumps({
        "ref_loss": ref_loss, "sharded_loss": sharded_loss,
        "max_param_diff": max_diff, "pod_agree": pod_agree,
    }))
""")


@pytest.mark.slow
def test_sharded_step_matches_single_device(tmp_path):
    script = tmp_path / "sharded_run.py"
    script.write_text(_SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath("src")
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(res["ref_loss"] - res["sharded_loss"]) < 1e-3
    assert res["max_param_diff"] < 5e-2          # bf16-ish tolerance
    assert res["pod_agree"] < 1e-5
