"""Plain reference of the GraSS featurize step (FlashSketch App. E):
per-example gradient of the correct-class margin of a ReLU MLP, a fixed
random subset of its coordinates, then the BlockPerm-SJLT sketch.

The configuration states the layout of the flattened gradient (the
parameter blocks in order, each row-major) and the sparsify rule: keep
the ``keep`` coordinates with the smallest hash(seed, 0x6A55, u), ties to
the lower index, in increasing order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import sketch as ref_sketch


def mlp_logits(params: dict, x, layers: int, precision):
    h = x
    for i in range(layers):
        h = jnp.dot(h, params[f"w{i}"], precision=precision) + params[f"b{i}"]
        if i < layers - 1:
            h = jax.nn.relu(h)
    return h


def margin(params: dict, x, y, layers: int, precision):
    """Correct-class logit minus the log-sum-exp of the others."""
    z = mlp_logits(params, x, layers, precision)
    others = jnp.where(jnp.arange(z.shape[-1]) == y, -jnp.inf, z)
    return z[y] - jax.nn.logsumexp(others)


def per_example_grads(params: dict, xs, ys, cfg: dict, precision: str):
    """(B, D) flattened gradients in the configuration's layout."""
    prec = (jax.lax.Precision.DEFAULT if precision == "bfloat16"
            else jax.lax.Precision.HIGHEST)
    layers = len(cfg["mlp"]["dims"]) - 1
    if precision == "bfloat16":
        params = {k: v.astype(jnp.bfloat16).astype(jnp.float32)
                  for k, v in params.items()}
        xs = xs.astype(jnp.bfloat16).astype(jnp.float32)

    def one(x, y):
        g = jax.grad(margin)(params, x, y, layers, prec)
        return jnp.concatenate([g[name].reshape(-1)
                                for name in cfg["gradient_layout"]])
    return jax.jit(jax.vmap(one))(xs, ys)


def sparsify_mask(total: int, keep: int, seed: int) -> np.ndarray:
    scores = ref_sketch.hash_words(seed, 0x6A55, np.arange(total))
    return np.sort(np.argsort(scores, kind="stable")[:keep])


def features(params: dict, xs, ys, cfg: dict, precision: str = "float32"):
    """(B, k) sketched features of the examples ``xs, ys``."""
    sp = cfg["sparsify"]
    grads = per_example_grads(params, xs, ys, cfg, precision)
    mask = sparsify_mask(grads.shape[1], sp["keep"], sp["seed"])
    return ref_sketch.apply(cfg["sketch"], grads[:, mask].T, precision).T
