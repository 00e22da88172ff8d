"""Plain reference of GraSS features of Moonlight-16B-A3B at one chip's
expert share: each sequence's TRUE per-weight gradient by ``jax.grad``, one
sequence at a time, sliced and sketched.  No probes, no factorization, and
the held experts evaluated densely on every token under the routing mask.

The model (``deepseek_v3`` block, fp32; the configuration's keys are the
published ``config.json``'s):

* ``u = RMSNorm(x)`` (eps ``rms_norm_eps``, learned weight); each layer is
  ``h = x + MLA(u)``, then ``x' = h + FFN(RMSNorm(h))``.
* MLA, no q-LoRA: ``q = u W_q`` split into ``q_nope, q_rope``;
  ``[c, k_rope] = u W_kva``, ``c <- RMSNorm(c)``; ``[k_nope, v] = c W_kvb``;
  RoPE (rotate-half, base ``rope_theta``) on ``q_rope`` and the head-shared
  ``k_rope``; scores ``(q_nope.k_nope + q_rope.k_rope) / sqrt(nope +
  rope)`` under a causal softmax; ``o = concat_h(softmax v) W_o``.
* Dense FFN (the first ``first_k_dense_replace`` layers):
  ``W_down(silu(u W_gate) * u W_up)``.
* MoE FFN: ``s = sigmoid(u W_r)`` over all ``router_experts``; ``chosen =
  top_k(s + b)``; ``w_e = routed_scaling_factor * s_e / (sum_chosen s +
  1e-20)``; ``y = Shared(u) + sum_{e in chosen and held} w_e E_e(u)``.
* Head: ``RMSNorm`` then ``W_head``; ``f`` is the summed next-token NLL.

Routing is a discrete choice: where the 6th and 7th scores of a token lie
within ``TIE_MARGIN`` of each other, two correct fp32 programs may choose
differently.  ``candidates`` therefore returns, for each sequence, the
features of every resolution of such near ties (found in its own forward,
layer by layer); the check takes the one nearest the program's row.

The layout (the attributed linears in order, each block
``G[mask_in, mask_out]`` row-major) and the keep rule (``sparsify_mask``
of ``reference/grass.py`` with seed ``2i`` for linear ``i``'s inputs and
``2i + 1`` for its outputs) are the configuration's.
"""
from __future__ import annotations

import itertools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import grass as ref_grass
from reference import sketch as ref_sketch

MAX_CANDIDATES = 256      # resolutions of near ties per sequence
# A 6th/7th score gap this close is a near tie: 4x the largest change of a
# 6th/7th gap between the program and this reference read on a v5e
# (9.5e-7), and 2,700x below the median gap (1.09e-2)
TIE_MARGIN = 4e-6


def layout(cfg: dict):
    """``(layer, key, expert or None, d_in, d_out)`` of each attributed
    linear, in order."""
    H, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    lora = cfg["kv_lora_rank"]
    out = []
    for l in range(cfg["num_hidden_layers"]):
        out += [(l, "q", None, H, nh * (nope + rope)),
                (l, "kv_a", None, H, lora + rope),
                (l, "kv_b", None, lora, nh * (nope + dv)),
                (l, "o", None, nh * dv, H)]
        if l < cfg["first_k_dense_replace"]:
            F = cfg["intermediate_size"]
            out += [(l, "gate", None, H, F), (l, "up", None, H, F),
                    (l, "down", None, F, H)]
            continue
        Fe = cfg["moe_intermediate_size"]
        Fs = Fe * cfg["n_shared_experts"]
        out += [(l, "router", None, H, cfg["router_experts"]),
                (l, "shared_gate", None, H, Fs),
                (l, "shared_up", None, H, Fs),
                (l, "shared_down", None, Fs, H)]
        for e in range(len(cfg["held_experts"])):
            out += [(l, "gate", e, H, Fe), (l, "up", e, H, Fe),
                    (l, "down", e, Fe, H)]
    return out


def masks(cfg: dict):
    keep = cfg["factor_keep"]
    return [(ref_grass.sparsify_mask(a, min(keep, a), 2 * i),
             ref_grass.sparsify_mask(b, min(keep, b), 2 * i + 1))
            for i, (_, _, _, a, b) in enumerate(layout(cfg))]


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, dim, theta):
    inv = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    ang = np.outer(pos, inv)
    cos = jnp.asarray(np.cos(np.concatenate([ang, ang], -1)), jnp.float32)
    sin = jnp.asarray(np.sin(np.concatenate([ang, ang], -1)), jnp.float32)
    if x.ndim == 3:                      # (T, heads, dim)
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _swiglu(u, g, up, down, prec):
    return jnp.dot(jax.nn.silu(jnp.dot(u, g, precision=prec))
                   * jnp.dot(u, up, precision=prec), down, precision=prec)


def _trunk(params, ids, swaps, cfg: dict, prec):
    """One sequence (``ids (T,)``): the logits ``(T, vocab)``, and per MoE
    layer and token the gap between its ``k``-th and ``k+1``-th choice
    scores.  ``swaps (moe layers, T)``: tokens whose ``k``-th choice is
    replaced by the ``k+1``-th."""
    T = ids.shape[0]
    nh = cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    lora, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    topk, E = cfg["num_experts_per_tok"], len(cfg["held_experts"])
    held = jnp.asarray(cfg["held_experts"])
    pos = np.arange(T)
    x = params["embed"][ids]
    margins = []
    for l, p in enumerate(params["layers"]):
        u = _rms(x, p["attn_norm"], eps)
        q = jnp.dot(u, p["q"], precision=prec).reshape(T, nh, nope + rope)
        kva = jnp.dot(u, p["kv_a"], precision=prec)
        c = _rms(kva[:, :lora], p["kv_norm"], eps)
        k_rope = _rope(kva[:, lora:], pos, rope, cfg["rope_theta"])
        kv = jnp.dot(c, p["kv_b"], precision=prec).reshape(T, nh, nope + dv)
        q_rope = _rope(q[..., nope:], pos, rope, cfg["rope_theta"])
        s = (jnp.einsum("thd,shd->hts", q[..., :nope], kv[..., :nope],
                        precision=prec)
             + jnp.einsum("thd,sd->hts", q_rope, k_rope, precision=prec)) \
            / math.sqrt(nope + rope)
        s = jnp.where(np.tril(np.ones((T, T), bool)), s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hts,shd->thd", a, kv[..., nope:],
                       precision=prec).reshape(T, nh * dv)
        h = x + jnp.dot(o, p["o"], precision=prec)
        u = _rms(h, p["ffn_norm"], eps)
        if l < cfg["first_k_dense_replace"]:
            x = h + _swiglu(u, p["gate"], p["up"], p["down"], prec)
            continue
        sc = jax.nn.sigmoid(jnp.dot(u, p["router"], precision=prec))
        val, idx = jax.lax.top_k(sc + p["bias"], topk + 1)
        margins.append(val[:, topk - 1] - val[:, topk])
        sw = swaps[len(margins) - 1]
        chosen = jnp.concatenate(
            [idx[:, :topk - 1],
             jnp.where(sw, idx[:, topk], idx[:, topk - 1])[:, None]], 1)
        ws = jnp.take_along_axis(sc, chosen, axis=1)
        ws = cfg["routed_scaling_factor"] * ws / (ws.sum(1, keepdims=True)
                                                  + 1e-20)
        y = _swiglu(u, p["shared_gate"], p["shared_up"], p["shared_down"],
                    prec)
        for e in range(E):
            gate = jnp.sum(jnp.where(chosen == held[e], ws, 0.0), axis=1)
            y = y + gate[:, None] * _swiglu(u, p["gate"][e], p["up"][e],
                                            p["down"][e], prec)
        x = h + y
    z = jnp.dot(_rms(x, params["norm"], eps), params["head"], precision=prec)
    return z, jnp.stack(margins) if margins else jnp.zeros((0, T))


def _prec(precision: str):
    return (jax.lax.Precision.DEFAULT if precision == "bfloat16"
            else jax.lax.Precision.HIGHEST)


def _lower(params, precision: str):
    """The weights rounded to bfloat16 for the control, else as given."""
    if precision != "bfloat16":
        return params
    return jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
                        params)


def moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def logits(params, ids, cfg: dict, precision: str = "float32"):
    """``(T, vocab)`` logits of one sequence."""
    swaps = jnp.zeros((moe_layers(cfg), ids.shape[0]), bool)
    return _trunk(_lower(params, precision), ids, swaps, cfg,
                  _prec(precision))[0]


def _grad_row_fn(cfg: dict, precision: str):
    """A jitted ``(params, ids, tgt, swaps) -> (row (D,), margins)``: the
    true gradient of the sequence's NLL w.r.t. every weight, each
    attributed linear's block ``G[mask_in, mask_out]`` in layout order."""
    prec = _prec(precision)
    lay, ms = layout(cfg), masks(cfg)

    def nll(params, ids, tgt, swaps):
        z, margins = _trunk(params, ids, swaps, cfg, prec)
        t = jnp.take_along_axis(z, tgt[:, None], axis=1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(z, axis=-1) - t), margins

    def row(params, ids, tgt, swaps):
        g, margins = jax.grad(nll, has_aux=True)(params, ids, tgt, swaps)
        blocks = []
        for (l, key, e, _, _), (mi, mo) in zip(lay, ms):
            G = g["layers"][l][key]
            if e is not None:
                G = G[e]
            blocks.append(G[mi][:, mo].reshape(-1))
        return jnp.concatenate(blocks), margins
    return jax.jit(row)


_ROW_FNS = {}


def candidates(params, ids, tgt, cfg: dict, tie_margin: float = TIE_MARGIN,
               precision: str = "float32") -> np.ndarray:
    """``(m, D)`` gradient rows of one sequence, one per resolution of its
    near ties: the first row takes every choice as the forward makes it;
    where a token's ``k``-th and ``k+1``-th scores lie within
    ``tie_margin`` the other choice is taken too, layer by layer (a choice
    at one layer moves the scores of the next)."""
    key = (json.dumps(cfg, sort_keys=True), precision)
    if key not in _ROW_FNS:
        _ROW_FNS.clear()
        _ROW_FNS[key] = _grad_row_fn(cfg, precision)
    fn = _ROW_FNS[key]
    params = _lower(params, precision)
    L, T = moe_layers(cfg), ids.shape[0]
    rows, stack = [], [(np.zeros((L, T), bool), 0, None)]
    while stack:
        swaps, first, done = stack.pop()
        row, margins = done or fn(params, ids, tgt, jnp.asarray(swaps))
        near = np.asarray(margins) < tie_margin
        near[:first] = False
        if not near.any():
            rows.append(np.asarray(row))
            continue
        l = int(np.argmax(near.any(axis=1)))
        toks = np.flatnonzero(near[l])
        for pick in itertools.product((False, True), repeat=len(toks)):
            s = swaps.copy()
            s[l, toks] = pick
            stack.append((s, l + 1, None if any(pick) else (row, margins)))
        if len(rows) + len(stack) > MAX_CANDIDATES:
            raise RuntimeError(f"more than {MAX_CANDIDATES} resolutions of "
                               f"near ties in one sequence")
    return np.stack(rows)


def sketch_rows(rows, cfg: dict, precision: str = "float32") -> np.ndarray:
    """``(m, k)`` features of gradient rows: the configuration's sketch."""
    return np.asarray(ref_sketch.apply(cfg["sketch"], jnp.asarray(rows).T,
                                       precision).T)
