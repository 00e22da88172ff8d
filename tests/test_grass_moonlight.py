"""Moonlight-16B-A3B as a GraSS gradient source, at a CPU size: the model
against a plain reference, the factorized features against the sketched
slices of TRUE per-weight gradients, the expert-parallel share, dropless
routing and the quarantine."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attribution import moonlight as ML
from repro.attribution.grass import (GrassPipeline, GrassPipelineConfig,
                                     sparsify_mask)
from repro.health import report
from repro.kernels import ref as kref

CFG = ML.MoonlightConfig(
    hidden=64, heads=4, qk_nope=16, qk_rope=8, v_head=16, kv_lora=32,
    dense_width=128, expert_width=16, shared_experts=2, experts=8, top_k=2,
    held=(0, 1, 2, 3), layers=3, dense_layers=1, vocab=128)
T, B = 16, 6
HI = jax.lax.Precision.HIGHEST


@pytest.fixture(scope="module")
def params():
    return ML.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tokens():
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, T + 1), 0, CFG.vocab)
    return ids[:, :-1], ids[:, 1:]


@pytest.fixture(scope="module")
def true_rows(params, tokens):
    return ref_rows(params, *tokens)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# ------------------------------------------------- the plain reference copy

def _rms(x, w):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + CFG.eps) * w


def _rope(x, dim):
    inv = 1.0 / CFG.rope_theta ** (np.arange(0, dim, 2) / dim)
    ang = np.outer(np.arange(x.shape[0]), inv)
    ang = np.concatenate([ang, ang], -1)
    cos, sin = np.cos(ang), np.sin(ang)
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    h = dim // 2
    return x * cos + jnp.concatenate([-x[..., h:], x[..., :h]], -1) * sin


def _ffn(u, g, up, down):
    return jnp.dot(jax.nn.silu(jnp.dot(u, g, precision=HI))
                   * jnp.dot(u, up, precision=HI), down, precision=HI)


def ref_moe(p, u, held):
    """The MoE layer on ``u (T, H)`` with the experts ``held`` (indices of
    the stacked weights' first axis, named ``held`` ids), every held
    expert evaluated on every token under the routing mask:
    ``(shared, routed)``."""
    s = jax.nn.sigmoid(jnp.dot(u, p["router"], precision=HI))
    _, chosen = jax.lax.top_k(s + p["bias"], CFG.top_k)
    w = jnp.take_along_axis(s, chosen, 1)
    w = CFG.routed_scale * w / (w.sum(1, keepdims=True) + 1e-20)
    routed = jnp.zeros_like(u)
    for slot, e in held:
        gate = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=1)
        routed = routed + gate[:, None] * _ffn(u, p["gate"][slot],
                                               p["up"][slot],
                                               p["down"][slot])
    return _ffn(u, p["shared_gate"], p["shared_up"], p["shared_down"]), \
        routed


def ref_logits(params, ids):
    """One sequence's logits ``(T, vocab)``."""
    nh, dn, dr, dv = CFG.heads, CFG.qk_nope, CFG.qk_rope, CFG.v_head
    n = ids.shape[0]
    x = params["embed"][ids]
    for l, p in enumerate(params["layers"]):
        u = _rms(x, p["attn_norm"])
        q = jnp.dot(u, p["q"], precision=HI).reshape(n, nh, dn + dr)
        kva = jnp.dot(u, p["kv_a"], precision=HI)
        c = _rms(kva[:, :CFG.kv_lora], p["kv_norm"])
        kr = _rope(kva[:, CFG.kv_lora:], dr)
        kv = jnp.dot(c, p["kv_b"], precision=HI).reshape(n, nh, dn + dv)
        s = (jnp.einsum("thd,shd->hts", q[..., :dn], kv[..., :dn],
                        precision=HI)
             + jnp.einsum("thd,sd->hts", _rope(q[..., dn:], dr), kr,
                          precision=HI)) / math.sqrt(dn + dr)
        a = jax.nn.softmax(jnp.where(np.tril(np.ones((n, n), bool)), s,
                                     -jnp.inf), -1)
        o = jnp.einsum("hts,shd->thd", a, kv[..., dn:],
                       precision=HI).reshape(n, nh * dv)
        h = x + jnp.dot(o, p["o"], precision=HI)
        u = _rms(h, p["ffn_norm"])
        if l < CFG.dense_layers:
            x = h + _ffn(u, p["gate"], p["up"], p["down"])
        else:
            shared, routed = ref_moe(p, u, list(enumerate(CFG.held)))
            x = h + shared + routed
    return jnp.dot(_rms(x, params["norm"]), params["head"], precision=HI)


def _layout():
    """``(layer, key, expert slot or None, d_in, d_out)`` in order."""
    H, F, Fe = CFG.hidden, CFG.dense_width, CFG.expert_width
    Fs = Fe * CFG.shared_experts
    out = []
    for l in range(CFG.layers):
        out += [(l, "q", None, H, CFG.heads * (CFG.qk_nope + CFG.qk_rope)),
                (l, "kv_a", None, H, CFG.kv_lora + CFG.qk_rope),
                (l, "kv_b", None, CFG.kv_lora,
                 CFG.heads * (CFG.qk_nope + CFG.v_head)),
                (l, "o", None, CFG.heads * CFG.v_head, H)]
        if l < CFG.dense_layers:
            out += [(l, "gate", None, H, F), (l, "up", None, H, F),
                    (l, "down", None, F, H)]
            continue
        out += [(l, "router", None, H, CFG.experts),
                (l, "shared_gate", None, H, Fs),
                (l, "shared_up", None, H, Fs),
                (l, "shared_down", None, Fs, H)]
        for e in range(len(CFG.held)):
            out += [(l, "gate", e, H, Fe), (l, "up", e, H, Fe),
                    (l, "down", e, Fe, H)]
    return out


@jax.jit
def _ref_grad(params, ids, tgt):
    def nll(p):
        z = ref_logits(p, ids)
        return jnp.sum(jax.nn.logsumexp(z, -1)
                       - z[jnp.arange(ids.shape[0]), tgt])
    return jax.grad(nll)(params)


def ref_rows(params, ids, tgt):
    """``(B, D)``: each sequence's TRUE gradient w.r.t. every weight (plain
    ``jax.grad``, one sequence at a time), sliced ``G[mask_in, mask_out]``
    per attributed linear and concatenated."""
    keep = CFG.keep
    out = []
    for i in range(ids.shape[0]):
        g = _ref_grad(params, ids[i], tgt[i])
        blocks = []
        for j, (l, key, e, a, b) in enumerate(_layout()):
            G = g["layers"][l][key]
            G = G if e is None else G[e]
            mi = np.asarray(sparsify_mask(a, min(keep, a), 2 * j))
            mo = np.asarray(sparsify_mask(b, min(keep, b), 2 * j + 1))
            blocks.append(G[mi][:, mo].reshape(-1))
        out.append(jnp.concatenate(blocks))
    return jnp.stack(out)


# ------------------------------------------------------------------ tests

def test_layout_has_one_block_per_linear():
    assert len(ML.linears(CFG)) == len(_layout()) == 7 + 2 * (8 + 3 * 4)
    assert ML.factor_dim(CFG) == sum(min(64, a) * min(64, b)
                                     for *_, a, b in _layout())
    full = ML.MoonlightConfig()
    assert len(ML.linears(full)) == 135
    assert ML.factor_dim(full) == 552_960


def test_logits_match_reference(params, tokens):
    ids, _ = tokens
    got = ML.logits(CFG, params, ids)
    want = jax.jit(jax.vmap(ref_logits, in_axes=(None, 0)))(params, ids)
    assert _rel(got, want) <= 1e-5


def _pipe(params, chunk=4, source_cfg=CFG):
    return GrassPipeline(GrassPipelineConfig(sketch_dim=128, seed=0,
                                             chunk=chunk),
                         params, source=ML.MoonlightGradients(source_cfg))


@pytest.mark.parametrize("block", [None, 8, 5])
def test_factor_rows_are_true_gradient_slices(params, tokens, true_rows,
                                              monkeypatch, block):
    """At the shipped block size each held expert's rows fit one block;
    at 8 and 5 rows a block they span several, with partial last blocks."""
    if block is not None:
        monkeypatch.setattr(ML, "BLOCK", block)
    ids, tgt = tokens
    src = ML.MoonlightGradients(CFG)
    rows, counts = jax.jit(src.rows)(params, ids, tgt)
    assert rows.shape == (B, src.dim)
    assert _rel(rows, true_rows) <= 1e-5


def test_features_match_reference(params, tokens, true_rows):
    """Through ``featurize`` (6 sequences in chunks of 4: the padded scan)
    and ``build_cache``: the sketch of the reference's slices."""
    ids, tgt = tokens
    pipe = _pipe(params)
    want = kref.flashsketch_ref(pipe.sketch.plan, true_rows.T).T
    assert pipe.sketch_lowering().gather_fused is False
    assert _rel(pipe.featurize(ids, tgt), want) <= 1e-5
    cache, _ = pipe.build_cache(ids, tgt, batch=4)
    assert _rel(cache, want) <= 1e-5


def _moe_input(params, seed=2):
    u = jax.random.normal(jax.random.PRNGKey(seed), (2, T, CFG.hidden))
    return params["layers"][1], u


def test_expert_shares_sum_to_the_uncut_layer(params):
    """Two chips' shares of the 8 routed experts: their routed parts, with
    the shared experts and the residual counted once, add up to the uncut
    layer of the reference."""
    p, u = _moe_input(params)
    # the uncut layer: all 8 experts, stacked
    full = dict(p, **{k: jnp.concatenate([p[k], p[k] * 0.5 + 0.1])
                      for k in ("gate", "up", "down")})
    lin = ML._Linears()
    parts = []
    for share in ((0, 1, 2, 3), (4, 5, 6, 7)):
        cfg = dataclasses.replace(CFG, held=share)
        sl = slice(share[0], share[0] + 4)
        ps = dict(p, **{k: full[k][sl] for k in ("gate", "up", "down")})
        parts.append(ML.moe(cfg, ps, u, lin))
    got = u + parts[0][0] + parts[0][1] + parts[1][1]
    for b in range(2):
        shared, routed = ref_moe(full, u[b], list(enumerate(range(8))))
        assert _rel(got[b], u[b] + shared + routed) <= 1e-5
    np.testing.assert_allclose(parts[0][0], parts[1][0])   # shared alike


def _pairs_reference(p, u, held):
    """The routed part as a sum over every (token, held expert) pair."""
    shared, routed = ref_moe(p, u, list(enumerate(held)))
    return routed


def test_every_routed_pair_contributes(params):
    p, u = _moe_input(params)
    lin = ML._Linears()
    _, routed, counts, _ = ML.moe(CFG, p, u, lin)
    s = jax.nn.sigmoid(jnp.einsum("bth,he->bte", u, p["router"],
                                  precision=HI))
    _, chosen = jax.lax.top_k(s + p["bias"], CFG.top_k)
    want_counts = [[int(jnp.sum(chosen[b] == e)) for e in CFG.held]
                   for b in range(2)]
    assert np.asarray(counts).tolist() == want_counts
    for b in range(2):
        assert _rel(routed[b], _pairs_reference(p, u[b], CFG.held)) <= 1e-5


def test_forced_imbalance_drops_nothing(params, tokens):
    """A bias that sends every token to expert 2: it receives all of them
    (4x its fair share), and the layer and the features still match the
    reference."""
    p, u = _moe_input(params)
    p = dict(p, bias=p["bias"].at[2].set(10.0))
    lin = ML._Linears()
    _, routed, counts, _ = ML.moe(CFG, p, u, lin)
    assert np.asarray(counts)[:, 2].tolist() == [T, T]
    for b in range(2):
        assert _rel(routed[b], _pairs_reference(p, u[b], CFG.held)) <= 1e-5
    layers = list(params["layers"])
    for l in range(CFG.dense_layers, CFG.layers):
        layers[l] = dict(layers[l], bias=layers[l]["bias"].at[2].set(10.0))
    biased = dict(params, layers=layers)
    ids, tgt = tokens
    src = ML.MoonlightGradients(CFG)
    rows, counts = jax.jit(src.rows)(biased, ids, tgt)
    assert (np.asarray(counts)[:, :, 2] == T).all()
    assert _rel(rows, ref_rows(biased, ids, tgt)) <= 1e-5


def test_nan_sequence_is_quarantined_and_counted(params, tokens):
    ids, tgt = tokens
    pipe = _pipe(params)
    clean = np.asarray(pipe.featurize(ids, tgt))
    report.reset_counters()
    bad_ids = ids.at[3, 5].set(CFG.vocab)        # outside the vocabulary
    feats = np.asarray(pipe.featurize(bad_ids, tgt))
    assert pipe.quarantined == 1
    assert report.counters()["grass.quarantined"] == 1
    assert np.all(feats[3] == 0.0)
    others = [i for i in range(B) if i != 3]
    assert np.isfinite(feats).all()
    np.testing.assert_allclose(feats[others], clean[others], rtol=1e-5,
                               atol=1e-6 * np.abs(clean).max())


def test_expert_tokens_are_counted(params, tokens):
    ids, tgt = tokens
    pipe = _pipe(params)
    report.reset_counters()
    pipe.featurize(ids, tgt)
    tokens_seen = pipe.source.expert_tokens
    assert tokens_seen.shape == (CFG.layers - CFG.dense_layers,
                                 len(CFG.held))
    # every held choice of every real token, none of the padding copies
    assert report.counters()["grass.expert_tokens"] == int(tokens_seen.sum())
    assert 0 < tokens_seen.sum() <= B * T * CFG.top_k * 2
