"""Moonlight-16B-A3B (``deepseek_v3``: latent attention and a 64-expert
MoE with shared experts) as a GraSS gradient source, by factorized
per-layer capture (LoGra, Choe et al. 2024; FactGraSS, Hu et al. 2025).

The per-example gradient of a linear layer ``y = x W`` is
``G = Σ_t x_tᵀ δ_t`` with ``x_t`` its input and ``δ_t = ∂f/∂y_t`` at
token ``t``.  Fixed random masks on the input and output coordinates give
exactly the sub-block ``G[mask_in, mask_out] = Σ_t x_t[mask_in]ᵀ
δ_t[mask_out]``: the forward captures ``x_t[mask_in]``, a zero probe on
each attributed linear's kept outputs makes one batched backward yield
every ``δ_t[mask_out]``, and no parameter gradient is ever formed.  The
blocks of every attributed linear, concatenated, are the rows
``GrassPipeline`` quarantines and sketches (``MoonlightGradients``).

The model (fp32; matmuls at ``jax.default_matmul_precision``), per layer
``h = x + MLA(RMSNorm(x))``, ``x' = h + FFN(RMSNorm(h))``:

* MLA without q-LoRA: ``q = u W_q`` (heads × (nope + rope)),
  ``[c, k_rope] = u W_kva``, ``c ← RMSNorm(c)``, ``[k_nope, v] = c W_kvb``;
  RoPE on ``q_rope`` and the head-shared ``k_rope``; a causal softmax of
  ``(q_nope·k_nope + q_rope·k_rope) / √(nope + rope)``; ``concat_h(p v)
  W_o``.  RoPE rotates halves, where the published checkpoint stores its
  rope columns interleaved: a fixed permutation of (random) weights.
* FFN: SwiGLU in the leading dense layers; in the others the MoE layer
  ``Shared(u) + Σ_{e ∈ chosen ∩ held} w_e E_e(u)``, with sigmoid scores
  ``s = σ(u W_r)`` over ALL routed experts, ``chosen = top_k(s + b)``,
  ``w_e = scale · s_e / (Σ_chosen s + 1e-20)``.  The layer is told which
  experts it holds (its share under expert parallelism) and computes only
  their part; it is dropless: every (token, held expert) pair is sorted
  by expert into one grouped matmul (a scan over expert-sorted row
  blocks that computes only the blocks holding pairs) with room for the
  worst case, so no token is ever dropped for capacity.
* Head: ``RMSNorm`` then ``W_head``; ``f(z)`` is the sequence's summed
  next-token NLL over the (sliced) vocabulary.  An id outside the
  vocabulary reads a NaN embedding, so its sequence is quarantined rather
  than silently clamped to another token.

Attributed linears (``linears``), in layout order: per dense layer ``q,
kv_a, kv_b, o, gate, up, down``; per MoE layer ``q, kv_a, kv_b, o, router,
shared_gate, shared_up, shared_down``, then each held expert's ``gate, up,
down``.  Linear ``i`` keeps ``min(keep, d_in)`` input coordinates by
``sparsify_mask(d_in, ·, 2i)`` and ``min(keep, d_out)`` output coordinates
by ``sparsify_mask(d_out, ·, 2i + 1)``; its block is row-major.

Tracing: scopes ``repro.mla.attention``, ``repro.moe.route``,
``repro.moe.experts`` in the forward (and their backward), and
``repro.grass.factors`` around the masked factor products; the counter
``grass.expert_tokens`` (``repro.health.report``) counts the tokens each
held expert received in a call.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.attribution.grass import GradientSource, sparsify_mask
from repro.health import report as health_report
from repro.tracing import scope

@dataclasses.dataclass(frozen=True)
class MoonlightConfig:
    """Widths default to the published Moonlight-16B-A3B ``config.json``;
    ``layers``, ``held`` and ``vocab`` are what this chip holds."""
    hidden: int = 2048
    heads: int = 16
    qk_nope: int = 128
    qk_rope: int = 64
    v_head: int = 128
    kv_lora: int = 512
    dense_width: int = 11264          # intermediate_size
    expert_width: int = 1408          # moe_intermediate_size
    shared_experts: int = 2           # n_shared_experts
    experts: int = 64                 # routed experts the router scores
    top_k: int = 6                    # num_experts_per_tok
    held: Tuple[int, ...] = tuple(range(8))   # routed experts held here
    routed_scale: float = 2.446       # routed_scaling_factor
    layers: int = 5
    dense_layers: int = 1             # first_k_dense_replace
    vocab: int = 20480
    rope_theta: float = 50000.0
    eps: float = 1e-5
    keep: int = 64                    # factor coordinates kept per side

    @property
    def q_head(self) -> int:
        return self.qk_nope + self.qk_rope

    @property
    def shared_width(self) -> int:
        return self.expert_width * self.shared_experts


def is_moe(cfg: MoonlightConfig, layer: int) -> bool:
    return layer >= cfg.dense_layers


def linears(cfg: MoonlightConfig) -> List[Tuple[int, str, int, int]]:
    """``(layer, name, d_in, d_out)`` of every attributed linear, in layout
    order; a held expert's linears are named ``e<id>.gate`` etc."""
    H, nh = cfg.hidden, cfg.heads
    out = []
    for l in range(cfg.layers):
        out += [(l, "q", H, nh * cfg.q_head),
                (l, "kv_a", H, cfg.kv_lora + cfg.qk_rope),
                (l, "kv_b", cfg.kv_lora, nh * (cfg.qk_nope + cfg.v_head)),
                (l, "o", nh * cfg.v_head, H)]
        if not is_moe(cfg, l):
            F = cfg.dense_width
            out += [(l, "gate", H, F), (l, "up", H, F), (l, "down", F, H)]
            continue
        Fs, Fe = cfg.shared_width, cfg.expert_width
        out += [(l, "router", H, cfg.experts), (l, "shared_gate", H, Fs),
                (l, "shared_up", H, Fs), (l, "shared_down", Fs, H)]
        for e in cfg.held:
            out += [(l, f"e{e}.gate", H, Fe), (l, f"e{e}.up", H, Fe),
                    (l, f"e{e}.down", Fe, H)]
    return out


def factor_dim(cfg: MoonlightConfig) -> int:
    """Coordinates per example: the kept blocks of every linear."""
    return sum(min(cfg.keep, a) * min(cfg.keep, b)
               for _, _, a, b in linears(cfg))


def factor_masks(cfg: MoonlightConfig) -> List[Tuple[np.ndarray, np.ndarray]]:
    """``(mask_in, mask_out)`` of each linear, in layout order."""
    out = []
    for i, (_, _, a, b) in enumerate(linears(cfg)):
        out.append((np.asarray(sparsify_mask(a, min(cfg.keep, a), 2 * i)),
                    np.asarray(sparsify_mask(b, min(cfg.keep, b),
                                             2 * i + 1))))
    return out


# ------------------------------------------------------------------ weights

def init_params(cfg: MoonlightConfig, key) -> dict:
    """Seeded weights: every projection ``N(0, 1/fan_in)``, the embedding
    ``N(0, 1)``, norms 1, the expert-choice bias 0.  Experts are stacked
    over the held ones, ``(held, d_in, d_out)``."""
    H, nh = cfg.hidden, cfg.heads
    keys = iter(jax.random.split(key, 16 * cfg.layers + 4))

    def w(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / np.sqrt(shape[-2]))

    layers = []
    for l in range(cfg.layers):
        p = {"attn_norm": jnp.ones((H,), jnp.float32),
             "q": w(H, nh * cfg.q_head),
             "kv_a": w(H, cfg.kv_lora + cfg.qk_rope),
             "kv_norm": jnp.ones((cfg.kv_lora,), jnp.float32),
             "kv_b": w(cfg.kv_lora, nh * (cfg.qk_nope + cfg.v_head)),
             "o": w(nh * cfg.v_head, H),
             "ffn_norm": jnp.ones((H,), jnp.float32)}
        if not is_moe(cfg, l):
            F = cfg.dense_width
            p.update(gate=w(H, F), up=w(H, F), down=w(F, H))
        else:
            Fs, Fe, E = cfg.shared_width, cfg.expert_width, len(cfg.held)
            p.update(router=w(H, cfg.experts),
                     bias=jnp.zeros((cfg.experts,), jnp.float32),
                     shared_gate=w(H, Fs), shared_up=w(H, Fs),
                     shared_down=w(Fs, H),
                     gate=w(E, H, Fe), up=w(E, H, Fe), down=w(E, Fe, H))
        layers.append(p)
    return {"embed": jax.random.normal(next(keys), (cfg.vocab, H),
                                       jnp.float32),
            "layers": layers,
            "norm": jnp.ones((H,), jnp.float32),
            "head": w(H, cfg.vocab)}


# ------------------------------------------------------------- the probes

@jax.custom_vjp
def _tap(y, probe, idx):
    """``y`` plus a zero probe on the coordinates ``idx`` of its last axis.
    The probe is zero, so the forward is ``y`` itself; the probe's
    cotangent is ``δ[..., idx]``."""
    return y


def _tap_fwd(y, probe, idx):
    return y, idx


def _tap_bwd(idx, g):
    return g, jnp.take(g, idx, axis=-1), None


_tap.defvjp(_tap_fwd, _tap_bwd)


class _Linears:
    """Runs the attributed linears in layout order: captures each one's
    kept input coordinates and taps its kept outputs with the next probe.
    With ``probes`` None the forward is the plain model."""

    def __init__(self, masks=None, probes=None):
        self.masks = masks
        self.probes = probes
        self.captured = []
        self.i = 0

    def __call__(self, x, W):
        """``x W`` for a dense linear, ``x (b, T, d_in)``."""
        y = jnp.einsum("...i,io->...o", x, W)
        if self.probes is None:
            return y
        m_in, m_out = self.masks[self.i]
        self.captured.append(jnp.take(x, m_in, axis=-1))
        y = _tap(y, self.probes[self.i], jnp.asarray(m_out))
        self.i += 1
        return y

    def experts(self, u, tok, wrow, p, expert, active):
        """The held experts' weighted SwiGLU outputs summed per token,
        ``(N, H)``, over expert-sorted row blocks: block ``i`` holds the
        rows ``u[tok[i]]`` (``N`` is padding), belongs to held expert
        ``expert[i]`` and is computed only where ``active[i]``; row ``r``'s
        output is weighted by ``wrow[i, r]``.  Captures and taps use each
        block's own expert's masks; the probes of one kind, ``(blocks, B,
        keep_out)``, sit at that kind's first held expert."""
        W = (p["gate"], p["up"], p["down"])
        if self.probes is None:
            return _expert_scan(u, tok, wrow, expert, active, W)[0]
        E = p["gate"].shape[0]
        ids = [[self.i + 3 * e + step for e in range(E)] for step in range(3)]
        masks = tuple(
            (jnp.asarray(np.stack([self.masks[j][0] for j in row])),
             jnp.asarray(np.stack([self.masks[j][1] for j in row])))
            for row in ids)
        y, caps = _expert_scan(u, tok, wrow, expert, active, W, masks,
                               tuple(self.probes[row[0]] for row in ids))
        self.captured.extend(caps)
        self.i += 3 * E
        return y


def _expert_scan(u, tok, wrow, expert, active, W, masks=None, probes=None):
    """A scan over the row blocks that computes only the active ones:
    ``(y (N, H), captures)``.  Each step runs its expert's SwiGLU on its
    rows and scatter-adds the weighted outputs into the tokens' rows.  With
    ``masks`` and ``probes`` each kind's kept inputs are captured and its
    kept outputs tapped.  The block is rematerialized in the backward, so
    the scan keeps only its gathered rows."""
    N, H = u.shape
    xb = jnp.concatenate([u, jnp.zeros((1, H), u.dtype)])[tok]  # row N: pad

    def block(xb, W, wb, e, pb):
        g = jnp.dot(xb, W[0][e])
        up = jnp.dot(xb, W[1][e])
        if masks is not None:
            g = _tap(g, pb[0], masks[0][1][e])
            up = _tap(up, pb[1], masks[1][1][e])
        a = jax.nn.silu(g) * up
        y = jnp.dot(a, W[2][e])
        if masks is None:
            return y * wb[:, None], ()
        y = _tap(y, pb[2], masks[2][1][e])
        return y * wb[:, None], (jnp.take(xb, masks[0][0][e], axis=-1),
                                 jnp.take(xb, masks[1][0][e], axis=-1),
                                 jnp.take(a, masks[2][0][e], axis=-1))

    def skip(*args):
        return jax.tree.map(jnp.zeros_like, jax.eval_shape(block, *args))

    # the checkpoint holds the cond, so its residuals are its inputs: the
    # scan hoists the loop-invariant weights instead of stacking a slice
    # of them per step
    @jax.checkpoint
    def maybe(xb, W, wb, e, act, pb):
        return jax.lax.cond(act, block, skip, xb, W, wb, e, pb)

    def step(acc, xs):
        xb, tb, wb, e, act, pb = xs
        y, caps = maybe(xb, W, wb, e, act, pb)
        return acc.at[tb].add(y), caps

    pb = probes if probes is not None else ()
    acc, caps = jax.lax.scan(step, jnp.zeros((N + 1, H), u.dtype),
                             (xb, tok, wrow, expert, active, pb))
    return acc[:N], caps


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def _rope_tables(cfg: MoonlightConfig, T: int):
    inv = 1.0 / cfg.rope_theta ** (np.arange(0, cfg.qk_rope, 2)
                                   / cfg.qk_rope)
    f = np.outer(np.arange(T), inv)
    emb = np.concatenate([f, f], axis=-1)
    return (jnp.asarray(np.cos(emb), jnp.float32),
            jnp.asarray(np.sin(emb), jnp.float32))


def _attention(cfg, p, u, lin, cos, sin):
    b, T, _ = u.shape
    nh, dn, dr, dv = cfg.heads, cfg.qk_nope, cfg.qk_rope, cfg.v_head
    with scope("repro.mla.attention"):
        q = lin(u, p["q"]).reshape(b, T, nh, dn + dr)
        kv_a = lin(u, p["kv_a"])
        c = _rms(kv_a[..., :cfg.kv_lora], p["kv_norm"], cfg.eps)
        k_rope = _rope(kv_a[..., cfg.kv_lora:], cos, sin)        # (b, T, dr)
        kv = lin(c, p["kv_b"]).reshape(b, T, nh, dn + dv)
        q = jnp.concatenate([q[..., :dn],
                             _rope(q[..., dn:], cos[:, None], sin[:, None])],
                            axis=-1)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            k_rope[:, :, None], (b, T, nh, dr))], axis=-1)
        s = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(dn + dr)
        causal = np.tril(np.ones((T, T), bool))
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhts,bshd->bthd", a, kv[..., dn:]).reshape(
            b, T, nh * dv)
        return lin(o, p["o"])


def _swiglu(lin, u, Wg, Wu, Wd):
    return lin(jax.nn.silu(lin(u, Wg)) * lin(u, Wu), Wd)


def route(cfg: MoonlightConfig, p: dict, u, lin):
    """Sigmoid scores over every routed expert, the chosen ``top_k`` by
    ``s + b`` and their weights: ``(chosen (..., k), w (..., k))``."""
    s = jax.nn.sigmoid(lin(u, p["router"]))
    _, chosen = jax.lax.top_k(s + p["bias"], cfg.top_k)
    sc = jnp.take_along_axis(s, chosen, axis=-1)
    w = cfg.routed_scale * sc / (jnp.sum(sc, axis=-1, keepdims=True) + 1e-20)
    return chosen, w


BLOCK = 256       # expert-sorted rows per block of the grouped matmul


def moe(cfg: MoonlightConfig, p: dict, u, lin):
    """The MoE layer on ``u``: ``(shared, routed, counts, blocks)`` with
    the shared experts' output, the held experts' part of the routed
    output, the tokens each held expert received per sequence ``(b,
    held)``, and the row blocks' (sequence of each row, expert, active).

    Dropless: the ``b·T·top_k`` (token, choice) pairs are sorted by (held
    expert, sequence), pairs of absent experts dropped from the work, and
    each held expert's rows padded to whole blocks of ``BLOCK``.  The
    grouped matmul is a scan over the blocks that computes only the active
    ones (a ``cond``), each with its own expert's weights, so its work
    follows the tokens routed here; the scan has room for the worst case
    (every choice of every token held), so no pair is ever dropped.  The
    outputs are scatter-added per block, so of the worst-case buffers only
    the gathered rows ``(blocks, BLOCK, H)`` are kept for the backward.
    """
    b, T, H = u.shape
    E, k = len(cfg.held), cfg.top_k
    N, P = b * T, b * T * cfg.top_k
    MB = -(-P // BLOCK) + E
    with scope("repro.moe.route"):
        chosen, w = route(cfg, p, u, lin)
    shared = _swiglu(lin, u, p["shared_gate"], p["shared_up"],
                     p["shared_down"])
    with scope("repro.moe.route"):
        local = np.full((cfg.experts,), E, np.int32)
        local[list(cfg.held)] = np.arange(E, dtype=np.int32)
        pair_local = jnp.asarray(local)[chosen.reshape(-1)]      # (P,)
        held = pair_local < E
        seq = jnp.arange(P, dtype=jnp.int32) // (T * k)
        key = jnp.where(held, pair_local * b + seq, E * b)
        order = jnp.argsort(key, stable=True)
        seg_sizes = jnp.zeros((E * b + 1,), jnp.int32).at[key].add(1)[:-1]
        n_e = seg_sizes.reshape(E, b).sum(axis=1)
        row0 = jnp.cumsum(n_e) - n_e            # expert e's first sorted row
        nblk = (n_e + BLOCK - 1) // BLOCK
        blk_end = jnp.cumsum(nblk)
        blk0 = blk_end - nblk                   # expert e's first block
        blk = jnp.arange(MB, dtype=jnp.int32)
        expert = jnp.searchsorted(blk_end, blk, side="right")
        active = expert < E
        expert = jnp.minimum(expert, E - 1).astype(jnp.int32)
        rank = (blk - blk0[expert])[:, None] * BLOCK + jnp.arange(BLOCK)
        valid = active[:, None] & (rank < n_e[expert][:, None])
        src = jnp.where(valid, row0[expert][:, None] + rank, P)
        tok = jnp.concatenate([order // k, jnp.array([N], order.dtype)])[src]
        wrow = jnp.concatenate([w.reshape(-1)[order],
                                jnp.zeros((1,), w.dtype)])[src]
    with scope("repro.moe.experts"):
        routed = lin.experts(u.reshape(N, H), tok, wrow, p, expert,
                             active).reshape(b, T, H)
    counts = seg_sizes.reshape(E, b).T
    return shared, routed, counts, (tok // T, expert, active)


def _logits(cfg: MoonlightConfig, params: dict, ids, lin):
    """Logits ``(b, T, vocab)``, the tokens each held expert received per
    sequence ``(b, moe layers, held)`` and each MoE layer's row blocks."""
    b, T = ids.shape
    cos, sin = _rope_tables(cfg, T)
    x = jnp.take(params["embed"], ids, axis=0, mode="fill",
                 fill_value=jnp.nan)
    counts, sorts = [], []
    for l, p in enumerate(params["layers"]):
        x = x + _attention(cfg, p, _rms(x, p["attn_norm"], cfg.eps), lin,
                           cos, sin)
        u = _rms(x, p["ffn_norm"], cfg.eps)
        if not is_moe(cfg, l):
            x = x + _swiglu(lin, u, p["gate"], p["up"], p["down"])
            continue
        shared, routed, cnt, srt = moe(cfg, p, u, lin)
        x = x + shared + routed
        counts.append(cnt)
        sorts.append(srt)
    z = jnp.einsum("bth,hv->btv", _rms(x, params["norm"], cfg.eps),
                   params["head"])
    counts = jnp.stack(counts, axis=1) if counts else \
        jnp.zeros((b, 0, len(cfg.held)), jnp.int32)
    return z, counts, sorts


def logits(cfg: MoonlightConfig, params: dict, ids):
    """``(b, T, vocab)`` logits of the plain forward (no probes)."""
    return _logits(cfg, params, ids, _Linears())[0]


def forward(cfg: MoonlightConfig, params: dict, ids, targets, probes,
            masks):
    """Per-sequence summed next-token NLL ``(b,)``, the captured inputs of
    the attributed linears, the tokens each held expert received per
    sequence ``(b, moe layers, held)`` and each MoE layer's row blocks."""
    lin = _Linears(masks, probes)
    z, counts, sorts = _logits(cfg, params, ids, lin)
    tz = jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]
    nll = jax.nn.logsumexp(z, axis=-1) - tz
    return nll.sum(axis=1), lin.captured, counts, sorts


# ----------------------------------------------------------- the source

class MoonlightGradients(GradientSource):
    """Factorized per-sequence gradients of ``f(z)`` (summed next-token
    NLL) for ``GrassPipeline``: ``featurize(ids, targets)`` with ``ids``
    and ``targets`` of shape ``(b, T)``.  Each row is the concatenation of
    ``G[mask_in, mask_out]`` over the attributed linears (``factor_dim``
    coordinates), already sparsified, so the sketch is a plain apply with
    no gather.  ``expert_tokens`` holds the last call's tokens per held
    expert ``(moe layers, held)``."""

    gathers = False

    def __init__(self, cfg: MoonlightConfig):
        self.cfg = cfg
        self.masks = factor_masks(cfg)
        self.shapes = [(len(mi), len(mo)) for mi, mo in self.masks]
        self.dim = factor_dim(cfg)
        self.expert_tokens: Optional[np.ndarray] = None

    def _probes(self, b: int, T: int, R: int) -> list:
        """Zero probes: ``(b, T, keep_out)`` per dense linear; the held
        experts' linears of one kind share one ``(blocks, BLOCK,
        keep_out)`` probe over the sorted row blocks, carried by the
        kind's first expert."""
        out = [None] * len(self.masks)
        for i, (l, name, _, _) in enumerate(linears(self.cfg)):
            ko = self.shapes[i][1]
            if not name.startswith("e"):
                out[i] = jnp.zeros((b, T, ko), jnp.float32)
            elif name.startswith(f"e{self.cfg.held[0]}."):
                out[i] = jnp.zeros((R, BLOCK, ko), jnp.float32)
        return out

    def rows(self, p, ids, targets):
        cfg = self.cfg
        b, T = ids.shape
        R = -(-b * T * cfg.top_k // BLOCK) + len(cfg.held)
        probes = self._probes(b, T, R)

        def loss(pr):
            nll, caps, counts, sorts = forward(cfg, p, ids, targets, pr,
                                               self.masks)
            return nll.sum(), (caps, counts, sorts)

        deltas, (caps, counts, sorts) = jax.grad(loss, has_aux=True)(probes)
        with scope("repro.grass.factors"):
            rows = self._blocks(caps, deltas, sorts, b)
        return rows, counts

    def _blocks(self, caps, deltas, sorts, b):
        """``(b, dim)``: each linear's ``Σ_t x_t[mask_in]ᵀ δ_t[mask_out]``
        per sequence, in layout order.  A held expert's sum runs over the
        sequence's tokens routed to it: each row block's products are
        split by sequence, then summed into the block's expert (padding
        rows carry sequence ``b`` and zero factors)."""
        E = len(self.cfg.held)
        out, c, i, moe_i = [], 0, 0, 0
        names = linears(self.cfg)
        while i < len(names):
            if not names[i][1].startswith("e"):
                blk = jnp.einsum("bti,bto->bio", caps[c], deltas[i])
                out.append(blk.reshape(b, -1))
                c, i = c + 1, i + 1
                continue
            seqs, expert, active = sorts[moe_i]
            # rows split by sequence with a select, not a product, so a
            # quarantined sequence's non-finite rows stay its own
            by_seq = (seqs[..., None] == jnp.arange(b))[..., None]
            by_expert = jax.nn.one_hot(jnp.where(active, expert, E), E,
                                       dtype=jnp.float32)
            kinds = []
            for step in range(3):                # gate, up, down
                x, d = caps[c + step], deltas[i + step]
                per_blk = jnp.einsum(
                    "mrsi,mrso->msio",
                    jnp.where(by_seq, x[:, :, None], 0.0),
                    jnp.where(by_seq, d[:, :, None], 0.0))
                kinds.append(jnp.einsum("me,msio->esio", by_expert,
                                        per_blk).reshape(E, b, -1))
            out.append(jnp.stack(kinds, axis=1)      # (E, 3, b, ki·ko)
                       .transpose(2, 0, 1, 3).reshape(b, -1))
            c, i, moe_i = c + 3, i + 3 * E, moe_i + 1
        return jnp.concatenate(out, axis=1)

    def note(self, counts) -> None:
        counts = np.asarray(counts)
        self.expert_tokens = counts
        if counts.size:
            health_report.record(
                "grass.expert_tokens", n=int(counts.sum()),
                detail=f"tokens per held expert: max {int(counts.max())}, "
                       f"mean {float(counts.mean()):.1f}")
