"""The work that one sequence's factorized GraSS features need, from the
configuration alone, whatever implements them: the model's forward, the
backward to every attributed linear's output (activations only, no
weight gradient), the masked factor products, and the sketch
(``work/sketch_apply.py``).

Counted as the problem needs it, so that no implementation can read more:
causal attention over the ``t + 1`` keys each query sees; each held expert
on the tokens routed to it, at the expected ``T · top_k · held / experts``
expert evaluations a sequence; the backward through every linear except
those whose input gradient nothing uses (the first layer's ``q`` and
``kv_a``, fed by the embedding); two multiplies per multiply-add.
"""
from __future__ import annotations

import harness
from reference import moonlight_grass as ref_lm

_sketch = harness.load_module("work", "sketch_apply")


def _linear_macs(cfg: dict) -> dict:
    """Multiply-adds per token of each kind of linear, over all layers."""
    H, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    lora = cfg["kv_lora_rank"]
    L, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    Fe = cfg["moe_intermediate_size"]
    share = (cfg["num_experts_per_tok"] * len(cfg["held_experts"])
             / cfg["router_experts"])
    return {
        "attention": L * (H * nh * (nope + rope) + H * (lora + rope)
                          + lora * nh * (nope + dv) + nh * dv * H),
        "dense": dense * 3 * H * cfg["intermediate_size"],
        "router": (L - dense) * H * cfg["router_experts"],
        "shared": (L - dense) * 3 * H * Fe * cfg["n_shared_experts"],
        "experts": (L - dense) * share * 3 * H * Fe,
        "head": H * cfg["vocab_size"],
    }


def flops(cfg: dict) -> dict:
    """Operations per sequence by part, and their ``total``."""
    T = cfg["sequence_length"]
    H, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    lin = _linear_macs(cfg)
    # forward and activation backward through each linear, less the
    # input gradient of the first layer's q and kv_a
    out = {k: 2 * 2 * T * v for k, v in lin.items()}
    out["attention"] -= 2 * T * H * (nh * (nope + rope)
                                     + cfg["kv_lora_rank"] + rope)
    # causal scores (nope + rope) and p·v (v) forward, twice that backward
    pairs = T * (T + 1) // 2
    out["scores"] = (2 * 3 * cfg["num_hidden_layers"] * nh * pairs
                     * (nope + rope + dv))
    keep = cfg["factor_keep"]
    share = (cfg["num_experts_per_tok"] * len(cfg["held_experts"])
             / cfg["router_experts"])
    factors = 0.0
    for _, _, e, a, b in ref_lm.layout(cfg):
        tokens = T * share / len(cfg["held_experts"]) if e is not None else T
        factors += 2 * tokens * min(keep, a) * min(keep, b)
    out["factors"] = factors
    out["sketch"] = _sketch.work(cfg["sketch"], 1)[1]
    out["total"] = sum(out.values())
    return out

