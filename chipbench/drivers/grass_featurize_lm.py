"""Closed-loop GraSS featurize of a language model's factorized
per-sequence gradients.  Each call is ``GrassPipeline.featurize`` over the
same device-resident sequences with the Moonlight gradient source
(``repro.attribution.moonlight``), and waits for the (sequences, k)
features.

The configuration gives everything: the published widths under their
``config.json`` keys, what this chip holds (layers, routed experts, the
vocabulary slice), the factor keep, the sketch, the sequences per call,
their length, the sequences per scan step (``chunk``) and the matmul
precision.  Weights and token ids are drawn from the seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import harness
from drivers.common import program_plan
from harness import WindowResult, closed_loop, span
from reference import moonlight_grass as ref_lm

LAST_ROWS = 4        # rows of the window's last call checked, from the seed
CONTROL_ROWS = 4     # sequences the bfloat16 control is computed for

# what the model implements of a deepseek_v3 config.json
SUPPORTED = {"q_lora_rank": None, "scoring_func": "sigmoid",
             "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
             "norm_topk_prob": True, "hidden_act": "silu",
             "tie_word_embeddings": False, "moe_layer_freq": 1}


def model_config(cfg: dict):
    """The program's ``MoonlightConfig`` for a configuration file."""
    from repro.attribution.moonlight import MoonlightConfig
    for key, want in SUPPORTED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key}={cfg[key]!r}: the model implements "
                             f"{want!r}")
    if len(cfg["held_experts"]) != cfg["n_routed_experts"]:
        raise ValueError("held_experts must list n_routed_experts experts")
    return MoonlightConfig(
        hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        qk_nope=cfg["qk_nope_head_dim"], qk_rope=cfg["qk_rope_head_dim"],
        v_head=cfg["v_head_dim"], kv_lora=cfg["kv_lora_rank"],
        dense_width=cfg["intermediate_size"],
        expert_width=cfg["moe_intermediate_size"],
        shared_experts=cfg["n_shared_experts"],
        experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        held=tuple(cfg["held_experts"]),
        routed_scale=cfg["routed_scaling_factor"],
        layers=cfg["num_hidden_layers"],
        dense_layers=cfg["first_k_dense_replace"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        keep=cfg["factor_keep"])


def make_data(seed: int, cfg: dict):
    """Seeded weights (projections N(0, 1/fan_in), embedding N(0, 1),
    norms 1, expert-choice bias 0; held experts stacked) and token ids
    uniform over the vocabulary slice, in one jitted call."""
    H, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    lora, V = cfg["kv_lora_rank"], cfg["vocab_size"]
    E, Fe = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    Fs = Fe * cfg["n_shared_experts"]
    S, T = cfg["sequences"], cfg["sequence_length"]

    @jax.jit
    def build(key):
        kp, kt = jax.random.split(key)
        n = [0]

        def w(*shape):
            n[0] += 1
            return (jax.random.normal(jax.random.fold_in(kp, n[0]), shape,
                                      jnp.float32) / np.sqrt(shape[-2]))

        def ones(d):
            return jnp.ones((d,), jnp.float32)
        layers = []
        for l in range(cfg["num_hidden_layers"]):
            p = {"attn_norm": ones(H), "q": w(H, nh * (nope + rope)),
                 "kv_a": w(H, lora + rope), "kv_norm": ones(lora),
                 "kv_b": w(lora, nh * (nope + dv)), "o": w(nh * dv, H),
                 "ffn_norm": ones(H)}
            if l < cfg["first_k_dense_replace"]:
                F = cfg["intermediate_size"]
                p.update(gate=w(H, F), up=w(H, F), down=w(F, H))
            else:
                p.update(router=w(H, cfg["router_experts"]),
                         bias=jnp.zeros((cfg["router_experts"],),
                                        jnp.float32),
                         shared_gate=w(H, Fs), shared_up=w(H, Fs),
                         shared_down=w(Fs, H), gate=w(E, H, Fe),
                         up=w(E, H, Fe), down=w(E, Fe, H))
            layers.append(p)
        params = {"embed": jax.random.normal(jax.random.fold_in(kp, 0),
                                             (V, H), jnp.float32),
                  "layers": layers, "norm": ones(H), "head": w(H, V)}
        ids = jax.random.randint(kt, (S, T + 1), 0, V, jnp.int32)
        return params, ids[:, :-1], ids[:, 1:]

    return jax.block_until_ready(build(harness.seed_key(seed)))


class Cell:
    def __init__(self, config: dict, workload: dict, seed: int):
        from repro.attribution.grass import GrassPipeline, GrassPipelineConfig
        from repro.attribution.moonlight import MoonlightGradients
        self.cfg = config
        self.seed = seed
        self.precision = config["matmul_precision"]
        self.params, self.ids, self.tgt = make_data(seed, config)
        sk = config["sketch"]
        program_plan(sk, "float32")
        self.source = MoonlightGradients(model_config(config))
        if self.source.dim != sk["d"]:
            raise RuntimeError(f"the program's rows have {self.source.dim} "
                               f"coordinates; the configuration states "
                               f"{sk['d']}")
        with jax.default_matmul_precision(self.precision):
            self.pipe = GrassPipeline(GrassPipelineConfig(
                sketch_dim=sk["k"], seed=sk["seed"], chunk=config["chunk"]),
                self.params, source=self.source)
            jax.block_until_ready(self.pipe.featurize(self.ids, self.tgt))
        self.first = self.last = None

    def window(self, seconds: float) -> WindowResult:
        rows = self.ids.shape[0]
        last = {}

        def call(i):
            with jax.default_matmul_precision(self.precision):
                with span("featurize"):
                    F = self.pipe.featurize(self.ids, self.tgt)
                with span("wait_result"):
                    F.block_until_ready()
            if i == 0:
                self.first = F
            last["F"] = F

        n, elapsed = closed_loop(call, seconds)
        self.last = last["F"]
        tokens = self.source.expert_tokens
        return WindowResult(
            end_to_end={"featurize_rows_per_s": n * rows / elapsed},
            attempted=n, failed=0,
            counts={"calls": n, "rows": n * rows, "window_s": elapsed},
            info={"calls": n, "window_s": elapsed,
                  "expert_tokens_max": int(tokens.max()),
                  "expert_tokens_mean": float(tokens.mean())})

    def release(self) -> None:
        self.pipe = None

    def _reference(self, seqs, precision: str = "float32",
                   tie_margin: float = ref_lm.TIE_MARGIN) -> dict:
        """``{i: (m, k)}`` reference features of the sequences ``seqs``, one
        row per resolution of the sequence's near routing ties (the first
        takes every choice as the reference's forward makes it), sketched
        in one pass."""
        rows = {i: ref_lm.candidates(self.params, self.ids[i], self.tgt[i],
                                     self.cfg, tie_margin, precision)
                for i in seqs}
        feats = ref_lm.sketch_rows(np.concatenate(list(rows.values())),
                                   self.cfg, precision)
        out, at = {}, 0
        for i, r in rows.items():
            out[i] = feats[at:at + len(r)]
            at += len(r)
        return out

    @staticmethod
    def _errs(got: dict, want: dict) -> dict:
        """``{i: [max|got_i − want_ij| for each reference row j]}`` over the
        rows ``got {sequence: (k,) features}``."""
        return {i: [float(np.max(np.abs(np.asarray(row, np.float64) - w)))
                    for w in want[i]] for i, row in got.items()}

    @classmethod
    def _rel_err(cls, got: dict, want: dict) -> float:
        """``max_i min_j max|got_i − want_ij| / max_i max|want_i0|``."""
        err = max(min(e) for e in cls._errs(got, want).values())
        return err / max(float(np.max(np.abs(want[i][0]))) for i in got)

    def checks(self) -> dict:
        """Every row of the first call of the window, and ``LAST_ROWS``
        rows of the last drawn from the seed, against the reference."""
        S = self.ids.shape[0]
        first = np.asarray(self.first)
        last = np.asarray(self.last)
        rng = np.random.default_rng([self.seed, 0xC4EC])
        picked = rng.choice(S, size=min(LAST_ROWS, S), replace=False)
        want = self._reference(range(S))
        got = [{i: first[i] for i in range(S)},
               {int(i): last[i] for i in picked}]
        errs = [e for g in got for e in self._errs(g, want).values()]
        scale = max(float(np.max(np.abs(w[0]))) for w in want.values())
        # readings for the tie margin: the rows that only another
        # resolution than the forward's own matches (10x nearer), and the
        # error against the forward's own (the check at a margin of 0)
        return {"rel_err": max(self._rel_err(g, want) for g in got),
                "near_tie_sequences": sum(len(w) > 1 for w in want.values()),
                "other_choice_rows": sum(e[0] > 10 * min(e) for e in errs),
                "rel_err_first": max(e[0] for e in errs) / scale}

    def control(self) -> dict:
        """The checks' numbers with the reference at bfloat16 put in the
        program's place, for the first ``CONTROL_ROWS`` sequences."""
        seqs = range(min(CONTROL_ROWS, self.ids.shape[0]))
        low = self._reference(seqs, "bfloat16", -np.inf)
        return {"rel_err": self._rel_err({i: low[i][0] for i in seqs},
                                         self._reference(seqs))}
