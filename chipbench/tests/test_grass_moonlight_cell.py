"""The ``grass.featurize.moonlight`` cell at a CPU size: its files, its
configuration against the catalog's published one, the check against the
plain reference with the real limit (and its failure under the bf16
control and under faults in the timed launch), and its four readers."""
from __future__ import annotations

import json
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
DATA = HERE / "tests" / "data"
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402
import program_trace  # noqa: E402

CELL = "grass.featurize.moonlight"
CONFIG = "grass_moonlight16b_ep8_k4096"
READERS = ("featurize.mfu", "featurize.experts_us", "featurize.attention_us",
           "featurize.factors_us")
SECONDS = 0.3
PUBLISHED = {   # Moonlight-16B-A3B config.json, the keys the model reads
    "hidden_size": 2048, "intermediate_size": 11264,
    "moe_intermediate_size": 1408, "n_shared_experts": 2,
    "num_attention_heads": 16, "kv_lora_rank": 512, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_experts_per_tok": 6, "first_k_dense_replace": 1,
    "routed_scaling_factor": 2.446, "rope_theta": 50000,
    "rms_norm_eps": 1e-05, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "norm_topk_prob": True}


@pytest.fixture(scope="module")
def man():
    return harness.manifest()


def tiny_spec(man):
    """The cell as BENCHMARK.json defines it, at a CPU size, with the real
    cell's limits."""
    spec = harness.cell_spec(man, CELL)
    wl = harness.load_json(DATA / "workloads" / f"{CELL}.json")
    wl["check"] = dict(spec.workload["check"])
    spec.workload = wl
    spec.config = harness.load_json(DATA / "configs" / f"{wl['config']}.json")
    return spec


def run_tiny(man, seed=2**33 + 5):
    import jax
    jax.clear_caches()
    return harness.run_cell(CELL, seed, SECONDS, False, spec=tiny_spec(man),
                            require_tpu=False)


# ------------------------------------------------------------ the files

def test_the_cell_finds_its_files(man):
    spec = harness.cell_spec(man, CELL)
    assert spec.entry["chips"] == 1 and spec.entry["config"] == CONFIG
    assert harness.load_module("drivers", spec.workload["driver"])
    assert {m["name"] for m in spec.end_to_end} == {"featurize_rows_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in spec.per_layer} == set(READERS)
    for name in READERS:
        assert callable(harness.load_module("layer_metrics", name).read)


def test_configuration_keeps_the_published_widths(man):
    (entry,) = [c for c in man["configs"] if c["name"] == CONFIG]
    cfg = harness.load_json(ROOT / entry["file"])
    assert cfg["source"] == entry["source"]
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert set(entry["reduced"]) == set(cfg["published"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert cfg["published"] == {"num_hidden_layers": 27,
                                "n_routed_experts": 64, "vocab_size": 163840}
    assert cfg["router_experts"] == 64
    assert len(cfg["held_experts"]) == cfg["n_routed_experts"] == 8
    assert cfg["vocab_size"] * 8 == 163840
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == 4


def test_configuration_matches_the_program(man):
    from repro.attribution import moonlight as ML
    drv = harness.load_module("drivers", "grass_featurize_lm")
    cfg = harness.load_json(HERE / "configs" / f"{CONFIG}.json")
    mc = drv.model_config(cfg)
    assert ML.factor_dim(mc) == cfg["sketch"]["d"] == 552_960
    assert len(ML.linears(mc)) == 135
    ref = harness.load_module("reference", "moonlight_grass")
    assert [(a, b) for *_, a, b in ref.layout(cfg)] == \
        [(a, b) for *_, a, b in ML.linears(mc)]
    drv.program_plan(cfg["sketch"], "float32")


def test_work_counts_the_problem(man):
    """About 1.2 TFLOP a sequence, most of it in the linears; the factor
    products and the sketch are small."""
    w = harness.load_module("work", "moonlight_attrib")
    cfg = harness.load_json(HERE / "configs" / f"{CONFIG}.json")
    f = w.flops(cfg)
    assert 1.1e12 < f["total"] < 1.3e12
    assert f["factors"] < 1e-3 * f["total"]
    assert f["sketch"] == 2 * 8 * 552_960


# ---------------------------------------------------- correct, on the CPU

def test_tiny_cell_is_correct(man):
    res = run_tiny(man)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"featurize_rows_per_s", "setup_s"}


def test_control_fails_the_limit(man):
    import jax
    jax.clear_caches()
    spec = tiny_spec(man)
    driver = harness.load_module("drivers", spec.workload["driver"])
    c = driver.Cell(spec.config, spec.workload, 11)
    c.window(SECONDS)
    c.release()
    program, control = c.checks(), c.control()
    for name, limit in spec.workload["check"].items():
        assert program[name] <= limit
        assert control[name] > 3 * limit, (name, control[name], limit)


def _alter_execute(monkeypatch, how):
    """Break ``lowering.execute`` -- the launch under the sketch."""
    from repro.kernels import lowering
    real = lowering.execute

    def broken(lw, operand, *a, **kw):
        return how(real(lw, operand, *a, **kw))
    monkeypatch.setattr(lowering, "execute", broken)


def _one_answer_altered(Y):
    return Y.at[0, 0].add(1e-2 * (1.0 + abs(Y[0, 0])))


def _half_the_columns_left_out(Y):
    return Y.at[:, Y.shape[1] // 2:].set(0.0)


@pytest.mark.parametrize("fault", [_one_answer_altered,
                                   _half_the_columns_left_out])
def test_fault_in_the_launch_is_not_correct(man, monkeypatch, fault):
    _alter_execute(monkeypatch, fault)
    res = run_tiny(man)
    assert not res["correct"], res["checks"]


def test_a_near_tie_is_resolved_both_ways(man):
    """A token whose last two choices lie within the tie margin: the
    reference returns a row for each choice, and they differ."""
    import numpy as np
    spec = tiny_spec(man)
    ref = harness.load_module("reference", "moonlight_grass")
    driver = harness.load_module("drivers", spec.workload["driver"])
    params, ids, tgt = driver.make_data(3, spec.config)
    assert len(ref.candidates(params, ids[0], tgt[0], spec.config, 0.0)) == 1
    # a margin above every gap makes each token a near tie at every MoE
    # layer: 2 tokens, 2 layers, 16 resolutions
    rows = ref.candidates(params, ids[0, :2], tgt[0, :2], spec.config, 1.0)
    assert len(rows) == 16
    assert max(float(np.max(np.abs(r - rows[0]))) for r in rows[1:]) > 0


# ------------------------------------------------------------ the readers

def _ctx(config, rows=64, busy_s=2.0):
    prog = program_trace.Program(
        window_s=2.5, span_s={}, span_n={}, idle_s={},
        scope_s={"repro.grass.grads/repro.mla.attention": 0.5,
                 "repro.grass.grads/repro.moe.route": 0.01,
                 "repro.grass.grads/repro.moe.experts": 0.2,
                 "repro.grass.grads/repro.grass.factors": 0.03,
                 "repro.grass.sketch/repro.ops.sketch_apply": 0.01,
                 "unscoped": 0.1},
        scope_kernel_s={})
    trace = types.SimpleNamespace(busy_s=busy_s, window_s=2.5)
    return types.SimpleNamespace(
        trace=trace, program=prog, counts={"rows": rows}, config=config,
        workload={}, peaks=lambda: harness.peaks("TPU v5 lite"),
        work=lambda op: harness.load_module("work", op))


def test_readers_read_a_synthetic_trace():
    cfg = harness.load_json(HERE / "configs" / f"{CONFIG}.json")
    ctx = _ctx(cfg)
    got = {n: harness.load_module("layer_metrics", n).read(ctx)
           for n in READERS}
    assert got["featurize.attention_us"] == pytest.approx(1e6 * 0.5 / 64)
    assert got["featurize.experts_us"] == pytest.approx(1e6 * 0.2 / 64)
    assert got["featurize.factors_us"] == pytest.approx(1e6 * 0.03 / 64)
    w = harness.load_module("work", "moonlight_attrib").flops(cfg)["total"]
    assert got["featurize.mfu"] == pytest.approx(100 * w * 64
                                                 / (2.0 * 197e12))
    assert 0 < got["featurize.mfu"] < 100


def test_readers_are_silent_without_their_markers():
    cfg = harness.load_json(HERE / "configs" / "grass_mlp109k_k1024.json")
    ctx = _ctx(cfg)
    ctx.program.scope_s = {"repro.grass.grads": 1.0}
    for name in READERS:
        assert harness.load_module("layer_metrics", name).read(ctx) is None


def test_readers_read_the_recorded_grass_trace():
    """The MLP cell's recorded trace has none of the model's scopes: the
    scope readers are silent there, as on a parent program."""
    prog = program_trace.reduce_file(DATA / "grass_program.xplane.pb")
    ctx = _ctx({}, rows=60_000)
    ctx.program = prog
    for name in READERS[1:]:
        assert harness.load_module("layer_metrics", name).read(ctx) is None
    assert json.dumps(prog.scope_s)
