"""Program spans, scopes and counters on the sketch hot path
(``repro.tracing``, ``repro.health.report``)."""
import ast
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.attribution import mlp as M
from repro.attribution.grass import GrassPipeline, GrassPipelineConfig
from repro.core.blockperm import make_plan
from repro.health import report
from repro.kernels import flashsketch, lowering, ops


@pytest.fixture(scope="module")
def plan():
    return make_plan(256, 64, kappa=2, s=2, block_rows=8, seed=4)


def _operand(plan, n=128):
    return jnp.asarray(np.random.default_rng(0).standard_normal(
        (plan.d, n)), jnp.float32)


def test_scope_names_the_ops_traced_under_it():
    def f(x):
        with tracing.scope("repro.test.step"):
            return jnp.sin(x) * 2.0
    text = jax.jit(f).lower(jnp.ones(4)).as_text(debug_info=True)
    assert "repro.test.step" in text


def test_jitted_sketch_apply_carries_the_kernel_scope(plan):
    A = _operand(plan)
    text = jax.jit(lambda a: ops.sketch_apply(plan, a, "pallas")).lower(
        A).as_text(debug_info=True)
    for name in ("repro.ops.sketch_apply", "repro.lowering.execute",
                 "repro.kernel.flashsketch_fwd"):
        assert name in text, name


def test_featurize_carries_the_grass_scopes():
    cfg = M.MLPConfig(d_in=64, hidden=(16,), steps=1)
    x, y = M.make_synthetic_mnist(16, 64, seed=0)
    params = M.init_mlp(cfg, jax.random.PRNGKey(0))
    pipe = GrassPipeline(GrassPipelineConfig(sparse_dim=256, sketch_dim=64,
                                             chunk=8), params)
    text = pipe._featurize.lower(params, jnp.asarray(x), jnp.asarray(y)) \
        .as_text(debug_info=True)
    for name in ("repro.grass.grads", "repro.grass.quarantine",
                 "repro.grass.sketch"):
        assert name in text, name


def _moonlight_pipe():
    from repro.attribution import moonlight as ML
    cfg = ML.MoonlightConfig(
        hidden=32, heads=2, qk_nope=8, qk_rope=4, v_head=8, kv_lora=16,
        dense_width=32, expert_width=8, experts=8, top_k=2, held=(0, 1),
        layers=2, vocab=64)
    params = ML.init_params(cfg, jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (3, 8), 0, cfg.vocab)
    pipe = GrassPipeline(GrassPipelineConfig(sketch_dim=64, chunk=2), params,
                         source=ML.MoonlightGradients(cfg))
    return pipe, params, ids


def test_moonlight_featurize_carries_the_model_scopes():
    """The factorized source's steps are scoped inside the GraSS steps:
    attention, routing and experts (forward and backward) and the factor
    products under ``repro.grass.grads``."""
    pipe, params, ids = _moonlight_pipe()
    text = pipe._featurize.lower(params, ids, ids).as_text(debug_info=True)
    for name in ("repro.grass.grads", "repro.grass.quarantine",
                 "repro.grass.sketch", "repro.mla.attention",
                 "repro.moe.route", "repro.moe.experts",
                 "repro.grass.factors"):
        assert name in text, name
    assert "repro.grass.grads/repro.grass.factors" in text


def test_moonlight_featurize_records_expert_tokens():
    pipe, params, ids = _moonlight_pipe()
    report.reset_counters()
    pipe.featurize(ids, ids)
    seen = pipe.source.expert_tokens
    assert seen.shape == (1, 2)
    assert report.counters()["grass.expert_tokens"] == int(seen.sum()) > 0
    (event, detail), = [e for e in report.recent_events()
                        if e[0] == "grass.expert_tokens"]
    assert f"max {int(seen.max())}" in detail and "mean" in detail


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    (path,) = Path(trace_dir).rglob("*.xplane.pb")
    spans = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    spans.setdefault(ev.name, []).append(
                        (int(ev.start_ns), int(ev.end_ns)))
    return spans


@pytest.mark.parametrize("memo", ["miss", "hit"])
def test_eager_call_records_nested_host_spans(plan, tmp_path, memo):
    """An eager call nests ops -> execute -> the kernel's launch span, with
    the lowering before the launch.  A miss (fresh memos) builds the
    tables and traces the launch inside execute; a hit runs the memoized
    executor under the kernel span alone, with no tables span."""
    A = _operand(plan)
    if memo == "miss":
        lowering.clear_lowering_cache()
    else:
        ops.sketch_apply(plan, A, "pallas").block_until_ready()   # warm
    with jax.profiler.trace(str(tmp_path)):
        ops.sketch_apply(plan, A, "pallas").block_until_ready()
    spans = _host_spans(tmp_path)
    (outer,) = spans["repro.ops.sketch_apply"]
    (mid,) = spans["repro.lowering.execute"]
    (inner,) = spans["repro.kernel.flashsketch_fwd"]
    assert outer[0] <= mid[0] <= inner[0] < inner[1] <= mid[1] <= outer[1]
    (lower,) = spans["repro.lowering.lower"]
    assert outer[0] <= lower[0] < lower[1] <= mid[0]
    if memo == "miss":
        (tables,) = spans["repro.kernel.tables"]
        assert mid[0] <= tables[0] < tables[1] <= inner[0]
    else:
        assert "repro.kernel.tables" not in spans


def test_memo_counters_count_lookups(plan):
    lowering.clear_lowering_cache()
    report.reset_counters()
    spec = lowering.LaunchSpec(op="fwd", n=96, impl="pallas")
    first = lowering.lower(plan, spec)
    second = lowering.lower(plan, spec)
    assert first is second
    assert report.counters() == {"lowering.memo_miss": 1,
                                 "lowering.memo_hit": 1}
    assert "lowering.memo_hit=1" in lowering.explain(plan, spec)


def _pallas_calls(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "pallas_call":
            yield node


def test_every_pallas_call_passes_a_name():
    tree = ast.parse(Path(flashsketch.__file__).read_text())
    calls = list(_pallas_calls(tree))
    assert calls
    for call in calls:
        assert "name" in {k.arg for k in call.keywords}, call.lineno


@pytest.mark.parametrize("entry,kernel,kwargs", [
    ("sketch_apply_t", "flashsketch_transpose", {}),
    ("blockrow_apply", "flashsketch_blockrow", {}),
    ("sketch_apply", "flashsketch_fwd_v1", {"impl": "pallas_v1"}),
])
def test_each_entry_names_its_kernel(plan, entry, kernel, kwargs):
    """Every public entry runs under ``repro.ops.<entry>`` and launches
    under ``repro.kernel.<kernel>``: both land in the lowered program."""
    X = _operand(plan) if entry != "sketch_apply_t" else \
        jnp.ones((plan.k, 128), jnp.float32)
    impl = kwargs.get("impl", "pallas")
    fn = getattr(ops, entry)
    text = jax.jit(lambda a: fn(plan, a, impl)).lower(X).as_text(
        debug_info=True)
    assert f"repro.ops.{entry}" in text
    assert f"repro.kernel.{kernel}" in text
