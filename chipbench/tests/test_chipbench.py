"""Tests of the chip benchmark that run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

They never touch the TPU library: the cells run at tiny sizes from
``tests/data`` with the harness's look for a chip skipped, and the limits
of ``correct`` are the real cells' limits.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
DATA = HERE / "tests" / "data"
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402

CELLS = ("sketch.apply", "sketch.apply.jit", "grass.featurize")
SECONDS = 0.3        # a tiny cell's window
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def man():
    return harness.manifest()


def tiny_spec(man, cell):
    """The cell as BENCHMARK.json defines it, at a CPU size, with the real
    cell's limits."""
    spec = harness.cell_spec(man, cell)
    wl = harness.load_json(DATA / "workloads" / f"{cell}.json")
    wl["check"] = dict(spec.workload["check"])
    spec.workload = wl
    spec.config = harness.load_json(DATA / "configs" / f"{wl['config']}.json")
    return spec


def run_tiny(man, cell, seed=2**33 + 3):
    import jax
    jax.clear_caches()
    return harness.run_cell(cell, seed, SECONDS, False,
                            spec=tiny_spec(man, cell), require_tpu=False)


# ---------------------------------------------------------------- manifest

def test_manifest_keys_and_names(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert man["paths"] == ["chipbench"]
    names = [c["name"] for c in man["configs"]] + \
        [w["name"] for w in man["workloads"]] + \
        [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"])
               for m in man["end_to_end"] + man["per_layer"])
    assert {m["name"] for m in man["end_to_end"]} >= {"setup_s"}
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["name"]: m for m in man["end_to_end"]}
    for m in man["per_layer"]:
        assert m["moves"] in layers


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files(man, cell):
    spec = harness.cell_spec(man, cell)
    assert spec.config["name"] == spec.entry["config"]
    assert (HERE / "drivers" / f"{spec.workload['driver']}.py").is_file()
    assert hasattr(harness.load_module("drivers", spec.workload["driver"]),
                   "Cell")
    assert {m["name"] for m in spec.end_to_end} >= {"setup_s"}
    assert len(spec.end_to_end) >= 2 and spec.per_layer
    for m in spec.per_layer:
        assert callable(harness.load_module("layer_metrics", m["name"]).read)
    config_entry = [c for c in man["configs"]
                    if c["name"] == spec.entry["config"]][0]
    assert config_entry["file"] == \
        f"chipbench/configs/{spec.entry['config']}.json"
    assert config_entry["source"] == spec.config["source"]


def test_every_config_is_used(man):
    used = {w["config"] for w in man["workloads"]}
    assert used == {c["name"] for c in man["configs"]}


# ------------------------------------------------------------ work, peaks

def test_sketch_apply_work_at_the_cell_shape(man):
    spec = harness.cell_spec(man, "sketch.apply")
    work = harness.load_module("work", "sketch_apply")
    nbytes, ops = work.work(spec.config["sketch"], 512)
    assert nbytes == 262144 * 512 * 4 + 1024 * 512 * 4 == 538_968_064
    assert ops == 2 * 8 * 262144 * 512
    ideal = work.ideal_s(spec.config["sketch"], 512,
                         harness.peaks("TPU v5 lite"))
    assert ideal == pytest.approx(538_968_064 / 819e9)


def test_global_family_nnz():
    work = harness.load_module("work", "sketch_apply")
    sk = {"family": "countsketch", "d": 1000, "k": 64, "kappa": 8, "s": 1}
    assert work.nnz(sk) == 1000


def test_peaks_refuse_an_unknown_device():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("TPU v99")


# ------------------------------------------------------------------ trace

def test_trace_reduces_the_recorded_trace():
    """``small.xplane.pb``: one un-jitted sketch apply at d=262144, n=512,
    recorded on a TPU v5 lite.  Its device ops, read by hand: the Pallas
    kernel from 1480376515 to 1483506804 ns and a copy from 1486670294 to
    1486677296 ns; the window span runs from 46897029 to 1487740396 ns,
    and the host sat in ``dispatch`` for nearly all of it."""
    red = harness.load_module("", "trace").reduce_file(
        DATA / "small.xplane.pb")
    kernel = (1483506804 - 1480376515) * 1e-9
    copy = (1486677296 - 1486670294) * 1e-9
    window = (1487740396 - 46897029) * 1e-9
    assert red.kernel_s == pytest.approx(kernel, rel=1e-12)
    assert red.busy_s == pytest.approx(kernel + copy, rel=1e-12)
    assert red.window_s == pytest.approx(window, rel=1e-12)
    assert red.idle_pct() == pytest.approx(
        100 * (1 - (kernel + copy) / window), rel=1e-12)
    assert red.op_s == pytest.approx({
        "jit_wrapped/tpu_custom_call.1 custom-call": kernel,
        "jit_reshape/copy.1 copy": copy}, rel=1e-12)
    assert red.gap_s == pytest.approx({"dispatch": window - kernel - copy},
                                      rel=1e-12)
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["jit_wrapped/tpu_custom_call.1 custom-call",
                                   red.op_s["jit_wrapped/tpu_custom_call.1 "
                                            "custom-call"]]
    assert [g[0] for g in bd["idle_gaps"]] == ["dispatch"]


def test_kernel_is_a_tpu_custom_call():
    tr = harness.load_module("", "trace")
    assert tr.is_kernel('%k.3 = f32[8,128]{1,0} custom-call(f32[8,128] %a), '
                        'custom_call_target="tpu_custom_call"')
    assert not tr.is_kernel('%c.1 = f32[8]{0} custom-call(f32[8] %a), '
                            'custom_call_target="Sharding"')
    assert not tr.is_kernel('%copy.1 = f32[1024,512]{1,0} copy(f32[1024,512]'
                            '{1,0} %bitcast.1)')


def test_trace_interval_union():
    tr = harness.load_module("", "trace")
    assert tr._union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    gaps = [(0, 10), (20, 30)]
    spans = [(0, 6, "dispatch"), (6, 12, "wait_result"), (18, 40, "solve")]
    assert tr._label_gaps(gaps, spans) == ["dispatch", "solve"]


# ---------------------------------------------------------- program builds

def test_build_counter_tells_cache_loads_from_compiles():
    import jax
    counter = harness.BuildCounter()
    before = counter.snapshot()
    jax.monitoring.record_event_duration_secs(harness.BUILD_EVENT, 0.1)
    jax.monitoring.record_event_duration_secs(harness.BUILD_EVENT, 0.2)
    jax.monitoring.record_event(harness.CACHE_HIT_EVENT)
    after = counter.snapshot()
    assert after["builds"] - before["builds"] == 2
    assert after["cache_loads"] - before["cache_loads"] == 1


def test_builds_per_call_reader():
    import types
    read = harness.load_module("layer_metrics", "apply.builds_per_call").read
    assert read(types.SimpleNamespace(counts={"calls": 4, "builds": 6})) \
        == 1.5
    assert read(types.SimpleNamespace(counts={"calls": 0, "builds": 0})) \
        is None


@pytest.mark.parametrize("cell", ["sketch.apply", "sketch.apply.jit"])
def test_eager_calls_build_and_jitted_calls_do_not(man, cell):
    """The public call, made eagerly, builds its program again in every
    call; inside ``jax.jit`` it builds nothing after the warm-up."""
    import jax
    jax.clear_caches()
    spec = tiny_spec(man, cell)
    counter = harness.BuildCounter()
    c = harness.load_module("drivers", spec.workload["driver"]).Cell(
        spec.config, spec.workload, 3)
    before = counter.snapshot()
    res = c.window(SECONDS)
    builds = counter.snapshot()["builds"] - before["builds"]
    if spec.workload["traffic"]["jit"]:
        assert builds == 0
    else:
        assert builds >= res.counts["calls"]


# ------------------------------------------------------------ the command

def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "sketch.apply",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_a_new_cell_is_one_data_file(tmp_path, man):
    """A cell of an existing configuration under another precision policy
    takes one new workload file and entries in BENCHMARK.json."""
    copy = tmp_path / "chipbench"
    shutil.copytree(HERE, copy, ignore=shutil.ignore_patterns("__pycache__"))
    wl = json.loads((copy / "workloads" / "sketch.apply.json").read_text())
    wl["traffic"]["policy"] = "fp8_e4m3_sr"
    wl["check"] = {"rel_err": 0.2}
    (copy / "workloads" / "sketch.apply.fp8sr.json").write_text(
        json.dumps(wl))
    new = json.loads(json.dumps(man))
    new["workloads"].append({"name": "sketch.apply.fp8sr",
                             "config": "blockperm_d262144_k1024",
                             "traffic": "closed_fp8sr_n512", "chips": 1,
                             "why": "the fp8 stochastic-rounding stream"})
    for m in new["end_to_end"] + new["per_layer"]:
        if "sketch.apply" in m.get("workloads", []):
            m["workloads"].append("sketch.apply.fp8sr")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    spec_mod = _load_copy(copy)
    spec = spec_mod.cell_spec(spec_mod.manifest(tmp_path),
                              "sketch.apply.fp8sr")
    assert spec.workload["traffic"]["policy"] == "fp8_e4m3_sr"
    assert {m["name"] for m in spec.end_to_end} == {"apply_ms", "setup_s"}
    assert {m["name"] for m in spec.per_layer} == {
        "sketch_apply_roofline", "apply.idle_pct", "apply.builds_per_call"}
    spec.config = harness.load_json(DATA / "configs" / "tiny_sketch.json")
    spec.workload["traffic"].update(n=128)
    res = spec_mod.run_cell("sketch.apply.fp8sr", 5, 0.2, False, spec=spec,
                            require_tpu=False)
    assert res["metrics"]["apply_ms"]["value"] > 0
    assert res["attempted"] >= 1


def _load_copy(copy: Path):
    import importlib.util
    spec = importlib.util.spec_from_file_location("harness_copy",
                                                  copy / "harness.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["harness_copy"] = mod
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------- correct, on the CPU

@pytest.mark.parametrize("cell", CELLS)
def test_tiny_cell_is_correct(man, cell):
    res = run_tiny(man, cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    spec = harness.cell_spec(man, cell)
    assert set(res["metrics"]) == {m["name"] for m in spec.end_to_end}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limit(man, cell):
    """The reference one precision down, in the program's place, reads
    above every limit it is compared with."""
    import jax
    jax.clear_caches()
    spec = tiny_spec(man, cell)
    driver = harness.load_module("drivers", spec.workload["driver"])
    c = driver.Cell(spec.config, spec.workload, 11)
    c.window(SECONDS)
    c.release()
    control = c.control()
    program = c.checks()
    assert control.keys() == spec.workload["check"].keys()
    for name, limit in spec.workload["check"].items():
        assert program[name] <= limit
        assert control[name] > 3 * limit, (name, control[name], limit)


# ---------------------------------------- faults under the timed path

def _alter_execute(monkeypatch, how):
    """Break ``lowering.execute`` -- the launch under every sketch path."""
    from repro.kernels import lowering
    real = lowering.execute

    def broken(lw, operand, *a, **kw):
        Y = real(lw, operand, *a, **kw)
        return how(Y)
    monkeypatch.setattr(lowering, "execute", broken)


def _one_answer_altered(Y):
    return Y.at[0, 0].add(1e-2 * (1.0 + abs(Y[0, 0])))


def _half_the_columns_left_out(Y):
    return Y.at[:, Y.shape[1] // 2:].set(0.0)


@pytest.mark.parametrize("cell,fault", [
    ("sketch.apply", _one_answer_altered),
    ("sketch.apply", _half_the_columns_left_out),
    ("sketch.apply.jit", _one_answer_altered),
    ("sketch.apply.jit", _half_the_columns_left_out),
    ("grass.featurize", _one_answer_altered),
    ("grass.featurize", _half_the_columns_left_out),
])
def test_fault_in_the_launch_is_not_correct(man, monkeypatch, cell, fault):
    _alter_execute(monkeypatch, fault)
    res = run_tiny(man, cell)
    assert not res["correct"], res["checks"]
