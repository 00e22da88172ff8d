"""Tests of the reader of the program's spans and scopes
(``program_trace.py``), on the CPU:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

``small.xplane.pb`` was recorded from a program without spans or scopes;
``apply_program.xplane.pb`` and ``grass_program.xplane.pb`` were recorded
on a TPU v5 lite from a program with them, by ``tools/record_trace.py``
(one warm eager ``sketch.apply`` call; one ``grass.featurize`` call over
256 examples).  The numbers below were read from the files by hand.
"""
from __future__ import annotations

import shutil
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
DATA = HERE / "tests" / "data"
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import program_trace as pt  # noqa: E402

tr = harness.load_module("", "trace")


def _read(metric, prog, **counts):
    return harness.load_module("layer_metrics", metric).read(
        types.SimpleNamespace(trace=prog, program=prog, counts=counts))


# ------------------------------------------------------------- helpers

def test_scope_path_keeps_the_program_segments():
    assert pt.scope_path("jit(featurize)/while/body/repro.grass.sketch/"
                         "repro.ops.sketch_apply/pallas_call:") == \
        "repro.grass.sketch/repro.ops.sketch_apply"
    assert pt.scope_path("jit(f)/repro.grass.grads/transpose(jvp("
                         "repro.grass.grads))/dot_general") == \
        "repro.grass.grads"
    assert pt.scope_path("jit(wrapped)/pallas_call:") == pt.UNSCOPED
    assert pt.scope_path("") == pt.UNSCOPED


def test_innermost_pieces_of_nested_spans():
    spans = [(0, 10, "a"), (2, 5, "b"), (6, 8, "c"), (3, 4, "d"),
             (12, 14, "e"), (13, 20, "f")]
    assert pt._innermost(spans) == [
        (0, 2, "a"), (2, 3, "b"), (3, 4, "d"), (4, 5, "b"), (5, 6, "a"),
        (6, 8, "c"), (8, 10, "a"), (12, 13, "e"), (13, 14, "f")]


def test_split_gaps_by_program_span():
    """Gaps and pieces in picoseconds; the split in seconds."""
    pieces = [(0, 2, "a"), (2, 5, "b"), (8, 9, "c")]
    labelled = [((1, 4), "dispatch"), ((4, 10), "wait_result")]
    got = pt._split_gaps(labelled, pieces)
    assert got == pytest.approx({
        "dispatch/a": 1e-12, "dispatch/b": 2e-12, "wait_result/b": 1e-12,
        "wait_result/c": 1e-12, "wait_result/unspanned": 4e-12}, rel=1e-9)


# ------------------------------------- a trace without program markers

def test_tf_op_reader_finds_the_eager_kernel_unscoped():
    sp = pt.read_space(DATA / "small.xplane.pb")
    ((plane, ops),) = sp.device.items()
    assert plane == "/device:TPU:0"
    assert [op[3] for op in ops] == ["jit(wrapped)/pallas_call:", ""]
    assert tr.is_kernel(ops[0][2]) and not tr.is_kernel(ops[1][2])
    red = tr.reduce_file(DATA / "small.xplane.pb")
    prog = pt.reduce_file(DATA / "small.xplane.pb")
    # the same times, read in picoseconds here and in nanoseconds there
    assert prog.scope_s == pytest.approx({pt.UNSCOPED: red.busy_s},
                                         rel=1e-6)
    assert prog.scope_kernel_s == pytest.approx(
        {pt.UNSCOPED: red.kernel_s}, rel=1e-6)
    assert prog.span_s == {} and prog.span_n == {}
    assert prog.idle_s == pytest.approx(
        {"dispatch/unspanned": red.gap_s["dispatch"]}, rel=1e-6)


@pytest.mark.parametrize("metric", ["apply.host_ms", "apply.launch_host_ms",
                                    "featurize.grads_us",
                                    "featurize.relayout_us"])
def test_new_readers_are_silent_without_program_markers(metric):
    prog = pt.reduce_file(DATA / "small.xplane.pb")
    assert _read(metric, prog, calls=1, rows=64) is None
    assert _read(metric, None, calls=1, rows=64) is None


# ------------------------------------------- traces with program markers

def test_apply_fixture_program_spans():
    """One warm eager call, read by hand: ``repro.ops.sketch_apply`` ran
    300.04944 ms and its launch span ``repro.kernel.flashsketch_fwd``
    298.341539 ms, both inside the 300.989591 ms window; the kernel is
    not scoped (an eager op is its own program)."""
    red = tr.reduce_file(DATA / "apply_program.xplane.pb")
    prog = pt.reduce_file(DATA / "apply_program.xplane.pb")
    assert prog.span_n == {name: 1 for name in (
        "repro.ops.sketch_apply", "repro.lowering.lower",
        "repro.lowering.execute", "repro.lowering.pad",
        "repro.kernel.tables", "repro.kernel.stream",
        "repro.kernel.flashsketch_fwd")}
    assert prog.span_s["repro.ops.sketch_apply"] == pytest.approx(
        300.04944e-3, rel=1e-9)
    assert _read("apply.host_ms", prog, calls=1) == pytest.approx(
        300.04944, rel=1e-9)
    assert _read("apply.launch_host_ms", prog, calls=1) == pytest.approx(
        298.341539, rel=1e-9)
    assert set(prog.scope_s) == {pt.UNSCOPED}
    assert prog.window_s == pytest.approx(red.window_s, rel=1e-6)
    idle = red.window_s - red.busy_s
    assert sum(prog.idle_s.values()) == pytest.approx(idle, rel=1e-6)
    spanned = sum(v for k, v in prog.idle_s.items()
                  if not k.endswith("/" + pt.UNSPANNED))
    assert spanned > 0.99 * idle
    top = max(prog.idle_s, key=prog.idle_s.get)
    assert top == "dispatch/repro.kernel.flashsketch_fwd"
    assert prog.breakdown()[0] == [top, prog.idle_s[top]]


def test_kernel_starts_inside_its_launch_span():
    """The host span and the device op share one clock: the kernel
    (1 device op, 334894212 ns) starts after its launch span starts
    (40249394 ns) and before it ends."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(DATA / "apply_program.xplane.pb"))
    launch = kernel = None
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "repro.kernel.flashsketch_fwd":
                    launch = (int(ev.start_ns), int(ev.end_ns))
                elif tr.DEVICE_PLANE.match(plane.name) and \
                        line.name == tr.OPS_LINE and tr.is_kernel(ev.name):
                    kernel = int(ev.start_ns)
    assert launch == (40249394, 40249394 + 298341539)
    assert kernel == 334894212
    assert launch[0] < kernel < launch[1]


def test_grass_fixture_program_scopes():
    """One featurize over 256 examples (4 chunks), read by hand from the
    ops' ``tf_op`` stats: 195.069532 µs of leaf ops under
    ``repro.grass.grads``; 2397.447266 µs under ``repro.grass.sketch``,
    of which the gather kernel is 1559.411094 µs."""
    red = tr.reduce_file(DATA / "grass_program.xplane.pb")
    prog = pt.reduce_file(DATA / "grass_program.xplane.pb")
    assert prog.under_scope("repro.grass.grads") == pytest.approx(
        195.069532e-6, rel=1e-9)
    assert prog.under_scope("repro.grass.sketch") == pytest.approx(
        2397.447266e-6, rel=1e-9)
    assert prog.under_scope("repro.grass.sketch", kernels=True) == \
        pytest.approx(1559.411094e-6, rel=1e-9)
    assert sum(prog.scope_s.values()) == pytest.approx(red.busy_s, rel=1e-4)
    assert _read("featurize.grads_us", prog, rows=256) == pytest.approx(
        195.069532 / 256, rel=1e-9)
    assert _read("featurize.relayout_us", prog, rows=256) == pytest.approx(
        (2397.447266 - 1559.411094) / 256, rel=1e-9)
    kernel_path = max(prog.scope_kernel_s, key=prog.scope_kernel_s.get)
    assert kernel_path == ("repro.grass.sketch/repro.ops.sketch_apply/"
                           "repro.ops.sketch_apply_indexed/"
                           "repro.lowering.execute/"
                           "repro.kernel.flashsketch_fwd_gather")
    assert prog.span_n == {"repro.grass.featurize": 1}
    assert _read("apply.host_ms", prog, calls=1) is None


def test_readers_find_the_runs_trace(tmp_path, monkeypatch):
    """In a run, the readers read the newest trace under the harness's
    output directory, once for all of them."""
    trace_dir = tmp_path / "trace" / "plugins" / "profile" / "run"
    trace_dir.mkdir(parents=True)
    shutil.copyfile(DATA / "grass_program.xplane.pb",
                    trace_dir / "host.xplane.pb")
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)
    red = tr.reduce_dir(tmp_path / "trace")
    ctx = types.SimpleNamespace(trace=red, counts={"rows": 256})
    read = harness.load_module("layer_metrics", "featurize.grads_us").read
    assert read(ctx) == pytest.approx(195.069532 / 256, rel=1e-9)
    assert pt.of(ctx) is pt.of(ctx)
    assert pt.of(types.SimpleNamespace(trace=None, counts={})) is None
