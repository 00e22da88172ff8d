"""The benchmark's core: it finds a cell's files by name, runs the cell and
prints its result.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

    chipbench/workloads/<cell>.json     the cell: its configuration, its
                                        driver, traffic parameters, limits
    chipbench/configs/<config>.json     the deployment's sizes and guarantee
    chipbench/drivers/<driver>.py       set-up, the measured window, checks
    chipbench/layer_metrics/<name>.py   one reader per per-layer metric
    chipbench/work/<op>.py              bytes and operations an op needs

A driver module defines ``Cell(config, workload, seed)``, whose
constructor is the set-up (operands made on the device, every shape of
the window warmed), with ``window(seconds) -> WindowResult``,
``release()`` (frees the program's state) and ``checks() -> {name:
value}``, each value compared against ``workload["check"][name]``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time
import types
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent          # chipbench/
ROOT = HERE.parent                              # the checkout
CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = ROOT / ".chipbench_out"
PROCESS_T0 = time.perf_counter()

# JAX's events that mean a program was built (compiled, or loaded from the
# persistent cache), loaded from the cache, or traced
BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class NoChip(RuntimeError):
    """JAX sees no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class WindowResult:
    """What a driver's measured window hands back to the harness."""
    end_to_end: Dict[str, float]      # end-to-end metric name -> value
    attempted: int
    failed: int
    counts: Dict[str, float]          # read by per-layer metrics
    info: Dict[str, object] = dataclasses.field(default_factory=dict)


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots);
    ``kind`` empty for a file of ``chipbench/`` itself."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    mod_name = f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def peaks(kind: str) -> dict:
    """The chip's published peaks (``chipbench/peaks.json``); a device kind
    that is not in the table is an error."""
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def manifest(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


@dataclasses.dataclass
class CellSpec:
    entry: dict            # the cell's entry in BENCHMARK.json
    workload: dict         # chipbench/workloads/<cell>.json
    config: dict           # chipbench/configs/<config>.json
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_spec(man: dict, cell: str) -> CellSpec:
    entries = [w for w in man["workloads"] if w["name"] == cell]
    if not entries:
        raise KeyError(f"no cell named {cell!r} in BENCHMARK.json")
    entry = entries[0]
    workload = load_json(HERE / "workloads" / f"{cell}.json")
    if workload["config"] != entry["config"]:
        raise ValueError(f"{cell}: workload file names config "
                         f"{workload['config']!r}, BENCHMARK.json "
                         f"{entry['config']!r}")
    config = load_json(HERE / "configs" / f"{entry['config']}.json")
    e2e = [m for m in man["end_to_end"] if _applies(m, cell)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in man["per_layer"]
             if _applies(m, cell) and m["moves"] in reported]
    return CellSpec(entry, workload, config, e2e, layer)


def seed_key(seed: int):
    """A JAX PRNG key for any non-negative whole-number seed, 32 bits or
    more."""
    import jax
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout; the program reads the same variable, so it keeps its
    programs there too."""
    path = str(CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class BuildCounter:
    """Counts program builds, the builds that loaded their program from
    the persistent cache, and traces; a build that did not load compiled."""

    def __init__(self):
        import jax
        self.n = {"builds": 0, "cache_loads": 0, "traces": 0}

        def timed(event, duration, **kw):
            if event == BUILD_EVENT:
                self.n["builds"] += 1
            elif event == TRACE_EVENT:
                self.n["traces"] += 1

        def counted(event, **kw):
            if event == CACHE_HIT_EVENT:
                self.n["cache_loads"] += 1
        jax.monitoring.register_event_duration_secs_listener(timed)
        jax.monitoring.register_event_listener(counted)

    def snapshot(self) -> Dict[str, int]:
        return dict(self.n)


def chips(n: int, require_tpu: bool = True):
    """The first ``n`` devices; raises ``NoChip`` off the TPU or short of
    chips.  Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {len(devs)} {devs[0].platform!r} "
                     f"device(s)")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def device_line(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(devs) -> int:
    out = []
    for d in devs:
        stats = d.memory_stats() or {}
        out.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(out)


def span(name: str):
    """A host span in the profiler's trace, named by the benchmark."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def closed_loop(call, seconds: float):
    """Run ``call(i)`` back to back until ``seconds`` have passed; returns
    (calls completed, seconds elapsed).  ``call`` waits for its result."""
    n = 0
    t0 = time.perf_counter()
    while True:
        call(n)
        n += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return n, elapsed


def _finite(x):
    """JSON has no inf or nan: such a number is written as null."""
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    return x


def _emit(line: dict, stream) -> None:
    print(json.dumps(_finite(line)), file=stream, flush=True)


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             spec: Optional[CellSpec] = None, require_tpu: bool = True,
             out=None, err=None) -> dict:
    """One run of one cell (``spec`` defaults to the cell of
    ``BENCHMARK.json``).  Prints info lines, then the result as the last
    line of ``out``, and each compared number beside its limit as the last
    lines of ``err``.  Returns the result."""
    out = out or sys.stdout
    err = err or sys.stderr
    spec = spec if spec is not None else cell_spec(manifest(), cell)
    devs = chips(spec.entry["chips"], require_tpu)
    dev = device_line(devs)
    enable_compile_cache()
    counter = BuildCounter()
    driver = load_module("drivers", spec.workload["driver"])

    cellobj = driver.Cell(spec.config, spec.workload, seed)
    setup_s = time.perf_counter() - PROCESS_T0
    _emit({"cell": cell, "seed": seed, "setup_s": setup_s, "device": dev},
          out)

    trace_dir = OUT_DIR / "trace"
    if trace:
        import jax
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    before = counter.snapshot()
    with span("window"):
        res = cellobj.window(seconds)
    after = counter.snapshot()
    if trace:
        import jax
        jax.profiler.stop_trace()
    built = {k: after[k] - before[k] for k in after}
    res.counts.update(built)
    _emit({"cell": cell,
           "compiles_in_window": built["builds"] - built["cache_loads"],
           "cache_loads_in_window": built["cache_loads"],
           "traces_in_window": built["traces"], **res.info,
           "device": dev}, out)

    peak = memory_peak(devs)
    cellobj.release()
    values = cellobj.checks()
    limits = spec.workload["check"]
    checks = {}
    for name, limit in limits.items():
        v = values.get(name, math.nan)
        checks[name] = {"value": v, "limit": limit}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())

    device = {**dev, "memory_peak_bytes": peak}
    metrics = {}
    breakdown = None
    if trace:
        red = load_module("", "trace").reduce_dir(trace_dir, devs=len(devs))
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
        ctx = types.SimpleNamespace(
            trace=red, counts=res.counts, config=spec.config,
            workload=spec.workload, peaks=lambda: peaks(dev["kind"]),
            work=lambda op: load_module("work", op))
        for m in spec.per_layer:
            v = load_module("layer_metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        vals = dict(res.end_to_end, setup_s=setup_s)
        for m in spec.end_to_end:
            if m["name"] in vals:
                metrics[m["name"]] = {"value": vals[m["name"]],
                                      "unit": m["unit"]}

    result = {"correct": correct, "attempted": res.attempted,
              "failed": res.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=err, flush=True)
    _emit(result, out)
    return result
