"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
per-operation device time, Pallas kernel time and idle gaps labelled by
the benchmark's own host spans.

* Busy time is the union of the intervals of the operations on a chip's
  ``XLA Ops`` line, inside the benchmark's ``window`` span, averaged over
  the chips used.
* A Pallas kernel is an operation whose HLO instruction is a custom call
  to ``tpu_custom_call``; no function name of the program is consulted.
* Per-operation time counts leaf operations only: a control-flow op (the
  ``while`` of a scan) spans the operations of its body.
* An operation is named by its program (the ``XLA Modules`` event that
  holds it, without the fingerprint), its HLO instruction name and its
  opcode, e.g. ``jit_wrapped/tpu_custom_call.1 custom-call``.
* Every idle gap inside the window is labelled with the benchmark span
  (``dispatch``, ``wait_result``, ``generator_sleep``, ...) that overlaps
  it most, or ``untraced``.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# "%name = <shape> opcode(operands), attributes"
HLO = re.compile(r"^%?(?P<name>[^\s=]+) = .*?\s(?P<op>[a-z][a-z0-9-]*)\(")
WINDOW_SPAN = "window"
SPANS = ("window", "operand_prep", "dispatch", "wait_result", "solve",
         "featurize", "generator_sleep", "submit")


@dataclasses.dataclass
class Reduction:
    busy_s: float                  # mean over chips
    window_s: float
    op_s: Dict[str, float]         # op name -> seconds, mean over chips
    kernel_s: float                # Pallas custom calls, mean over chips
    gap_s: Dict[str, float]        # host span -> idle seconds, mean

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(self.op_s), "idle_gaps": top(self.gap_s)}


def is_kernel(hlo: str) -> bool:
    """Whether the HLO text of a device op is a Pallas (Mosaic) kernel."""
    m = HLO.match(hlo)
    return bool(m) and m.group("op") == "custom-call" \
        and 'custom_call_target="tpu_custom_call"' in hlo


def op_name(hlo: str, module: str) -> str:
    m = HLO.match(hlo)
    short = f"{m.group('name')} {m.group('op')}" if m else hlo[:80]
    return f"{module.split('(')[0]}/{short}" if module else short


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap(a0, a1, b0, b1) -> int:
    return max(0, min(a1, b1) - max(a0, b0))


def _label_gaps(gaps, labels) -> List[str]:
    """For each gap (sorted), the span name that overlaps it most.  Spans
    of one name come from one thread, one after another."""
    by_name: Dict[str, List[Tuple[int, int]]] = {}
    for s0, s1, name in labels:
        by_name.setdefault(name, []).append((s0, s1))
    best = [(0, "untraced")] * len(gaps)
    for name, ivs in by_name.items():
        ivs.sort()
        j = 0
        for gi, (g0, g1) in enumerate(gaps):
            while j < len(ivs) and ivs[j][1] <= g0:
                j += 1
            ov, k = 0, j
            while k < len(ivs) and ivs[k][0] < g1:
                ov += _overlap(g0, g1, *ivs[k])
                k += 1
            if ov > best[gi][0]:
                best[gi] = (ov, name)
    return [name for _, name in best]


def reduce_file(path, devs: int = 1) -> Reduction:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    spans: List[Tuple[int, int, str]] = []
    device_planes = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            device_planes.append((int(m.group(1)), plane))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SPANS:
                    spans.append((int(ev.start_ns), int(ev.end_ns), ev.name))
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no '{WINDOW_SPAN}' span in the trace")
    w0, w1 = windows[0][0], windows[0][1]
    labels = [s for s in spans if s[2] != WINDOW_SPAN]
    device_planes = [p for _, p in sorted(device_planes, key=lambda x: x[0])]
    device_planes = device_planes[:devs]
    if not device_planes:
        raise ValueError(f"{path}: no TPU device plane in the trace")

    busy = kernel = 0.0
    op_s: Dict[str, float] = {}
    gap_s: Dict[str, float] = {}
    for plane in device_planes:
        lines = {line.name: line for line in plane.lines}
        modules = sorted((int(ev.start_ns), int(ev.end_ns), ev.name)
                         for ev in (lines[MODULES_LINE].events
                                    if MODULES_LINE in lines else ()))
        events = []
        for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
            s, e = int(ev.start_ns), int(ev.end_ns)
            if _overlap(s, e, w0, w1) > 0:
                events.append((max(s, w0), min(e, w1), ev.name))
        events.sort(key=lambda x: (x[0], -x[1]))
        intervals = [(s, e) for s, e, _ in events]
        j = 0
        for i, (s, e, hlo) in enumerate(events):
            # a control-flow op (a scan's while) spans the ops of its
            # body: only the leaves are counted per op
            if i + 1 < len(events) and events[i + 1][0] < e \
                    and events[i + 1][1] <= e:
                continue
            while j < len(modules) and modules[j][1] < s:
                j += 1
            module = modules[j][2] if j < len(modules) and \
                modules[j][0] <= s else ""
            name = op_name(hlo, module)
            op_s[name] = op_s.get(name, 0.0) + (e - s) * 1e-9
            if is_kernel(hlo):
                kernel += (e - s) * 1e-9
        merged = _union(intervals)
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                if g1 > g0]
        for (g0, g1), label in zip(gaps, _label_gaps(gaps, labels)):
            gap_s[label] = gap_s.get(label, 0.0) + (g1 - g0) * 1e-9
    k = len(device_planes)
    return Reduction(busy_s=busy / k, window_s=(w1 - w0) * 1e-9,
                     op_s={n: v / k for n, v in op_s.items()},
                     kernel_s=kernel / k,
                     gap_s={n: v / k for n, v in gap_s.items()})


def newest_trace(directory) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def reduce_dir(directory, devs: int = 1) -> Reduction:
    return reduce_file(newest_trace(directory), devs)
