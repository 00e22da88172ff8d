"""The program's own markers in a profiler trace (``.xplane.pb``): its host
spans and its named scopes, which ``trace.py`` does not read.

The program under test (``repro.tracing``) names its steps
``repro.<layer>.<step>``:

* A program span is a host event of that name.  Its seconds and count
  inside the benchmark's ``window`` span go to ``span_s`` / ``span_n``.
  Each instant of a device idle gap goes to the innermost program span
  open then (spans of one thread nest), keyed ``<benchmark span>/<program
  span>`` (the benchmark span that overlaps the gap most, as in
  ``trace.py``), or ``<benchmark span>/unspanned``: ``idle_s``.
* A program scope is a ``repro.`` segment of a device op's ``tf_op`` stat
  (its op-name path, named scopes included), which sits in the event
  metadata of the raw ``XSpace``; ``jax.profiler.ProfileData`` does not
  expose it, so this module reads the file itself, with a message class
  for the few fields of ``xplane.proto`` it needs.  A leaf op's device
  time goes to its scope path (``repro.grass.sketch/repro.ops.sketch_apply
  /...``), or to ``unscoped``: ``scope_s``, and ``scope_kernel_s`` for
  Pallas kernels alone.

Times are kept in picoseconds, as the file holds them, and reported in
seconds; device seconds are the mean over the chips that ran an operation
in the window.  A trace of a program without markers reads as all
``unspanned`` and ``unscoped``.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import harness

_tr = harness.load_module("", "trace")

PROGRAM = "repro."
SCOPE = re.compile(r"repro\.[A-Za-z0-9_.]*[A-Za-z0-9_]")
UNSCOPED = "unscoped"
UNSPANNED = "unspanned"
PS = 1e-12


@dataclasses.dataclass
class Program:
    window_s: float
    span_s: Dict[str, float]       # program span -> host seconds in window
    span_n: Dict[str, int]         # program span -> count in window
    idle_s: Dict[str, float]       # "<bench span>/<program span>" -> s
    scope_s: Dict[str, float]      # scope path -> device seconds, leaves
    scope_kernel_s: Dict[str, float]   # the same, Pallas kernels only

    def under_scope(self, name: str, kernels: bool = False) -> float:
        """Device seconds of the ops whose scope path holds ``name``
        (Pallas kernels only with ``kernels``)."""
        table = self.scope_kernel_s if kernels else self.scope_s
        return sum(v for path, v in table.items()
                   if name in path.split("/"))

    def breakdown(self) -> list:
        """The top idle seconds by program span, then the top device
        seconds by scope path, as ``[name, seconds]`` pairs."""
        def top(d):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:10]]
        return top(self.idle_s) + top(self.scope_s)


def scope_path(tf_op: str) -> str:
    """The program scopes in a device op's op-name path, outermost first
    (``jit(f)/while/body/repro.grass.sketch/.../pallas_call`` ->
    ``repro.grass.sketch/...``), or ``unscoped``."""
    path: List[str] = []
    for name in SCOPE.findall(tf_op or ""):
        if not path or path[-1] != name:
            path.append(name)
    return "/".join(path) or UNSCOPED


@dataclasses.dataclass
class Space:
    """What this module needs of an ``XSpace``: host events as
    ``(start_ps, end_ps, name)``, and per TPU plane the events of its
    ``XLA Ops`` line as ``(start_ps, end_ps, hlo, tf_op)``."""
    host: List[Tuple[int, int, str]]
    device: Dict[str, List[Tuple[int, int, str, str]]]


def read_space(path) -> Space:
    space = _xspace_message()()
    space.ParseFromString(Path(path).read_bytes())
    host: List[Tuple[int, int, str]] = []
    device: Dict[str, List[Tuple[int, int, str, str]]] = {}
    for plane in space.planes:
        is_host = plane.name.startswith("/host:")
        if not is_host and not _tr.DEVICE_PLANE.match(plane.name):
            continue
        stat_names = {m.key: m.value.name for m in plane.stat_metadata}
        names: Dict[int, str] = {}
        tf_op: Dict[int, str] = {}
        for m in plane.event_metadata:
            names[m.key] = m.value.name
            for st in m.value.stats:
                if stat_names.get(st.metadata_id) == "tf_op":
                    tf_op[m.key] = st.str_value or \
                        stat_names.get(st.ref_value, "")
        for line in plane.lines:
            if not is_host and line.name != _tr.OPS_LINE:
                continue
            t0 = line.timestamp_ns * 1000
            for ev in line.events:
                s = t0 + ev.offset_ps
                name = names.get(ev.metadata_id, "")
                if is_host:
                    host.append((s, s + ev.duration_ps, name))
                else:
                    device.setdefault(plane.name, []).append(
                        (s, s + ev.duration_ps, name,
                         tf_op.get(ev.metadata_id, "")))
    return Space(host, device)


def reduce_file(path) -> Program:
    sp = read_space(path)
    windows = [e for e in sp.host if e[2] == _tr.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no '{_tr.WINDOW_SPAN}' span in the trace")
    w0, w1 = windows[0][0], windows[0][1]
    labels = [e for e in sp.host
              if e[2] in _tr.SPANS and e[2] != _tr.WINDOW_SPAN]
    program = [e for e in sp.host if e[2].startswith(PROGRAM)]
    span_s: Dict[str, float] = {}
    span_n: Dict[str, int] = {}
    for s, e, name in program:
        ov = _tr._overlap(s, e, w0, w1)
        if ov > 0:
            span_s[name] = span_s.get(name, 0.0) + ov * PS
            span_n[name] = span_n.get(name, 0) + 1
    pieces = _innermost(program)

    idle: Dict[str, float] = {}
    scope_s: Dict[str, float] = {}
    scope_kernel_s: Dict[str, float] = {}
    chips = 0
    for ops in sp.device.values():
        events = [(max(s, w0), min(e, w1), hlo, tf) for s, e, hlo, tf in ops
                  if _tr._overlap(s, e, w0, w1) > 0]
        if not events:
            continue
        chips += 1
        events.sort(key=lambda x: (x[0], -x[1]))
        for i, (s, e, hlo, tf) in enumerate(events):
            # a control-flow op (a scan's while) spans the ops of its body:
            # only the leaves are counted, as in trace.py
            if i + 1 < len(events) and events[i + 1][0] < e \
                    and events[i + 1][1] <= e:
                continue
            path = scope_path(tf)
            scope_s[path] = scope_s.get(path, 0.0) + (e - s) * PS
            if _tr.is_kernel(hlo):
                scope_kernel_s[path] = scope_kernel_s.get(path, 0.0) + \
                    (e - s) * PS
        merged = _tr._union([(s, e) for s, e, _, _ in events])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                if g1 > g0]
        labelled = list(zip(gaps, _tr._label_gaps(gaps, labels)))
        for name, v in _split_gaps(labelled, pieces).items():
            idle[name] = idle.get(name, 0.0) + v
    k = max(chips, 1)
    return Program(window_s=(w1 - w0) * PS, span_s=span_s, span_n=span_n,
                   idle_s={n: v / k for n, v in idle.items()},
                   scope_s={n: v / k for n, v in scope_s.items()},
                   scope_kernel_s={n: v / k
                                   for n, v in scope_kernel_s.items()})


_CACHE: Dict[tuple, Program] = {}


def of(ctx) -> Optional[Program]:
    """The program markers of a per-layer reader's run: ``ctx.program``
    when the caller gives one, else the newest trace of the run
    (``harness.OUT_DIR``), read once for all readers; ``None`` when the
    run was not traced."""
    if getattr(ctx, "program", None) is not None:
        return ctx.program
    if getattr(ctx, "trace", None) is None:
        return None
    path = _tr.newest_trace(harness.OUT_DIR / "trace")
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = reduce_file(path)
    return _CACHE[key]


def _innermost(spans: List[Tuple[int, int, str]]) -> List[Tuple[int, int, str]]:
    """Disjoint, sorted pieces of the spans' union, each named by the
    innermost span open over it.  A span that outlives its parent (another
    thread's) is cut at the parent's end."""
    pieces: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, int, str]] = []
    cursor = 0

    def emit(a, b, name):
        if b > a:
            pieces.append((a, b, name))

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            emit(cursor, top[1], top[2])
            cursor = top[1]
        if stack:
            emit(cursor, s, stack[-1][2])
            e = min(e, stack[-1][1])
        cursor = s
        stack.append((s, e, name))
    while stack:
        top = stack.pop()
        emit(cursor, top[1], top[2])
        cursor = top[1]
    return pieces


def _split_gaps(labelled, pieces) -> Dict[str, float]:
    """Seconds of each labelled gap (sorted, in ps) under each program span
    piece (``_innermost``), keyed ``<label>/<span>``; the rest
    ``<label>/unspanned``."""
    out: Dict[str, float] = {}
    j = 0
    for (g0, g1), label in labelled:
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        covered, k = 0, j
        while k < len(pieces) and pieces[k][0] < g1:
            ov = _tr._overlap(g0, g1, pieces[k][0], pieces[k][1])
            if ov:
                key = f"{label}/{pieces[k][2]}"
                out[key] = out.get(key, 0.0) + ov * PS
                covered += ov
            k += 1
        if g1 - g0 > covered:
            key = f"{label}/{UNSPANNED}"
            out[key] = out.get(key, 0.0) + (g1 - g0 - covered) * PS
    return out


_XSPACE = None


def _xspace_message():
    """A message class for the fields of ``tsl/profiler/protobuf/
    xplane.proto`` read here, built at run time (maps as their wire form,
    repeated key/value entries); every other field is skipped."""
    global _XSPACE
    if _XSPACE is not None:
        return _XSPACE
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    pkg = "chipbench_xplane"
    fdp = descriptor_pb2.FileDescriptorProto(
        name=f"{pkg}.proto", package=pkg, syntax="proto3")

    def message(name, *fields):
        m = fdp.message_type.add(name=name)
        for fname, number, kind, repeated in fields:
            f = m.field.add(name=fname, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if isinstance(kind, str):
                f.type, f.type_name = F.TYPE_MESSAGE, f".{pkg}.{kind}"
            else:
                f.type = kind

    message("XStat", ("metadata_id", 1, F.TYPE_INT64, False),
            ("str_value", 5, F.TYPE_STRING, False),
            ("ref_value", 7, F.TYPE_UINT64, False))
    message("XEvent", ("metadata_id", 1, F.TYPE_INT64, False),
            ("offset_ps", 2, F.TYPE_INT64, False),
            ("duration_ps", 3, F.TYPE_INT64, False))
    message("XLine", ("name", 2, F.TYPE_STRING, False),
            ("timestamp_ns", 3, F.TYPE_INT64, False),
            ("events", 4, "XEvent", True))
    message("XEventMetadata", ("name", 2, F.TYPE_STRING, False),
            ("stats", 5, "XStat", True))
    message("XStatMetadata", ("name", 2, F.TYPE_STRING, False))
    message("EventMetadataEntry", ("key", 1, F.TYPE_INT64, False),
            ("value", 2, "XEventMetadata", False))
    message("StatMetadataEntry", ("key", 1, F.TYPE_INT64, False),
            ("value", 2, "XStatMetadata", False))
    message("XPlane", ("name", 2, F.TYPE_STRING, False),
            ("lines", 3, "XLine", True),
            ("event_metadata", 4, "EventMetadataEntry", True),
            ("stat_metadata", 5, "StatMetadataEntry", True))
    message("XSpace", ("planes", 1, "XPlane", True))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    _XSPACE = message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{pkg}.XSpace"))
    return _XSPACE
