"""Program spans and scopes: where the sketch hot path spends its time.

``scope(name)`` opens two markers at once:

  * a host span (``jax.profiler.TraceAnnotation``) on the profiler's host
    plane, which shares its clock with the device planes — so a trace
    shows which program step the host was in while the device sat idle;
  * a named scope (``jax.named_scope``) that lands in the op-name path
    (the ``tf_op`` stat of a device trace) of every operation traced
    under it — so device time splits by the program step that emitted it.

Names follow ``repro.<layer>.<step>``: ``repro.ops.sketch_apply``,
``repro.lowering.execute``, ``repro.kernel.flashsketch_fwd``,
``repro.grass.sketch``.  There is no switch: a ``TraceAnnotation`` costs
about a microsecond when no profiler runs, and a named scope acts only
while JAX traces.  Record a trace with ``jax.profiler.trace(dir)`` and
read the spans on the host plane, the scopes in the device ops' names.

Counters live in one registry, ``repro.health.report.record``.
"""
from __future__ import annotations

import contextlib
import functools

import jax


@contextlib.contextmanager
def scope(name: str):
    """A host span and a named scope called ``name`` around the block."""
    with jax.profiler.TraceAnnotation(name), jax.named_scope(name):
        yield


def scoped(name: str):
    """Decorator form of ``scope``: the whole call runs under ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
