"""Program spans on the profiler's clock (the device-time half of
``repro.obs``).

``span(name, **meta)`` returns ``jax.profiler.TraceAnnotation(name,
**meta)``: a host span written into the profiler's own trace, on the
clock the device's events carry, so a trace reduction can charge each
device idle interval to the innermost program span over it.  With no
profiler active an annotation costs about what a
``contextlib.nullcontext`` does (under a microsecond), so the spans stay
in the code with no switch.  Where jax cannot be imported (the
simulator's numpy-only environment) ``span`` returns a ``nullcontext``.

Names are ``repro.<layer>.<what>``: the second part names the layer
(``serve`` for the entry points, ``ft`` for the FT driver, ``workload``
for the workload adapters; docs/obs_api.md has the table).  The
virtual-time :class:`repro.obs.SpanTracer` stays the simulator's
timeline.
"""
from __future__ import annotations

import contextlib
import functools

_NULL = contextlib.nullcontext()


@functools.cache
def _factory():
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return lambda name, **meta: _NULL
    return TraceAnnotation


def span(name: str, **meta):
    """A context manager that records ``name`` (with ``meta`` as its
    stats) on the profiler's host timeline while a trace is active."""
    return _factory()(name, **meta)
