"""repro.obs: virtual-time tracing, metrics and per-link utilization.

One recorder (:class:`ObsRecorder`) wires through the whole FT stack —
the transport's observer list, the VirtualClock's charge hook, the
collective engine, the runtimes' step/recovery arcs — and produces:

  * a virtual-time span timeline exportable as Chrome-trace JSON
    (``python -m repro.obs trace run.json``) or a text flamegraph;
  * a counters/gauges/histograms registry snapshotted into the run
    result (``RunResult.obs_metrics`` / ``RunReport.obs_metrics``);
  * measured per-link byte/busy heat tables on priced (topo) runs.

Default off: ``SimRuntime``/``FTSession`` take ``obs=None`` and the
wired hot paths then cost one falsy check and zero allocations
(docs/obs_api.md documents the contract and the metric schema).

Separately, :func:`span` puts program spans on the profiler's clock
(``repro.obs.profiler``): the serving and FT paths carry them always, and
they land in a ``jax.profiler`` trace beside the device's events.
"""
from repro.obs.exporters import (chrome_trace, text_flamegraph,
                                 write_chrome_trace)
from repro.obs.links import LinkUsage
from repro.obs.metrics import Histogram, MetricsRegistry, time_distribution
from repro.obs.profiler import span
from repro.obs.recorder import ObsRecorder
from repro.obs.tracer import RUNTIME_TID, Span, SpanTracer

__all__ = [
    "ObsRecorder", "SpanTracer", "Span", "RUNTIME_TID",
    "MetricsRegistry", "Histogram", "time_distribution", "LinkUsage",
    "chrome_trace", "write_chrome_trace", "text_flamegraph", "span",
]
