"""Observability spine (DESIGN.md §15): spans on the profiler's clock by
default (``trace.ProfilerTracer``), a deterministic tick-clock tracer for
the tests and ``--trace-out``, a unified counters/gauges registry, Perfetto
export of tick-clock traces, and idle-time attribution. ``trace.scope``
names the ops of jitted code (``jax.named_scope``)."""

from repro.obs.export import to_chrome, write_chrome_trace
from repro.obs.registry import PROCESS, Registry
from repro.obs.report import format_report, idle_report
from repro.obs.trace import (DEFAULT, IDLE_BUCKETS, ProfilerTracer, Tracer,
                             current, host_span, install, scope, use)

__all__ = [
    "DEFAULT", "IDLE_BUCKETS", "PROCESS", "ProfilerTracer", "Registry",
    "Tracer", "current", "format_report", "host_span", "idle_report",
    "install", "scope", "to_chrome", "use", "write_chrome_trace",
]
