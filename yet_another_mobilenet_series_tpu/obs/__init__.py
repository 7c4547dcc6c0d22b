"""Runtime telemetry: a process-wide metrics registry, a coordinator-only
span tracer, and a stall watchdog (docs/OBSERVABILITY.md).

Three independent layers, composable and individually cheap enough to leave
on in production:

- ``registry``: typed counters/gauges/histograms unifying every ad-hoc
  runtime signal (decode failures, checkpoint barrier waits, rebuilds after
  rematerialization, forced host syncs); snapshots ride into every
  ``Logger.scalars`` row under an ``obs/`` prefix.
- ``trace``: a ring-buffered span tracer (context-manager API, monotonic
  clocks, no host<->device syncs on the hot path) emitting
  Chrome-trace/Perfetto JSON. Unlike the ``jax.profiler`` window it can stay
  on for a whole run: spans measure HOST time around dispatches.
- ``watchdog``: a heartbeat thread armed per train step; if no step (or
  eval/checkpoint progress event) lands within a configurable deadline it
  dumps ``hang_report.json`` — open spans, last completed step, registry
  snapshot, all thread stacks — before the job dies silently (a hung
  collective or a stalled input pipeline is the motivating failure mode).
- ``device``: the layer BELOW the dispatch boundary — compile-time +
  cost_analysis accounting for every AOT executable, pull-based HBM/RSS
  memory gauges, the dispatch-efficiency (achieved FLOPS) gauge, and the
  serving profiler capture (docs/OBSERVABILITY.md "Device telemetry").
"""

from . import device
from .registry import Counter, Gauge, Histogram, MetricsRegistry, get_registry
from .trace import SpanTracer, configure, get_tracer
from .watchdog import StallWatchdog

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "SpanTracer",
    "StallWatchdog",
    "configure",
    "device",
    "get_registry",
    "get_tracer",
]
