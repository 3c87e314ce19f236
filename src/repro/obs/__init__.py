"""Unified observability layer (DESIGN.md §15).

Three sub-layers, one import surface:

* ``obs.counters`` -- the device counter word: every fused program in
  ``kernels/kde_sampler``, ``kernels/kde_hash`` and their sharded twins
  returns a fixed-width ``(WIDTH,)`` uint32 payload whose slot 0 is the
  PR-6 status bitmask and whose remaining slots count realized device
  work (kernel evals, level-1 reads, draws, rejection retries, FAR
  samples, overflow occupancy, psums).  Words fold through scan carries
  (or slot 0, add the rest) and add ZERO collectives -- the counters are
  trace-time constants or replicated post-psum values.
* ``obs.metrics`` -- host-side trace spans and a metrics registry:
  ``Timer``/``span`` with mandatory ``block_until_ready`` fencing and
  ``jax.profiler.TraceAnnotation`` integration (spans have their own
  switch and follow the profiler by default), ``scope`` (a
  ``jax.named_scope`` decorator naming a device layer), plus counters /
  gauges / fixed-bucket histograms (deterministic p50/p99).  Near-zero
  overhead while disabled (module flag, no per-call dict churn).
* ``obs.export`` -- versioned exporters: the JSON-lines metrics stream of
  ``launch/serve.py``, a Prometheus-text dump, and the shared telemetry
  schema block every ``BENCH_*.json`` artifact carries.
"""
from repro.obs import counters, export, metrics
from repro.obs.counters import (COUNTER_SLOTS, WIDTH, counter, fold,
                                status_of, totals, word)
from repro.obs.metrics import (Timer, counter_inc, disable, enable, enabled,
                               event, gauge_set, get_registry, histogram,
                               reset, scope, set_spans, span)

__all__ = [
    "counters", "metrics", "export",
    "WIDTH", "COUNTER_SLOTS", "word", "fold", "status_of", "counter",
    "totals",
    "Timer", "span", "scope", "set_spans", "enable", "disable", "enabled",
    "reset",
    "counter_inc", "gauge_set", "histogram", "event", "get_registry",
]
