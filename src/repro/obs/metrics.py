"""Trace spans + metrics registry (DESIGN.md §15.2).

Host-side telemetry with a hard performance contract:

* **Disabled by default, near-zero overhead.**  One module-level flag
  guards every recording call; while disabled, ``counter_inc`` /
  ``gauge_set`` / ``histogram(...).record`` are a single branch, and
  while spans are off ``span`` returns a shared no-op context manager --
  no dict churn, no allocation on the hot path.
* **Fenced timing.**  ``Timer`` is the one sanctioned way to time device
  work: it calls ``jax.block_until_ready`` on whatever the timed callable
  returns, so the recorded interval is realized device time, never an
  async-dispatch tail (the PR-9 bench_streaming fencing bug, made
  impossible by construction).  Both the dispatch (unfenced) and fenced
  wall times are kept so benchmarks can report async overlap.
* **Deterministic percentiles.**  Histograms use fixed log-spaced bucket
  edges; p50/p99 are cumulative-count lookups over those buckets, so two
  runs with identical samples report identical quantiles (no
  interpolation of float accumulation order).
* **xprof integration.**  Spans have a switch of their own
  (:func:`set_spans`): while spans are on, ``span(name)`` opens a
  ``jax.profiler.TraceAnnotation`` so the name shows up on the host
  timeline of a profiler trace, beside the device operations.  By default
  spans follow the profiler (on exactly while a trace is being
  collected); ``set_spans`` pins them on or off, and ``enable()`` turns
  them on.  :func:`scope` is the device-side twin: a
  ``jax.named_scope`` around a traced function, so every operation it
  lowers to carries the layer's name in its op-name metadata.
"""
from __future__ import annotations

import bisect
import functools
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation as _Annotation

_enabled = False
_lock = threading.Lock()

# One registry per process: {kind: {name: metric}}.  Flat dicts keyed by
# full metric name; labels are baked into the name by the caller
# (``serve.latency.t0.sample``) -- no per-call label-dict hashing.
_counters: Dict[str, int] = {}
_gauges: Dict[str, float] = {}
_histograms: Dict[str, "Histogram"] = {}
_events: List[Tuple[str, dict]] = []
_MAX_EVENTS = 4096


#: the spans-only switch: True on, False off, None on while a profiler
#: trace is being collected
_spans: Optional[bool] = None


def enable() -> None:
    """Turn the registry on, and spans with it (module-level flags)."""
    global _enabled, _spans
    _enabled = True
    _spans = True


def disable() -> None:
    """Turn the registry off; spans return to following the profiler."""
    global _enabled, _spans
    _enabled = False
    _spans = None


def set_spans(on: Optional[bool]) -> None:
    """The spans-only switch, independent of the registry: ``True`` opens
    a ``TraceAnnotation`` per span, ``False`` makes ``span`` the shared
    no-op, ``None`` opens them only while a profiler trace is being
    collected.  Span histograms are still recorded only while the
    registry is enabled."""
    global _spans
    _spans = on


def enabled() -> bool:
    return _enabled


def reset() -> None:
    """Drop all recorded metrics and events (tests, run boundaries)."""
    with _lock:
        _counters.clear()
        _gauges.clear()
        _histograms.clear()
        _events.clear()


def counter_inc(name: str, value: int = 1) -> None:
    """Monotone counter; no-op while disabled."""
    if not _enabled:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + int(value)


def gauge_set(name: str, value: float) -> None:
    """Last-write-wins gauge; no-op while disabled."""
    if not _enabled:
        return
    with _lock:
        _gauges[name] = float(value)


def event(name: str, **fields) -> None:
    """Append one structured event (watchdog decisions, chaos
    injections); bounded ring, no-op while disabled."""
    if not _enabled:
        return
    with _lock:
        _events.append((name, dict(fields)))
        if len(_events) > _MAX_EVENTS:
            del _events[: len(_events) - _MAX_EVENTS]


def events(prefix: str = "") -> List[Tuple[str, dict]]:
    """Snapshot of recorded events, optionally name-prefix filtered."""
    with _lock:
        return [e for e in _events if e[0].startswith(prefix)]


# Default edges: 1us .. ~100s, 4 buckets per decade (log-spaced).  Fixed
# edges => deterministic quantiles under identical sample streams.
_DEFAULT_EDGES = tuple(
    round(10.0 ** (e / 4.0), 6) for e in range(0, 4 * 8 + 1))


class Histogram:
    """Fixed-bucket histogram (values in microseconds by convention).

    ``record`` is an O(log buckets) bisect + int increment; quantiles are
    read as the upper edge of the first bucket whose cumulative count
    crosses ``q`` -- deterministic and merge-safe (counts add)."""

    __slots__ = ("edges", "counts", "total", "sum")

    def __init__(self, edges: Tuple[float, ...] = _DEFAULT_EDGES):
        self.edges = tuple(float(e) for e in edges)
        self.counts = [0] * (len(self.edges) + 1)
        self.total = 0
        self.sum = 0.0

    def record(self, value: float) -> None:
        self.counts[bisect.bisect_left(self.edges, float(value))] += 1
        self.total += 1
        self.sum += float(value)

    def quantile(self, q: float) -> float:
        """Upper bucket edge at cumulative fraction ``q`` (0 when
        empty); the last bucket reports its lower edge (unbounded)."""
        if self.total == 0:
            return 0.0
        need = q * self.total
        acc = 0
        for i, c in enumerate(self.counts):
            acc += c
            if acc >= need and c:
                return self.edges[min(i, len(self.edges) - 1)]
        return self.edges[-1]

    @property
    def p50(self) -> float:
        return self.quantile(0.50)

    @property
    def p99(self) -> float:
        return self.quantile(0.99)

    def as_dict(self) -> dict:
        return dict(count=self.total, sum=self.sum, p50=self.p50,
                    p99=self.p99)


def histogram(name: str,
              edges: Tuple[float, ...] = _DEFAULT_EDGES) -> Histogram:
    """Get-or-create the named histogram.  Recording while disabled is
    the caller's single ``if obs.enabled()`` branch; this accessor always
    returns a live histogram so exporters can read it."""
    with _lock:
        h = _histograms.get(name)
        if h is None:
            h = _histograms[name] = Histogram(edges)
        return h


def observe(name: str, value: float,
            edges: Tuple[float, ...] = _DEFAULT_EDGES) -> None:
    """Record one histogram sample; no-op while disabled."""
    if not _enabled:
        return
    histogram(name, edges).record(value)


class _NullSpan:
    """Shared no-op context manager -- the disabled-mode ``span``."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """Spans-on span: xprof TraceAnnotation (with ``meta`` as its keyword
    metadata) + elapsed histogram while the registry is enabled."""

    __slots__ = ("name", "meta", "_t0", "_ann")

    def __init__(self, name: str, meta: dict):
        self.name = name
        self.meta = meta
        self._ann = None
        self._t0 = 0.0

    def __enter__(self):
        self._ann = _Annotation(self.name, **self.meta)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        us = (time.perf_counter() - self._t0) * 1e6
        self._ann.__exit__(*exc)
        observe(f"span.{self.name}.us", us)
        return False


def span(name: str, **meta):
    """``with obs.span("serve.tick", requests=32): ...`` -- xprof-annotated
    timed region; ``meta`` (op, request count) rides in the annotation's
    keyword metadata, so the name stays fixed.  The shared no-op singleton
    while spans are off."""
    on = _spans
    if on is None:
        on = _Annotation.is_enabled()
    return _Span(name, meta) if on else _NULL_SPAN


def scope(name: str):
    """Decorator: trace the function inside ``jax.named_scope(name)``, so
    every device operation it lowers to carries ``name`` in its op-name
    metadata (``jit(f)/.../level1/...``).  Metadata only: the compiled
    program is unchanged.  A fresh scope per call (a shared
    ``named_scope`` object keeps one saved stack and cannot nest)."""
    def deco(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return deco


class Timer:
    """The sanctioned benchmark/serving timer: fenced device timing.

    ``time(fn)`` calls ``fn``, records the unfenced (dispatch) wall time,
    then ``jax.block_until_ready`` on the return value and records the
    fenced wall time.  ``timeit(fn, repeats, warmup)`` is the
    benchmarks/common loop with the fence built in -- warmup runs are
    fenced too (compiles drained off-clock).

    Results land on the instance (``wall_us`` = fenced median,
    ``dispatch_us``) and -- when the registry is enabled -- in the
    ``timer.<name>.us`` histogram.
    """

    def __init__(self, name: str):
        self.name = name
        self.wall_us: float = 0.0
        self.dispatch_us: float = 0.0
        self.samples_us: List[float] = []

    def _fence(self, out):
        import jax
        try:
            jax.block_until_ready(out)
        except (TypeError, ValueError):
            pass        # non-pytree return (host object): nothing to fence
        return out

    def time(self, fn: Callable):
        """One fenced measurement; returns ``fn``'s result."""
        with span(self.name):
            t0 = time.perf_counter()
            out = fn()
            t_disp = time.perf_counter()
            self._fence(out)
            t1 = time.perf_counter()
        self.dispatch_us = (t_disp - t0) * 1e6
        us = (t1 - t0) * 1e6
        self.wall_us = us
        self.samples_us.append(us)
        observe(f"timer.{self.name}.us", us)
        return out

    def timeit(self, fn: Callable, repeats: int = 3, warmup: int = 1,
               reduce: str = "median") -> float:
        """Fenced replacement of ``benchmarks.common.timeit``: median (or
        ``min``/``mean``) fenced wall microseconds over ``repeats``."""
        for _ in range(warmup):
            self._fence(fn())
        t = []
        for _ in range(repeats):
            self.time(fn)
            t.append(self.wall_us)
        t.sort()
        if reduce == "min":
            self.wall_us = t[0]
        elif reduce == "mean":
            self.wall_us = sum(t) / len(t)
        else:
            self.wall_us = t[len(t) // 2]
        return self.wall_us


def get_registry() -> dict:
    """Snapshot of the whole registry (exporters, tests)."""
    with _lock:
        return dict(
            enabled=_enabled,
            counters=dict(_counters),
            gauges=dict(_gauges),
            histograms={k: h.as_dict() for k, h in _histograms.items()},
            events=list(_events),
        )


def histograms() -> Dict[str, Histogram]:
    """Live histogram objects (exporters need bucket internals)."""
    with _lock:
        return dict(_histograms)
