"""Reduction of a profiler trace to what the per-layer metrics read.

A traced run writes an ``.xplane.pb`` file; :func:`load` reads it with
``jax.profiler.ProfileData`` into a :class:`Trace`:

* ``ops``: per device, the operations that ran on it, as
  ``(name, start_ns, end_ns)`` from the device plane's ``XLA Ops`` line;
* ``spans``: the benchmark's own host spans (``TraceAnnotation`` names
  that start with ``chipbench.``) as ``(name, start_ns, end_ns)``;
* ``op_text``: per operation name, the text of its stats (the HLO
  instruction, its source op), which tells a kernel's kind where the name
  alone does not.

Everything else here is plain arithmetic on such lists, so that the tests
can check it on hand-built events: the union of busy intervals, the busy
time inside a window, the idle gaps, each gap's attribution to the host
span that covers it, and device time per operation name.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]

#: prefix of every host span the benchmark writes
SPAN_PREFIX = "chipbench."

#: the device plane line that holds one event per executed operation
OPS_LINE = "XLA Ops"

#: a gap that no benchmark span covers is attributed to this name
OUTSIDE = "outside spans"


@dataclasses.dataclass
class Trace:
    """Device operations per device and the benchmark's host spans."""

    ops: Dict[str, List[Event]]
    spans: List[Event]
    op_text: Dict[str, str] = dataclasses.field(default_factory=dict)

    def spans_named(self, name: str) -> List[Event]:
        """Host spans called ``chipbench.<name>``, in time order."""
        full = SPAN_PREFIX + name
        return sorted((s for s in self.spans if s[0] == full),
                      key=lambda s: s[1])

    def window(self) -> Optional[Interval]:
        """(start, end) of the measured window's span, if it was traced."""
        w = self.spans_named("window")
        return (w[0][1], w[-1][2]) if w else None


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint intervals."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def clip(intervals: Sequence[Interval], lo: float,
         hi: float) -> List[Interval]:
    """The parts of ``intervals`` that lie inside [lo, hi]."""
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def covered(union: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of [lo, hi] covered by the disjoint intervals ``union``."""
    return sum(b - a for a, b in clip(union, lo, hi))


def gaps(union: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that the disjoint ``union`` leaves free."""
    out, cur = [], lo
    for a, b in clip(union, lo, hi):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        out.append((cur, hi))
    return out


def attribute(gap_list: Sequence[Interval],
              spans: Sequence[Event]) -> Dict[str, float]:
    """Total gap length per host span: each gap goes to the shortest span
    that covers its midpoint (the innermost), or to :data:`OUTSIDE`."""
    out: Dict[str, float] = defaultdict(float)
    for a, b in gap_list:
        mid = 0.5 * (a + b)
        best = None
        for name, s, e in spans:
            if s <= mid <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        out[best[0] if best else OUTSIDE] += b - a
    return dict(out)


def durations(events: Sequence[Event], lo: float = float("-inf"),
              hi: float = float("inf")) -> Dict[str, float]:
    """Summed duration per event name, of the parts inside [lo, hi]."""
    out: Dict[str, float] = defaultdict(float)
    for name, s, e in events:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            out[name] += b - a
    return dict(out)


def busy(trace: Trace, lo: float, hi: float) -> Dict[str, float]:
    """Per device, the time in [lo, hi] in which some operation ran."""
    return {dev: covered(merge([(s, e) for _, s, e in evs]), lo, hi)
            for dev, evs in trace.ops.items()}


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` file (or the newest under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    text: Dict[str, str] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = ops[plane.name] = []
                for ev in line.events:
                    evs.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
                    if ev.name not in text:
                        text[ev.name] = " ".join(
                            f"{k}={v}" for k, v in ev.stats)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return Trace(ops={k: v for k, v in ops.items() if v}, spans=spans,
                 op_text=text)


def idle_share(trace: Trace) -> Optional[float]:
    """1 - busy / window over the traced window, averaged over devices;
    None when the window or the devices are missing from the trace."""
    win = trace.window()
    if win is None or not trace.ops or win[1] <= win[0]:
        return None
    b = busy(trace, *win)
    return 1.0 - sum(b.values()) / len(b) / (win[1] - win[0])
