#!/usr/bin/env python3
"""What the program writes into a profiler trace: its own host spans and
the layer scopes of its device operations.

The program names its host phases with ``repro.obs.metrics.span``
(``serve.tick``, ``serve.stage``, ..., ``sparsify.call``, ...): while a
trace is being collected each span is a ``TraceAnnotation`` on a host
line.  Its device layers carry ``jax.named_scope`` names (``level1``,
``level2``, ``edge_scan``, ``degrees``) in the op-name metadata of every
operation they lower to.  A TPU trace's operation events carry no such
stat, only the HLO instruction (``%fusion.6 = f32[...] fusion(...)``)
inside a program's ``XLA Modules`` event (``jit_f(<program id>)``); the
profiler keeps each program's compiled HLO, metadata included, in the
``/host:metadata`` plane, and :func:`hlo_op_names` reads the op name of
every instruction from there.  :func:`load` reads both into a
:class:`Layers`:

* ``program_spans``: host events named ``serve.*`` or ``sparsify.*``, as
  ``(name, start_ns, end_ns)``;
* ``ops``: per device, every operation of the ``XLA Ops`` line as
  ``(op-name path, start_ns, end_ns)``.

A trace of a program that writes neither yields empty lists, and every
reader here then returns None: the metrics that read them leave the line.

    python3 chipbench/layers.py <trace dir or .xplane.pb>

prints, for the measured window, the device-busy time under each layer
scope, the idle gaps by the innermost span (the benchmark's and the
program's), and the share of device-busy time between each group's
``serve.dispatch`` and ``serve.readback`` or inside ``sparsify.call``.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    # run as a script: import the package from the checkout's root, and
    # keep this directory (whose trace.py would shadow the standard
    # library's) off the path
    _here = Path(__file__).resolve().parent
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != _here]
    sys.path.insert(0, str(_here.parent))

from chipbench import trace as _trace  # noqa: E402

Event = Tuple[str, float, float]

#: name prefixes of the program's own host spans
PROGRAM_PREFIXES = ("serve.", "sparsify.")

#: the device layer scopes the program writes
SCOPES = ("level1", "level2", "edge_scan", "degrees")

#: stats of a device event that may hold its op-name path, first found wins
PATH_STATS = ("tf_op", "op_name")

#: the device plane line of program executions
MODULES_LINE = "XLA Modules"

_INSTR = re.compile(r"%?([^\s=]+)")

_SPLIT = re.compile(r"[/()]")


@dataclasses.dataclass
class Layers:
    """The program's host spans and the scope paths of device operations."""

    program_spans: List[Event]
    ops: Dict[str, List[Event]]

    def spans_named(self, name: str) -> List[Event]:
        """Program spans called ``name``, in time order."""
        return sorted((s for s in self.program_spans if s[0] == name),
                      key=lambda s: s[1])


def has_scope(path: str, scope: str) -> bool:
    """True when ``scope`` is one of the path's components
    (``jit(f)/vmap(level1)/jit(g)/level1/dot_general`` holds ``level1``)."""
    return scope in _SPLIT.split(path)


def _span_name(name: str) -> str:
    """A ``TraceMe`` name with its encoded metadata (``name#k=v#``) cut."""
    return name.split("#", 1)[0]


# --------------------------------------------------------------------- #
# the profiler's protobufs, read by field number (no generated classes):
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map entry:
# key 1, value 2), .stat_metadata = 5; XEventMetadata.name = 2,
# .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
# .bytes_value = 6; HloProto.hlo_module = 1; HloModuleProto.computations
# = 3; HloComputationProto.instructions = 2; HloInstructionProto.name =
# 1, .metadata = 7; OpMetadata.op_name = 2
# --------------------------------------------------------------------- #
def _fields(buf: bytes):
    """(field number, value) of each field of one protobuf message; a
    varint as an int, a length-delimited field as bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            val, i = buf[i:i + width], i + width
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, val


def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _field(buf: bytes, num: int, default=b""):
    return next((v for f, v in _fields(buf) if f == num), default)


def hlo_protos(path: str) -> Dict[str, bytes]:
    """Per program, named as its ``XLA Modules`` events are
    (``jit_f(<program id>)``), the serialized ``HloProto`` the profiler
    kept in the ``/host:metadata`` plane; empty when it kept none."""
    with open(path, "rb") as f:
        space = memoryview(f.read())     # slices share the file's bytes
    out: Dict[str, bytes] = {}
    for num, plane in _fields(space):
        if num != 1 or _field(plane, 2) != b"/host:metadata":
            continue
        names = {}
        for f, entry in _fields(plane):
            if f == 5:
                meta = _field(entry, 2)
                names[_field(entry, 1, 0)] = bytes(_field(meta, 2)).decode()
        for f, entry in _fields(plane):
            if f != 4:
                continue
            meta = _field(entry, 2)
            for g, stat in _fields(meta):
                if g == 5 and names.get(_field(stat, 1, 0)) == "Hlo Proto":
                    out[bytes(_field(meta, 2)).decode()] = _field(stat, 6)
    return out


def hlo_op_names(proto: bytes) -> Dict[str, str]:
    """Instruction name -> op-name metadata, over every computation (loop
    bodies and fused computations too) of one serialized ``HloProto``."""
    out = {}
    for comp_num, comp in _fields(_field(proto, 1)):
        if comp_num != 3:
            continue
        for f, instr in _fields(comp):
            if f == 2:
                out[bytes(_field(instr, 1)).decode()] = bytes(_field(
                    _field(instr, 7), 2)).decode()
    return out


def _program(raw: Dict[str, bytes], prog: str) -> Optional[str]:
    """The key of ``raw`` for the program ``prog`` (``jit_f(<id>)``): the
    same name, else the one program of that module name, else None."""
    if prog in raw:
        return prog
    base = prog.split("(", 1)[0]
    same = [k for k in raw if k.split("(", 1)[0] == base]
    return same[0] if len(same) == 1 else None


def _op_paths(evs, modules, raw, parsed, cache) -> List[Event]:
    """Each operation event as ``(op-name path, start, end)``: its path
    from its stats when they hold one, else from the HLO (``raw``, parsed
    once per program into ``parsed``) of the program whose ``XLA
    Modules`` event encloses it."""
    out, mods, m = [], sorted(modules, key=lambda e: e[1]), 0
    for ev in evs:
        start, end = ev.start_ns, ev.start_ns + ev.duration_ns
        while m < len(mods) and mods[m][2] < start:
            m += 1
        prog = mods[m][0] if m < len(mods) and mods[m][1] <= start else ""
        key = (prog, ev.name)
        path = cache.get(key)
        if path is None:
            stats = dict(ev.stats)
            path = next((str(stats[k]) for k in PATH_STATS if k in stats),
                        None)
            if path is None:
                if prog not in parsed:
                    found = _program(raw, prog)
                    parsed[prog] = hlo_op_names(raw[found]) if found else {}
                path = parsed[prog].get(_INSTR.match(ev.name).group(1), "")
            cache[key] = path
        out.append((path, start, end))
    return out


def load(path: str) -> Layers:
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
    raw, parsed, cache = hlo_protos(path), {}, {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if _trace.OPS_LINE not in lines:
                continue
            modules = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in (lines[MODULES_LINE].events
                                  if MODULES_LINE in lines else [])]
            ops[plane.name] = _op_paths(lines[_trace.OPS_LINE].events,
                                        modules, raw, parsed, cache)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIXES):
                        spans.append((_span_name(ev.name), ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return Layers(program_spans=spans,
                  ops={k: v for k, v in ops.items() if v})


def of(ctx: dict) -> Optional[Layers]:
    """The :class:`Layers` of a metric's context: ``ctx["layers"]`` when
    given, else read once from the cell's trace directory and kept there
    for the cell's other metrics; None when there is no trace to read."""
    if "layers" not in ctx:
        from chipbench.harness import TRACE_DIR
        try:
            ctx["layers"] = load(str(TRACE_DIR / ctx["spec"]["cell"]["name"]))
        except (FileNotFoundError, OSError):
            ctx["layers"] = None
    return ctx["layers"]


def busy_union(layers: Layers) -> List[Tuple[float, float]]:
    """Union over all devices of the intervals in which an operation ran."""
    return _trace.merge([(s, e) for evs in layers.ops.values()
                         for _, s, e in evs])


def scope_time(layers: Layers, scope: str, lo: float,
               hi: float) -> Dict[str, float]:
    """Per device, the time in [lo, hi] covered by operations whose path
    holds ``scope``: the union of their intervals, so that an operation
    nested in another (a loop body inside its ``while``) counts once."""
    return {dev: _trace.covered(_trace.merge(
                [(s, e) for p, s, e in evs if has_scope(p, scope)]), lo, hi)
            for dev, evs in layers.ops.items()}


def span_time(layers: Layers, name: str, lo: float, hi: float) -> float:
    """Summed length inside [lo, hi] of the program spans called ``name``."""
    return sum(_trace.durations(layers.spans_named(name), lo, hi).values())


def idle_inside(layers: Layers, names: Sequence[str], lo: float,
                hi: float) -> float:
    """Time inside [lo, hi] that lies under a program span called one of
    ``names`` while no operation ran on any device."""
    inside = _trace.merge([(s, e) for n, s, e in layers.program_spans
                           if n in names])
    busy = busy_union(layers)
    return sum((b - a) - _trace.covered(busy, a, b)
               for a, b in _trace.clip(inside, lo, hi))


# --------------------------------------------------------------------- #
# the reductions the per-layer metrics share
# --------------------------------------------------------------------- #
def span_ms_per_tick(ctx: dict, name: str) -> Optional[float]:
    """Summed time of program span ``name`` inside the window over the
    window's ticks (ms); None without ticks, without such spans, or on a
    trace with no device plane (a host without the chip: its host times
    are not the chip's host times)."""
    tr, lay = ctx["trace"], of(ctx)
    win, ticks = tr.window(), tr.spans_named("tick")
    if (lay is None or win is None or not ticks or not tr.ops
            or not lay.spans_named(name)):
        return None
    return span_time(lay, name, *win) / len(ticks) / 1e6


def scope_ms(ctx: dict, scope: str, count: int) -> Optional[float]:
    """``scope_time`` on the device that spent most, inside the window,
    over ``count`` ticks or calls (ms); None when no operation holds it."""
    tr, lay = ctx["trace"], of(ctx)
    win = tr.window()
    if lay is None or win is None or not count:
        return None
    spent = max(scope_time(lay, scope, *win).values(), default=0.0)
    if spent <= 0:
        return None
    return spent / count / 1e6


# --------------------------------------------------------------------- #
# the report
# --------------------------------------------------------------------- #
def report(tr: _trace.Trace, lay: Layers) -> dict:
    """Where the window's device time and idle time go, by layer scope
    and by innermost span (seconds, averaged over devices, and shares)."""
    win = tr.window()
    if win is None or not lay.ops:
        return {}
    lo, hi = win
    ndev = len(lay.ops)
    busy = _trace.busy(tr, lo, hi)
    total_busy = sum(busy.values())
    out: dict = {"window_s": (hi - lo) / 1e9,
                 "busy_s": total_busy / ndev / 1e9}
    scoped = {s: sum(scope_time(lay, s, lo, hi).values()) for s in SCOPES}
    anyscope = sum(_trace.covered(_trace.merge(
        [(s, e) for p, s, e in evs
         if any(has_scope(p, sc) for sc in SCOPES)]), lo, hi)
        for evs in lay.ops.values())
    out["busy_by_scope_s"] = {s: v / ndev / 1e9 for s, v in scoped.items()}
    out["busy_unscoped_share"] = (1.0 - anyscope / total_busy
                                  if total_busy else None)
    spans = tr.spans + lay.program_spans
    idle: Dict[str, float] = {}
    for evs in lay.ops.values():
        union = _trace.merge([(s, e) for _, s, e in evs])
        for k, v in _trace.attribute(_trace.gaps(union, lo, hi),
                                     spans).items():
            idle[k] = idle.get(k, 0.0) + v / ndev / 1e9
    out["idle_by_span_s"] = dict(sorted(idle.items(), key=lambda kv: -kv[1]))
    for outer, prefix in (("chipbench.tick", "serve."),
                          ("chipbench.call", "sparsify.")):
        # idle time inside the benchmark's span, and the part of it whose
        # innermost span is one of the program's
        inner = [s for s in spans if s[0] == outer or
                 s[0].startswith(prefix)]
        got: Dict[str, float] = {}
        for evs in lay.ops.values():
            union = _trace.merge([(s, e) for _, s, e in evs])
            for k, v in _trace.attribute(_trace.gaps(union, lo, hi),
                                         inner).items():
                got[k] = got.get(k, 0.0) + v
        tot = sum(v for k, v in got.items() if k != _trace.OUTSIDE)
        if tot:
            out[f"idle_in_{outer}_under_{prefix}share"] = sum(
                v for k, v in got.items() if k.startswith(prefix)) / tot
    # the shared clock: device-busy time between a group's dispatch and
    # its readback, or inside a sparsifier call
    disp, back = lay.spans_named("serve.dispatch"), lay.spans_named(
        "serve.readback")
    if disp and back:
        ends = [b[2] for b in back]
        spans_ = []
        for _, s, _e in disp:
            nxt = next((e for e in ends if e >= s), None)
            if nxt is not None:
                spans_.append((s, nxt))
        out["busy_in_dispatch_to_readback_share"] = _covered_share(
            lay, _trace.merge(spans_), lo, hi, total_busy)
    calls = lay.spans_named("sparsify.call")
    if calls:
        out["busy_in_sparsify_call_share"] = _covered_share(
            lay, _trace.merge([(s, e) for _, s, e in calls]), lo, hi,
            total_busy)
    return out


def _covered_share(lay, intervals, lo, hi, total_busy):
    """Share of the window's device-busy time inside ``intervals``."""
    if not total_busy:
        return None
    inside = 0.0
    for evs in lay.ops.values():
        union = _trace.merge([(s, e) for _, s, e in evs])
        inside += sum(_trace.covered(union, a, b)
                      for a, b in _trace.clip(intervals, lo, hi))
    return inside / total_busy


def main(argv) -> int:
    path = argv[0]
    print(json.dumps(report(_trace.load(path), load(path)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
