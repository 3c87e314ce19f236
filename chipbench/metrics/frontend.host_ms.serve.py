"""Host time of the serving front end per tick: the benchmark's ``tick``
span (``KernelGraphServable.tick``) minus the time inside it in which an
operation ran on the device, averaged over the window's ticks (ms)."""
from chipbench import trace as _trace


def reduce(ctx):
    tr = ctx["trace"]
    ticks = tr.spans_named("tick")
    if not ticks or not tr.ops:
        return None
    union = _trace.merge([(s, e) for evs in tr.ops.values()
                          for _, s, e in evs])
    host = [(e - s) - _trace.covered(union, s, e) for _, s, e in ticks]
    return sum(host) / len(host) / 1e6
