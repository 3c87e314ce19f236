"""Device-busy time per serving tick: the union of the intervals in
which an operation ran on the device, inside the window, over the
window's ticks (ms)."""
from chipbench import trace as _trace


def reduce(ctx):
    tr = ctx["trace"]
    win, ticks = tr.window(), tr.spans_named("tick")
    if win is None or not ticks or not tr.ops:
        return None
    busy = _trace.busy(tr, *win)
    return max(busy.values()) / len(ticks) / 1e6
