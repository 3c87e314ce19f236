"""Device time of the collectives per sparsifier call on the mesh: the
union of the intervals of the all-reduce and collective-permute
operations (``chipbench/collectives.py``: the edge scan's one psum per
batch, the degree ring's ppermutes), inside the window, on the device
that spent most, over the window's calls (ms)."""
from chipbench import collectives


def reduce(ctx):
    tr, calls = ctx["trace"], ctx["record"].get("calls")
    win = tr.window()
    if win is None or not calls or not tr.ops:
        return None
    spent = max(collectives.time(evs, *win) for evs in tr.ops.values())
    if spent <= 0:
        return None
    return spent / calls / 1e6
